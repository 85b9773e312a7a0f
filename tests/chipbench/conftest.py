"""Fixtures of the benchmark's own tests: a throw-away checkout with
tiny cells dropped in (see _tiny.py)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
for p in (str(HERE.parents[1]), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    import _tiny

    return _tiny.make_tiny_checkout(tmp_path_factory.mktemp("chipbench"))
