"""Fixtures of the benchmark's own tests: a throw-away checkout with
tiny cells dropped in, and the guard's copy with one of everything
appended (see _tiny.py)."""

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


@pytest.fixture(scope="session")
def guard_root(tmp_path_factory):
    import _tiny

    return _tiny.make_guard_checkout(tmp_path_factory.mktemp("guard"))


CHECKOUTS = ("committed", "with_additions")


@pytest.fixture
def root_of(request):
    """``root_of(which)``: the checkout a manifest-reading test reads,
    the repository itself or the guard's copy of it."""
    def find(which: str) -> Path:
        if which == "committed":
            return HERE.parents[1]
        return request.getfixturevalue("guard_root")

    return find


@pytest.fixture(params=CHECKOUTS)
def checkout(request, root_of):
    """Every test that takes this runs twice: on the committed
    benchmark, and on a copy to which a configuration, a serving mix, a
    cell and a per-layer metric were appended as a later PR appends
    them. A test that passes on the first and fails on the second
    would have to be edited by that PR, which it may not do."""
    return root_of(request.param)
