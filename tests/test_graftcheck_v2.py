"""graftcheck v2 (GC006-GC009): interprocedural concurrency & lifetime
analysis — the tier-1 gate for the rules ISSUE 8 added.

Same three layers as test_graftcheck.py: (1) the fixture corpus pins
each new rule's exact findings (rule ids AND line numbers) plus the
good twin staying clean; (2) the semantic contracts that make each
rule trustworthy (re-entrant locks don't fabricate cycles, the
real-smoke marker sanctions exactly one function, the fixture corpus
is pruned from recursive scans); (3) the SELF-RUNS: the four new
rules are clean over the shipped package AND the tests tree
(the acceptance scan), and the GC009 mutation test proves the
protocol gate actually gates — perturbing one KIND_* value or one
ctypes argtypes entry in a copied tree flips the exit non-zero with
the exact rule id.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from mpistragglers_jl_tpu.tools.graftcheck import run

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_REPO, "mpistragglers_jl_tpu")
_FIX = os.path.join(_REPO, "tests", "graftcheck_fixtures")

NEW_RULES = ["GC006", "GC007", "GC008", "GC009"]


def _findings(target, **kw):
    return run([os.path.join(_FIX, target)], **kw)


def _keys(findings):
    return [(f.rule, f.line) for f in findings]


# --------------------------------------------------------------------------
# fixture corpus: exact rule ids + line numbers per checker
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "bad,expected",
    [
        (
            "gc006_bad.py",
            [("GC006", 20), ("GC006", 21), ("GC006", 38),
             ("GC006", 42), ("GC006", 46), ("GC006", 47),
             ("GC006", 62)],  # 62: the 3-lock cycle (SCC, not
            # pairwise — a->b->c->a)
        ),
        (
            "gc007_bad.py",
            [("GC007", 16), ("GC007", 23), ("GC007", 39),
             ("GC007", 45), ("GC007", 51)],
        ),
        (
            "gc008_bad_pkg",
            [("GC008", 10), ("GC008", 13),  # chaos/: OS clock in an
             # episode probe — the round-20 chaos-plane purity
             ("GC008", 13), ("GC008", 23),
             ("GC008", 9), ("GC008", 12),  # fleet/: OS clock in a
             # decision function — the round-18 control-plane purity
             ("GC008", 10), ("GC008", 13),  # qos/: OS clock in a
             # tenant-budget refill — the round-19 QoS-plane purity
             ("GC008", 4), ("GC008", 9), ("GC008", 11), ("GC008", 12),
             ("GC008", 18)],  # 18: wall sleep through `import time
            # as _t` — alias-proof matching
        ),
        (
            "gc009_bad_pkg",
            [("GC009", 1), ("GC009", 1), ("GC009", 9), ("GC009", 10),
             ("GC009", 11), ("GC009", 18), ("GC009", 22),
             ("GC009", 23), ("GC009", 27),
             ("GC009", 31)],  # 31: argtypes-but-no-restype for an
            # int64_t-returning export (c_int truncation)
        ),
    ],
)
def test_bad_fixture_exact_findings(bad, expected):
    res = _findings(bad)
    assert _keys(res.fresh) == expected, [
        f.format() for f in res.fresh
    ]
    assert not res.baselined


@pytest.mark.parametrize(
    "good",
    ["gc006_good.py", "gc007_good.py", "gc008_good_pkg",
     "gc009_good_pkg"],
)
def test_good_fixture_clean(good):
    res = _findings(good)
    assert res.fresh == [], [f.format() for f in res.fresh]


# --------------------------------------------------------------------------
# semantic contracts per rule
# --------------------------------------------------------------------------


def test_gc006_reentrant_reacquire_is_not_a_cycle():
    """The good fixture's `forward` holds _a and _b and calls a helper
    that re-enters _a (an RLock): a re-entrant acquisition of an
    already-held lock can never block, so it must create neither a
    self-deadlock finding nor a fabricated _b -> _a ordering edge
    (the bug the first cut of the edge builder had)."""
    res = _findings("gc006_good.py", rules=["GC006"])
    assert res.fresh == [], [f.format() for f in res.fresh]
    # while the SAME shape over a non-reentrant Lock is the bad
    # fixture's line-21 self-deadlock finding
    bad = _findings("gc006_bad.py", rules=["GC006"])
    assert ("GC006", 21) in _keys(bad.fresh)


def test_gc007_transfer_shapes_discharge_the_obligation():
    """Both sanctioned pin transfers — constructor escape (the
    ArenaPayload pattern) and returned control marker (the
    _MARK_RESULT pattern) — satisfy the release obligation; the
    leak-shaped twin without either is the bad fixture's line-23
    finding."""
    good = _findings("gc007_good.py", rules=["GC007"])
    assert good.fresh == []
    bad = _findings("gc007_bad.py", rules=["GC007"])
    assert ("GC007", 23) in _keys(bad.fresh)


def test_gc008_real_smoke_marker_sanctions_one_function():
    """gc008_good_pkg/checks.py carries a sub-second wall-clock assert
    inside `real_thread_smoke`, sanctioned ONLY by the
    `# graftcheck: real-smoke` marker on the line above the def —
    strip the marker and the same tree produces exactly that
    finding."""
    import ast as _ast  # noqa: F401  (parity with test_graftcheck)

    from mpistragglers_jl_tpu.tools.graftcheck.checkers import (
        gc008_wall_clock as gc008,
    )
    from mpistragglers_jl_tpu.tools.graftcheck.core import (
        load_modules,
    )

    res = _findings("gc008_good_pkg", rules=["GC008"])
    assert res.fresh == []
    mods = load_modules([os.path.join(_FIX, "gc008_good_pkg")])
    checker = gc008.WallClock()
    got = []
    for m in mods:
        if m.path.endswith("checks.py"):
            m.source = m.source.replace(
                gc008.REAL_SMOKE_MARKER, "# x"
            )
            m._lines = None  # re-split the patched source
        got += list(checker.check_module(m))
    assert [(f.rule, f.symbol) for f in got] == [
        ("GC008", "real_thread_smoke")
    ], [f.format() for f in got]


def test_gc008_applies_to_tests_root():
    """The satellite contract: the timing-margin lint actually guards
    where the flakes live. The shipped tests/ tree is clean under
    GC008 (the PR's deflake ports + the marked real smokes), and the
    fixture corpus is pruned from the recursive scan
    by its `.graftcheck-skip` marker — without the pruning this run
    would drown in deliberate fixture violations."""
    res = run([os.path.join(_REPO, "tests")], rules=["GC008"])
    assert res.fresh == [], [f.format() for f in res.fresh]
    # the fixture corpus was skipped: scanning it alone finds files,
    # and the violations they hold
    only_fix = run([_FIX], rules=["GC008"])
    assert only_fix.n_files > 0 and only_fix.fresh


def test_gc008_covers_the_fleet_package():
    """Round-18: the control plane joined the virtual-time plane — the
    shipped fleet/ package is clean under GC008's purity half
    (decision code reads only its injected clock; wall seconds enter
    via the caller's timer=), and the fixture's fleet twin pins the
    OS-clock-in-a-decision-function leak shape by line."""
    res = run([os.path.join(_PKG, "fleet")], rules=["GC008"])
    assert res.fresh == [], [f.format() for f in res.fresh]
    bad = _findings("gc008_bad_pkg", rules=["GC008"])
    fleet_hits = [
        (f.rule, f.line) for f in bad.fresh
        if os.sep + "fleet" + os.sep in f.path
    ]
    assert fleet_hits == [("GC008", 9), ("GC008", 12)], [
        f.format() for f in bad.fresh
    ]


def test_gc008_covers_the_chaos_package():
    """Round-20: the chaos plane joined the virtual-time plane — the
    shipped chaos/ package is clean under GC008's purity half (an
    episode's timing comes from the scenario's seed and the injected
    VirtualClock, never the OS clock: bit-identical replay is the
    plane's whole witness), and the fixture's chaos twin pins the
    OS-clock-in-an-episode-probe leak shape by line."""
    res = run([os.path.join(_PKG, "chaos")], rules=["GC008"])
    assert res.fresh == [], [f.format() for f in res.fresh]
    bad = _findings("gc008_bad_pkg", rules=["GC008"])
    chaos_hits = [
        (f.rule, f.line) for f in bad.fresh
        if os.sep + "chaos" + os.sep in f.path
    ]
    assert chaos_hits == [("GC008", 10), ("GC008", 13)], [
        f.format() for f in bad.fresh
    ]


def test_gc008_covers_the_qos_package():
    """Round-19: the QoS plane joined the virtual-time plane — the
    shipped qos/ package is clean under GC008's purity half (token
    buckets refill and deficit rotations advance only from the
    caller-injected ``now``), and the fixture's qos twin pins the
    OS-clock-in-a-budget-refill leak shape by line."""
    res = run([os.path.join(_PKG, "qos")], rules=["GC008"])
    assert res.fresh == [], [f.format() for f in res.fresh]
    bad = _findings("gc008_bad_pkg", rules=["GC008"])
    qos_hits = [
        (f.rule, f.line) for f in bad.fresh
        if os.sep + "qos" + os.sep in f.path
    ]
    assert qos_hits == [("GC008", 10), ("GC008", 13)], [
        f.format() for f in bad.fresh
    ]


def test_skip_marker_prunes_recursive_scans_only(tmp_path):
    """A directory holding `.graftcheck-skip` is pruned when reached
    recursively but still analyzable as an explicit root."""
    pkg = tmp_path / "tree"
    (pkg / "skipped").mkdir(parents=True)
    (pkg / "kept.py").write_text("X = 1\n")
    (pkg / "skipped" / ".graftcheck-skip").write_text("")
    (pkg / "skipped" / "mod.py").write_text("Y = 2\n")
    rec = run([str(pkg)])
    assert rec.n_files == 1
    direct = run([str(pkg / "skipped")])
    assert direct.n_files == 1


def test_gc006_clean_on_the_lock_heavy_modules():
    """The hand-audited modules the tentpole names: ProcessBackend's
    _cond/_ring_lock/_send_lock are only ever held one at a time, and
    the native Coordinator's _zlock is an RLock whose finalizer
    re-entry is sanctioned — GC006 agrees with the audit."""
    for rel in (
        os.path.join("backends", "process.py"),
        os.path.join("native", "transport.py"),
        os.path.join("sim", "clock.py"),
        "obs",
    ):
        res = run([os.path.join(_PKG, rel)], rules=["GC006"])
        assert res.fresh == [], (rel, [f.format() for f in res.fresh])


# --------------------------------------------------------------------------
# GC009: the mutation test — the gate actually gates
# --------------------------------------------------------------------------


def _mutated_tree(tmp_path, mutate):
    """Copy the real transport pair into a tmp tree and apply
    ``mutate(source) -> source`` to the .py half."""
    native = tmp_path / "native"
    native.mkdir()
    src_dir = os.path.join(_PKG, "native")
    for name in ("transport.py", "transport.cpp"):
        shutil.copy(os.path.join(src_dir, name), native / name)
    p = native / "transport.py"
    src = p.read_text()
    out = mutate(src)
    assert out != src, "mutation did not apply"
    p.write_text(out)
    return str(tmp_path)


def _cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO
    return subprocess.run(
        [sys.executable, "-m",
         "mpistragglers_jl_tpu.tools.graftcheck", *args],
        capture_output=True, text=True, cwd=_REPO, env=env,
        timeout=120,
    )


def test_gc009_mutation_kind_value_flips_exit(tmp_path):
    """Perturb one KIND_* value in a copied transport.py: the scan
    exits non-zero and names GC009 at the perturbed line."""
    tree = _mutated_tree(
        tmp_path,
        lambda s: s.replace("KIND_CONTROL = 1", "KIND_CONTROL = 9", 1),
    )
    r = _cli(tree, "--rules", "GC009", "--baseline", "none",
             "--no-cache")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "GC009" in r.stdout
    assert "KIND_CONTROL" in r.stdout


def test_gc009_mutation_argtypes_entry_flips_exit(tmp_path):
    """Perturb one ctypes argtypes entry (a 64-bit parameter narrowed
    to c_int): exit non-zero, GC009 named, the drifted function and
    argument index in the message."""
    old = (
        "    lib.msgt_coord_isend.argtypes = [\n"
        "        ctypes.c_void_p, ctypes.c_int, ctypes.c_int64, "
        "ctypes.c_int64,\n"
    )
    new = old.replace(
        "ctypes.c_int64, ctypes.c_int64,",
        "ctypes.c_int64, ctypes.c_int,",
    )
    tree = _mutated_tree(tmp_path, lambda s: s.replace(old, new, 1))
    r = _cli(tree, "--rules", "GC009", "--baseline", "none",
             "--no-cache")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "GC009" in r.stdout
    assert "msgt_coord_isend" in r.stdout
    assert "argument 3" in r.stdout


def test_gc009_unmutated_pair_is_clean(tmp_path):
    """Control: the same copy WITHOUT a mutation scans clean — the
    mutation tests above fail because of the mutation, nothing else."""
    native = tmp_path / "native"
    native.mkdir()
    src_dir = os.path.join(_PKG, "native")
    for name in ("transport.py", "transport.cpp"):
        shutil.copy(os.path.join(src_dir, name), native / name)
    res = run([str(tmp_path)], rules=["GC009"])
    assert res.fresh == [], [f.format() for f in res.fresh]


# --------------------------------------------------------------------------
# self-runs: the acceptance scans
# --------------------------------------------------------------------------


def test_new_rules_clean_on_package_and_tests_tree():
    """ISSUE 8 acceptance: `--rules GC006,GC007,GC008,GC009` runs
    clean on the package + tests tree (the fixture corpus prunes
    itself via `.graftcheck-skip`)."""
    res = run([_PKG, os.path.join(_REPO, "tests")], rules=NEW_RULES)
    assert res.fresh == [], "\n".join(f.format() for f in res.fresh)
    assert res.n_rules == 4


def test_cli_new_rules_listed_and_clean():
    rules = _cli("--list-rules")
    assert rules.returncode == 0
    for rule in NEW_RULES:
        assert rule in rules.stdout
    r = _cli(
        "mpistragglers_jl_tpu", "tests",
        "--rules", ",".join(NEW_RULES), "--no-cache", "-q",
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_new_rules_ride_the_cache(tmp_path):
    """The per-file cache machinery serves the v2 rules too: a warm
    re-run reproduces the bad fixture's findings exactly from cache
    (same identity, not just the same keys)."""
    cache = str(tmp_path / "cache.json")
    first = _findings("gc006_bad.py", cache_path=cache,
                      rules=["GC006"])
    assert os.path.exists(cache)
    second = _findings("gc006_bad.py", cache_path=cache,
                       rules=["GC006"])
    assert [f.format() for f in second.fresh] == [
        f.format() for f in first.fresh
    ]
    assert len(first.fresh) == 7


def test_gc009_is_project_wide_and_never_cached(tmp_path):
    """GC009 reads a sibling .cpp the per-file sha cache cannot key,
    so it must run live every time: mutate the .cpp (NOT the .py)
    between two cached runs and the second run must see the drift."""
    native = tmp_path / "native"
    native.mkdir()
    src_dir = os.path.join(_PKG, "native")
    for name in ("transport.py", "transport.cpp"):
        shutil.copy(os.path.join(src_dir, name), native / name)
    cache = str(tmp_path / "cache.json")
    clean = run([str(tmp_path)], rules=["GC009"], cache_path=cache)
    assert clean.fresh == []
    cpp = native / "transport.cpp"
    cpp.write_text(
        cpp.read_text().replace(
            "constexpr int64_t KIND_CONTROL = 1;",
            "constexpr int64_t KIND_CONTROL = 9;", 1,
        )
    )
    drifted = run([str(tmp_path)], rules=["GC009"], cache_path=cache)
    assert any(
        "KIND_CONTROL" in f.message for f in drifted.fresh
    ), [f.format() for f in drifted.fresh]
