"""graftcheck core: checker registry, suppressions, baseline, cache.

The framework half of the suite — rule-agnostic machinery that the
checkers (:mod:`.checkers`) plug into:

* :class:`Checker` + :func:`register` — the registry. A checker is
  per-file (``check_module``) or project-wide (``check_project``, for
  rules that need the whole import graph).
* ``# graftcheck: disable=GC003`` — line-level suppression, honored on
  the flagged line or the line directly above it (so a suppression can
  sit on its own line when the flagged one is full). ``disable=all``
  silences every rule for that line. Suppressed findings are dropped
  from the fresh set but still counted.
* :class:`Baseline` — a checked-in JSON of *documented false
  positives*, each entry carrying a mandatory justification. Entries
  match findings by ``(rule, path, symbol)`` — line-free, so ordinary
  refactors don't churn the file. The file is CAPPED (its own ``cap``
  field): growing it past the cap fails the run, and a stale entry
  (matching nothing) fails too — the baseline can only shrink quietly,
  never grow or rot.
* per-file result cache keyed on (content sha, tool fingerprint), plus
  a whole-tree cache for project-wide checkers keyed on the sorted
  (relpath, content sha) set and each project checker's
  :meth:`Checker.project_fingerprint` (extra inputs outside the .py
  set — GC009's sibling ``transport.cpp``). With both hot, a clean
  re-run over an unchanged tree parses NOTHING: :class:`ModuleInfo`
  defers ``ast.parse`` to first ``.tree`` access.
* :meth:`Checker.check_run` — a post-suppression hook that sees the
  suppressed bucket; GC013 uses it to flag suppressions that suppress
  nothing (its findings are not themselves suppressible).

Stdlib-only by contract (the tier-1 self-run asserts the tool pulls in
no jax): everything here is :mod:`ast` + :mod:`json` + :mod:`hashlib`.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator

__all__ = [
    "Finding",
    "ModuleInfo",
    "Checker",
    "register",
    "all_checkers",
    "Baseline",
    "BaselineError",
    "dotted_path",
    "resolve_relative",
    "load_modules",
    "run",
    "RunResult",
]


# --------------------------------------------------------------------------
# findings
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Finding:
    """One rule violation at one source location.

    ``symbol`` is the enclosing ``Class.method`` / ``function``
    qualname ("<module>" at module scope) — the stable half of the
    identity baseline entries match on; ``line``/``col`` are 1-based /
    0-based like CPython's own diagnostics.
    """

    rule: str
    path: str  # posix-relative to the scan root's parent
    line: int
    col: int
    symbol: str
    message: str

    def format(self) -> str:
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule} "
            f"[{self.symbol}] {self.message}"
        )

    def key(self) -> tuple[str, str, str]:
        return (self.rule, self.path, self.symbol)


def dotted_path(expr: ast.expr) -> tuple[str, ...] | None:
    """``('jax', 'lax', 'axis_size')`` for an attribute chain rooted
    at a bare name; None when rooted elsewhere (call results,
    subscripts). The one shared walker every checker matches
    attribute/callee chains with — for a call, pass ``call.func``."""
    parts: list[str] = []
    cur: ast.expr = expr
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if not isinstance(cur, ast.Name):
        return None
    parts.append(cur.id)
    return tuple(reversed(parts))


def resolve_relative(
    mod_name: str, is_package: bool, node: ast.ImportFrom
) -> str | None:
    """Absolute dotted target of a (possibly relative) ImportFrom, or
    None when the relative level climbs out of the root package.
    Shared by GC001's closure walk and the analysis engine's import
    maps (it lives here so :mod:`.analysis` need not import a checker
    module)."""
    if node.level == 0:
        return node.module
    parts = mod_name.split(".") if mod_name else []
    pkg = parts if is_package else parts[:-1]
    up = node.level - 1
    if up > len(pkg):
        return None
    base = pkg[: len(pkg) - up]
    if node.module:
        base = base + node.module.split(".")
    return ".".join(base) if base else None


def symbol_of(tree: ast.Module, node: ast.AST) -> str:
    """Enclosing qualname of ``node`` ("<module>" at top level).

    Computed by walking down the scopes that contain the node's
    position — cheap and parent-pointer-free.
    """
    line = getattr(node, "lineno", None)
    if line is None:
        return "<module>"
    parts: list[str] = []
    scope: ast.AST = tree
    while True:
        inner = None
        for child in ast.iter_child_nodes(scope):
            if isinstance(
                child,
                (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
            ):
                end = getattr(child, "end_lineno", child.lineno)
                if child.lineno <= line <= end:
                    inner = child
                    break
        if inner is None:
            break
        parts.append(inner.name)
        scope = inner
    return ".".join(parts) if parts else "<module>"


# --------------------------------------------------------------------------
# module loading
# --------------------------------------------------------------------------


@dataclass
class ModuleInfo:
    """One source file handed to the checkers. The AST is LAZY: a
    warm cached run (per-file and project caches both hot) must parse
    nothing, so ``ast.parse`` happens at first ``.tree`` access — a
    syntax error therefore surfaces at first use, which the runner
    still reports as the same exit-2 configuration failure."""

    path: str  # absolute
    relpath: str  # posix, relative to the scan root's parent
    name: str  # dotted module name ("pkg.sub.mod"; "" outside a pkg)
    source: str
    sha: str

    _tree: ast.Module | None = field(default=None, repr=False)
    _lines: list[str] | None = field(default=None, repr=False)

    @property
    def tree(self) -> ast.Module:
        if self._tree is None:
            self._tree = ast.parse(self.source, filename=self.path)
        return self._tree

    @property
    def lines(self) -> list[str]:
        if self._lines is None:
            self._lines = self.source.splitlines()
        return self._lines

    def finding(
        self, rule: str, node: ast.AST, message: str
    ) -> Finding:
        return Finding(
            rule=rule,
            path=self.relpath,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            symbol=symbol_of(self.tree, node),
            message=message,
        )


def _module_name(abspath: str, base: str) -> str:
    """Dotted module name of ``abspath`` relative to namespace base
    ``base`` (``pkg.sub.mod``; ``__init__.py`` maps to its package's
    name; loose files get their stem)."""
    rel = os.path.relpath(abspath, base)
    parts = rel.replace(os.sep, "/").split("/")
    if parts[-1] == "__init__.py":
        parts = parts[:-1]
    else:
        parts[-1] = parts[-1][: -len(".py")]
    return ".".join(parts)


def package_base(top: str) -> str:
    """The directory whose children are the top of the dotted
    namespace for ``top``: walk UP past ``__init__.py`` packages, so a
    scan started anywhere INSIDE a package yields the same relpaths
    and dotted names as a scan of the whole package — baseline entries
    (recorded package-root-relative) keep matching on sub-path and
    single-file scans."""
    d = top if os.path.isdir(top) else os.path.dirname(top)
    while os.path.exists(os.path.join(d, "__init__.py")):
        parent = os.path.dirname(d)
        if parent == d:  # filesystem root: stop
            break
        d = parent
    return d


def load_modules(paths: Iterable[str]) -> list[ModuleInfo]:
    """Read every ``.py`` under ``paths`` (files or directories).

    Parsing is deferred to first ``.tree`` access (so fully cached
    runs never parse); a file that fails to parse raises there — a
    syntax error in the tree is a finding-level event for CI, not
    something to skip silently.
    """
    out: list[ModuleInfo] = []
    seen: set[str] = set()
    for top in paths:
        top = os.path.abspath(top)
        if os.path.isfile(top):
            files = [top]
        else:
            files = []
            for dirpath, dirnames, filenames in os.walk(top):
                # a directory holding a `.graftcheck-skip` marker file
                # is pruned from RECURSIVE scans (the fixture corpus of
                # deliberately-bad files under tests/); naming it as an
                # explicit scan root still analyzes it — the fixture
                # tests do exactly that
                dirnames[:] = sorted(
                    d for d in dirnames
                    if d != "__pycache__"
                    and not d.startswith(".")
                    and not os.path.exists(
                        os.path.join(dirpath, d, ".graftcheck-skip")
                    )
                )
                files += [
                    os.path.join(dirpath, f)
                    for f in sorted(filenames)
                    if f.endswith(".py")
                ]
        base = package_base(top)
        for f in files:
            if f in seen:
                continue
            seen.add(f)
            with open(f, "r", encoding="utf-8") as fh:
                src = fh.read()
            out.append(
                ModuleInfo(
                    path=f,
                    relpath=os.path.relpath(f, base).replace(os.sep, "/"),
                    name=_module_name(f, base),
                    source=src,
                    sha=hashlib.sha256(src.encode()).hexdigest(),
                )
            )
    return out


# --------------------------------------------------------------------------
# checker registry
# --------------------------------------------------------------------------


class Checker:
    """Base class: subclass, set ``rule``/``name``/``description``,
    implement ``check_module`` (per-file; cached) or ``check_project``
    (whole module set, ``project = True``; cached whole-tree on the
    sorted (relpath, sha) set plus :meth:`project_fingerprint`)."""

    rule: str = "GC000"
    name: str = "unnamed"
    description: str = ""
    project: bool = False

    #: attached by the runner around ``check_project`` so a
    #: project-wide checker can keep derived per-file artifacts (the
    #: analysis engine's per-function summaries) in the shared cache
    #: file via ``aux_get``/``aux_put``; None under ``--no-cache``
    aux_cache: "_Cache | None" = None

    def check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        return iter(())

    def check_project(
        self, mods: list[ModuleInfo]
    ) -> Iterator[Finding]:
        return iter(())

    def project_fingerprint(self, mods: list[ModuleInfo]) -> str:
        """Extra whole-tree cache-key material for a project checker
        whose verdict depends on inputs OUTSIDE the scanned .py set
        (GC009 reads a sibling transport.cpp): return a digest of
        those inputs so the project cache invalidates when they
        change. Must not parse — it runs on every (including fully
        cached) invocation."""
        return ""

    def check_run(
        self,
        mods: list[ModuleInfo],
        *,
        suppressed: list[Finding],
        active_rules: set[str],
        all_rules_active: bool,
    ) -> Iterator[Finding]:
        """Post-suppression hook, always live (must be cheap): runs
        after findings are bucketed, seeing what was suppressed.
        GC013 implements this to flag suppressions that suppress
        nothing. Findings yielded here bypass line suppression (a
        stale-suppression report must not be silenceable by the very
        comment it reports) but still pass the baseline split."""
        return iter(())


_REGISTRY: dict[str, Checker] = {}


def register(cls: type[Checker]) -> type[Checker]:
    """Class decorator: instantiate + index by rule id (unique)."""
    inst = cls()
    if inst.rule in _REGISTRY:
        raise ValueError(f"duplicate checker rule {inst.rule}")
    _REGISTRY[inst.rule] = inst
    return cls


def all_checkers() -> dict[str, Checker]:
    # the checkers package self-registers on import; imported lazily so
    # `import ...graftcheck.core` alone stays side-effect-free
    from . import checkers  # noqa: F401

    return dict(sorted(_REGISTRY.items()))


# --------------------------------------------------------------------------
# suppressions
# --------------------------------------------------------------------------

_SUPPRESS_RE = re.compile(
    r"#\s*graftcheck:\s*disable=([A-Za-z0-9_]+(?:\s*,\s*[A-Za-z0-9_]+)*)"
)


def _suppressed_rules(line_text: str) -> set[str]:
    m = _SUPPRESS_RE.search(line_text)
    if not m:
        return set()
    return {t.strip() for t in m.group(1).split(",") if t.strip()}


def is_suppressed(mod: ModuleInfo, f: Finding) -> bool:
    """True iff the finding's line (or the line directly above it)
    carries ``# graftcheck: disable=<rule>`` naming the rule (or
    ``all``)."""
    for ln in (f.line, f.line - 1):
        if 1 <= ln <= len(mod.lines):
            rules = _suppressed_rules(mod.lines[ln - 1])
            if f.rule in rules or "all" in rules:
                return True
    return False


# --------------------------------------------------------------------------
# baseline
# --------------------------------------------------------------------------


class BaselineError(ValueError):
    """The baseline file itself is invalid (over cap, stale entry,
    missing justification): a CONFIG failure, reported distinctly from
    code findings so CI can tell 'the tree regressed' from 'the
    baseline rotted'."""


class Baseline:
    """Checked-in false-positive ledger; see the module docstring for
    the policy. Entry shape::

        {"rule": "GC004", "path": "pkg/utils/straggle.py",
         "symbol": "PoolLatencyModel.publish",
         "justification": "..."}
    """

    def __init__(self, entries: list[dict], cap: int):
        self.entries = entries
        self.cap = cap
        for i, e in enumerate(entries):
            missing = {"rule", "path", "symbol", "justification"} - set(e)
            if missing:
                raise BaselineError(
                    f"baseline entry {i} is missing {sorted(missing)}"
                )
            if not str(e["justification"]).strip():
                raise BaselineError(
                    f"baseline entry {i} ({e['rule']} {e['path']}) has "
                    "an empty justification — baselines are for "
                    "DOCUMENTED false positives only"
                )
        if len(entries) > cap:
            raise BaselineError(
                f"baseline holds {len(entries)} entries but is capped "
                f"at {cap}; fix the new findings instead of baselining "
                "them (raising the cap is a reviewed change)"
            )

    @classmethod
    def load(cls, path: str) -> "Baseline":
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
        return cls(
            list(data.get("entries", [])), int(data.get("cap", 0))
        )

    def split(
        self,
        findings: list[Finding],
        *,
        active_rules: set[str] | None = None,
        scan_prefixes: list[str] | None = None,
    ) -> tuple[list[Finding], list[Finding]]:
        """(fresh, baselined). Raises :class:`BaselineError` on a stale
        entry — one matching no finding.

        Staleness is judged only over entries the scan could have
        matched: a ``--rules`` subset or a sub-path scan must not die
        on the full baseline's out-of-scope entries (``active_rules``:
        rule ids that ran; ``scan_prefixes``: relpath prefixes covered
        by the scan roots). An entry whose FILE was deleted is still
        stale on a covering scan — the prefix test is against the scan
        roots, not against the files found under them.
        """
        keys = {
            (e["rule"], e["path"], e["symbol"]): e for e in self.entries
        }
        hit: set[tuple] = set()
        fresh, old = [], []
        for f in findings:
            if f.key() in keys:
                hit.add(f.key())
                old.append(f)
            else:
                fresh.append(f)

        def applicable(k: tuple[str, str, str]) -> bool:
            rule, path, _ = k
            if active_rules is not None and rule not in active_rules:
                return False
            if scan_prefixes is not None and not any(
                path == p or path.startswith(p + "/")
                for p in scan_prefixes
            ):
                return False
            return True

        stale = [k for k in keys if k not in hit and applicable(k)]
        if stale:
            raise BaselineError(
                "stale baseline entries (match no current finding — "
                f"delete them): {sorted(stale)}"
            )
        return fresh, old


# --------------------------------------------------------------------------
# per-file cache
# --------------------------------------------------------------------------


def _tool_fingerprint() -> str:
    """sha over the graftcheck package's own sources: any edit to the
    framework or a checker invalidates every cached result."""
    root = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames if d != "__pycache__"
        )
        for f in sorted(filenames):
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f), "rb") as fh:
                    h.update(fh.read())
    return h.hexdigest()


class _Cache:
    """{(relpath, content sha) key -> [finding dicts]} for the
    per-file checkers, valid for one (tool fingerprint, active rule
    set) — stored alongside, checked on load. The rule set is part of
    the fingerprint because a ``--rules`` subset run records only its
    subset's findings; without the salt a later full scan would
    replay those partial results as if they were complete (a dirty
    tree reading clean).

    Two more sections ride the same file and the same fingerprint:

    * ``aux`` — free-form per-checker artifact store (the analysis
      engine's per-function summaries), sectioned by checker and keyed
      however the checker likes (by (relpath, sha), conventionally).
    * ``project`` — ONE whole-tree record for the project checkers,
      keyed on the runner-computed project key; see :func:`run`.
    """

    def __init__(self, path: str | None, salt: str = ""):
        self.path = path
        self.fingerprint = _tool_fingerprint() + "|" + salt
        self.data: dict[str, list[dict]] = {}
        self.aux: dict[str, dict] = {}
        self.project: dict = {}
        self.dirty = False
        if path and os.path.exists(path):
            try:
                with open(path, "r", encoding="utf-8") as f:
                    raw = json.load(f)
                if raw.get("fingerprint") == self.fingerprint:
                    self.data = raw.get("files", {})
                    aux = raw.get("aux", {})
                    self.aux = aux if isinstance(aux, dict) else {}
                    proj = raw.get("project", {})
                    self.project = (
                        proj if isinstance(proj, dict) else {}
                    )
            except (OSError, ValueError):
                self.data = {}

    _FIELDS = frozenset(
        ("rule", "path", "line", "col", "symbol", "message")
    )

    def _decode(self, got) -> list[Finding] | None:
        if not isinstance(got, list):
            return None
        out = []
        for d in got:
            if not (
                isinstance(d, dict) and set(d) == self._FIELDS
            ):
                return None
            out.append(Finding(**d))
        return out

    def get(self, key: str) -> list[Finding] | None:
        """Cached findings for ``key``, or None. The file's contents
        are NOT trusted: any structurally invalid entry voids that
        sha's record (treated as a miss and re-analyzed) instead of
        crashing or replaying garbage."""
        return self._decode(self.data.get(key))

    def put(self, key: str, findings: list[Finding]) -> None:
        self.data[key] = [f.__dict__ for f in findings]
        self.dirty = True

    def aux_get(self, section: str, key: str):
        """Checker-owned artifact, or None. Structure is the owning
        checker's contract — it must validate what it reads back."""
        sec = self.aux.get(section)
        return sec.get(key) if isinstance(sec, dict) else None

    def aux_put(self, section: str, key: str, value) -> None:
        self.aux.setdefault(section, {})[key] = value
        self.dirty = True

    def project_get(self, key: str) -> list[Finding] | None:
        if self.project.get("key") != key:
            return None
        return self._decode(self.project.get("findings"))

    def project_put(self, key: str, findings: list[Finding]) -> None:
        self.project = {
            "key": key,
            "findings": [f.__dict__ for f in findings],
        }
        self.dirty = True

    def save(self) -> None:
        if not self.path or not self.dirty:
            return
        tmp = self.path + ".tmp"
        try:
            # a cache path in a not-yet-existing directory (CI hands us
            # `.graftcheck-cache/pkg.json` before any run has created
            # it) must create the directory, not silently never persist
            parent = os.path.dirname(self.path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(
                    {"fingerprint": self.fingerprint,
                     "files": self.data,
                     "aux": self.aux,
                     "project": self.project},
                    f,
                )
            os.replace(tmp, self.path)
        except OSError:
            pass  # a cache that cannot persist is just a slow cache


# --------------------------------------------------------------------------
# the runner
# --------------------------------------------------------------------------


@dataclass
class RunResult:
    fresh: list[Finding]
    baselined: list[Finding]
    suppressed: list[Finding]
    n_files: int
    n_rules: int
    baseline_size: int

    @property
    def ok(self) -> bool:
        return not self.fresh


def run(
    paths: Iterable[str],
    *,
    baseline_path: str | None = None,
    cache_path: str | None = None,
    rules: Iterable[str] | None = None,
    progress: Callable[[str], None] | None = None,
) -> RunResult:
    """Analyze ``paths`` with every registered checker.

    Returns a :class:`RunResult`; raises :class:`BaselineError` when
    the baseline file itself is invalid. ``rules`` restricts to a
    subset of rule ids (the fixture tests use this to isolate one
    checker).
    """
    paths = [str(p) for p in paths]  # consumed twice (modules, prefixes)
    checkers = all_checkers()
    if rules is not None:
        want = set(rules)
        unknown = want - set(checkers)
        if unknown:
            raise ValueError(f"unknown rules {sorted(unknown)}")
        checkers = {r: c for r, c in checkers.items() if r in want}
    mods = load_modules(paths)
    by_path = {m.relpath: m for m in mods}

    per_file = [c for c in checkers.values() if not c.project]
    project = [c for c in checkers.values() if c.project]
    cache = _Cache(
        cache_path, salt=",".join(sorted(c.rule for c in per_file))
    )

    findings: list[Finding] = []
    for mod in mods:
        # keyed on (relpath, content sha) — NOT content alone: checker
        # results are path-dependent (GC011's witness home), so
        # two identical-content files at different paths must never
        # replay each other's records
        key = f"{mod.relpath}\0{mod.sha}"
        cached = cache.get(key)
        if cached is not None and per_file:
            findings += cached
            continue
        mine: list[Finding] = []
        for chk in per_file:
            mine += list(chk.check_module(mod))
        cache.put(key, mine)
        findings += mine
        if progress is not None:
            progress(mod.relpath)
    if project:
        # whole-tree cache: the project checkers' verdict is a pure
        # function of the (relpath, sha) set, the project rule ids,
        # and whatever non-.py inputs each checker fingerprints
        # (GC009's transport.cpp) — key all of it, replay on a hit
        pf = hashlib.sha256()
        for m in sorted(mods, key=lambda m: m.relpath):
            pf.update(m.relpath.encode())
            pf.update(b"\0")
            pf.update(m.sha.encode())
            pf.update(b"\n")
        for chk in sorted(project, key=lambda c: c.rule):
            pf.update(chk.rule.encode())
            pf.update(chk.project_fingerprint(mods).encode())
        pkey = pf.hexdigest()
        cached_p = cache.project_get(pkey)
        if cached_p is not None:
            findings += cached_p
        else:
            mine_p: list[Finding] = []
            for chk in project:
                # a pathless cache (--no-cache) can never persist, so
                # handing it over would only buy the serialization
                # cost of aux_put with none of the warm-run payoff
                chk.aux_cache = cache if cache.path else None
                try:
                    mine_p += list(chk.check_project(mods))
                finally:
                    chk.aux_cache = None
            cache.project_put(pkey, mine_p)
            findings += mine_p
    cache.save()

    live: list[Finding] = []
    suppressed: list[Finding] = []
    for f in findings:
        mod = by_path.get(f.path)
        if mod is not None and is_suppressed(mod, f):
            suppressed.append(f)
        else:
            live.append(f)

    # post-suppression hooks (GC013 stale-suppression): always live,
    # appended to the live set AFTER bucketing so a stale-suppression
    # report cannot be silenced by the comment it reports
    all_rules_active = set(checkers) == set(_REGISTRY)
    for chk in checkers.values():
        live += list(
            chk.check_run(
                mods,
                suppressed=suppressed,
                active_rules=set(checkers),
                all_rules_active=all_rules_active,
            )
        )

    if baseline_path is not None and not os.path.exists(baseline_path):
        # a typo'd --baseline must be a loud config error, not a
        # silent ledger-off run (the CLI documents exit 2 for this)
        raise BaselineError(
            f"baseline file not found: {baseline_path} "
            "(pass --baseline none to run without one)"
        )
    if baseline_path:
        # the prefix a scan root covers, in the same namespace the
        # relpaths use (relative to the enclosing package's parent)
        prefixes = [
            os.path.relpath(
                os.path.abspath(p), package_base(os.path.abspath(p))
            ).replace(os.sep, "/")
            for p in paths
        ]
        bl = Baseline.load(baseline_path)
        fresh, baselined = bl.split(
            live,
            active_rules=set(checkers),
            scan_prefixes=prefixes,
        )
        baseline_size = len(bl.entries)
    else:
        fresh, baselined, baseline_size = live, [], 0

    order = lambda f: (f.path, f.line, f.rule)  # noqa: E731
    return RunResult(
        fresh=sorted(fresh, key=order),
        baselined=sorted(baselined, key=order),
        suppressed=sorted(suppressed, key=order),
        n_files=len(mods),
        n_rules=len(checkers),
        baseline_size=baseline_size,
    )
