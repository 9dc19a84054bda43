"""AST-based repo lint for the JAX pitfalls this codebase has actually
hit (each rule cites the PR that paid for it):

``dispatch-in-loop``
    ``run_program`` / ``run_programs`` called inside a Python ``for`` /
    ``while`` body.  Each iteration re-enters the jit boundary, and any
    shape drift (program length, lane count) recompiles per iteration
    -- the PR 6 recompile-per-shape leak.  Batch the programs and
    dispatch once (``run_programs``), or hoist the call out of the
    loop.

``vmap-over-scan``
    ``vmap`` applied over the engine's scan/op entry points
    (``run_program`` / ``run_programs`` / ``_scan_program`` /
    ``apply_op``).  The engine's batched-scatter paths are written for
    the explicit lane axis of ``run_programs``; wrapping them in
    ``vmap`` instead lowers the per-zone scatters to gather/select
    chains (measured 27x slower in the PR 4 fleet bring-up).

``jit-needs-static``
    ``jax.jit`` of a function taking a config-like argument (``cfg`` /
    ``config`` / ``spec`` / ``obs``) without ``static_argnums`` /
    ``static_argnames``.  Configs are hashable compile-time constants
    here; tracing them either fails outright (dataclasses of ints used
    in shapes) or silently retraces per call.

``bench-schema``
    A ``BENCH_<name>.json`` artifact reference that
    ``benchmarks/paper_headline.py`` does not name, or a hard-coded
    integer bench ``schema_version`` (import ``SCHEMA_VERSION``
    instead) -- stale references survived two artifact renames before
    this gate.  Only files that mention ``BENCH_*.json`` artifacts are
    in scope for the schema-version half, so unrelated schemas (e.g.
    the Perfetto export's) are not flagged.

Suppress any finding with a ``# lint: ok`` comment on the flagged
line.  Pure stdlib (``ast`` + ``tokenize``); no repro imports, so
``tools/lint.py`` can run it without touching JAX.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path
from typing import Iterable, List, Optional, Sequence, Set

PRAGMA = re.compile(r"#\s*lint:\s*ok\b")
_BENCH_REF = re.compile(r"^BENCH_\w+\.json$")
_BENCH_ANY = re.compile(r"BENCH_\w+\.json")

#: callables whose per-iteration dispatch is the recompile hazard
DISPATCH_NAMES = {"run_program", "run_programs"}
#: engine entry points that must not be vmapped over
SCAN_NAMES = {"run_program", "run_programs", "_scan_program", "apply_op"}
#: parameter names that mark a function as config-taking
CONFIG_PARAMS = {"cfg", "config", "spec", "obs", "engine_config"}


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _call_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _names_in(node: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            out.add(sub.id)
        elif isinstance(sub, ast.Attribute):
            out.add(sub.attr)
    return out


def _is_jit(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` as a bare expression."""
    return _call_name(node) == "jit"


def _jit_without_static(dec: ast.AST) -> bool:
    """True for a decorator that jits WITHOUT static_argnums/names:
    bare ``@jax.jit``, ``@jax.jit(...)``, or
    ``@functools.partial(jax.jit, ...)`` missing the static keywords."""
    if _is_jit(dec):
        return True
    if isinstance(dec, ast.Call):
        statics = {kw.arg for kw in dec.keywords}
        has_static = statics & {"static_argnums", "static_argnames"}
        if _is_jit(dec.func):
            return not has_static
        if (_call_name(dec.func) == "partial" and dec.args
                and _is_jit(dec.args[0])):
            return not has_static
    return False


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, bench_artifacts: Set[str],
                 mentions_bench: bool):
        self.path = path
        self.bench_artifacts = bench_artifacts
        self.mentions_bench = mentions_bench
        self.loop_depth = 0
        self.findings: List[Finding] = []

    def _add(self, node: ast.AST, rule: str, message: str) -> None:
        self.findings.append(
            Finding(self.path, getattr(node, "lineno", 0), rule, message))

    # -- loops ---------------------------------------------------------- #
    def _loop(self, node) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_AsyncFor = visit_While = _loop

    def visit_Call(self, node: ast.Call) -> None:
        name = _call_name(node.func)
        if name in DISPATCH_NAMES and self.loop_depth > 0:
            self._add(node, "dispatch-in-loop",
                      f"{name}() inside a Python loop dispatches (and "
                      f"on shape drift recompiles) per iteration; "
                      f"batch the lanes into one run_programs call")
        if name == "vmap":
            hit: Set[str] = set()
            for arg in node.args:
                hit |= _names_in(arg) & SCAN_NAMES
            if hit:
                self._add(node, "vmap-over-scan",
                          f"vmap over {sorted(hit)[0]} lowers the "
                          f"engine's batched scatters to gather/select "
                          f"chains (measured 27x slower); use the "
                          f"explicit lane axis of run_programs")
        self.generic_visit(node)

    def _visit_def(self, node) -> None:
        args = node.args
        params = {a.arg for a in
                  args.posonlyargs + args.args + args.kwonlyargs}
        config_params = params & CONFIG_PARAMS
        for dec in node.decorator_list:
            if config_params and _jit_without_static(dec):
                self._add(
                    dec, "jit-needs-static",
                    f"jit of {node.name}() without static_argnums/"
                    f"static_argnames, but it takes config-like "
                    f"argument(s) {sorted(config_params)}; configs "
                    f"are hashable compile-time constants here")
        depth, self.loop_depth = self.loop_depth, 0
        self.generic_visit(node)
        self.loop_depth = depth

    visit_FunctionDef = visit_AsyncFunctionDef = _visit_def

    def visit_Constant(self, node: ast.Constant) -> None:
        if (isinstance(node.value, str)
                and _BENCH_REF.match(node.value)
                and self.bench_artifacts
                and node.value not in self.bench_artifacts):
            self._add(node, "bench-schema",
                      f"{node.value} is not an artifact "
                      f"benchmarks/paper_headline.py writes "
                      f"({', '.join(sorted(self.bench_artifacts))})")
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        if self.mentions_bench:
            sides = [node.left, *node.comparators]
            has_key = any("schema_version" in _strs_in(s) for s in sides)
            has_int = any(isinstance(s, ast.Constant)
                          and isinstance(s.value, int) for s in sides)
            if has_key and has_int:
                self._add(node, "bench-schema",
                          "hard-coded bench schema_version comparison; "
                          "import SCHEMA_VERSION from "
                          "benchmarks/paper_headline.py")
        self.generic_visit(node)


def _strs_in(node: ast.AST) -> Set[str]:
    return {sub.value for sub in ast.walk(node)
            if isinstance(sub, ast.Constant)
            and isinstance(sub.value, str)}


def _pragma_lines(source: str) -> Set[int]:
    out: Set[int] = set()
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT and PRAGMA.search(tok.string):
                out.add(tok.start[0])
    except tokenize.TokenizeError:
        pass
    return out


def bench_artifacts(root: Path) -> Set[str]:
    """The ``BENCH_*.json`` artifact names
    ``benchmarks/paper_headline.py`` writes -- the canonical set every
    other reference is checked against.  Empty (disabling the
    artifact-name half of ``bench-schema``) when the file is absent."""
    bench = root / "benchmarks" / "paper_headline.py"
    if not bench.is_file():
        return set()
    return set(_BENCH_ANY.findall(bench.read_text()))


def lint_source(source: str, path: str, *,
                bench_names: Set[str] = frozenset()) -> List[Finding]:
    """Lint one module's source text; ``path`` labels the findings."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [Finding(path, exc.lineno or 0, "syntax",
                        f"does not parse: {exc.msg}")]
    visitor = _Visitor(path, set(bench_names),
                       mentions_bench=bool(_BENCH_ANY.search(source)))
    visitor.visit(tree)
    suppressed = _pragma_lines(source)
    return [f for f in visitor.findings if f.line not in suppressed]


def lint_paths(root: Path, paths: Iterable[Path]) -> List[Finding]:
    names = bench_artifacts(root)
    out: List[Finding] = []
    for p in sorted(paths):
        if p.name == "paper_headline.py" and p.parent.name == "benchmarks":
            continue  # the canonical artifact list itself
        rel = str(p.relative_to(root)) if p.is_relative_to(root) else str(p)
        out.extend(lint_source(p.read_text(), rel, bench_names=names))
    return sorted(out, key=lambda f: (f.path, f.line))


def lint_tree(root: Path,
              subdirs: Sequence[str] = ("src", "tools", "tests")
              ) -> List[Finding]:
    """Lint every ``.py`` file under ``root``'s code directories."""
    paths: List[Path] = []
    for sub in subdirs:
        base = root / sub
        if base.is_dir():
            paths.extend(base.rglob("*.py"))
    return lint_paths(root, paths)
