"""pdt-analyze core: finding model, suppressions, baselines, pass protocol.

The analyzer is a collection of AST passes over the package tree (plus the
``tests/`` tree for the marker-convention pass).  Everything here is
stdlib-only and import-light by design: the CLI must run in CI containers
and pre-commit hooks without touching JAX, and the passes must never
*execute* the code they inspect — a purity analyzer that imports the
module under analysis would trigger the very side effects it polices.

Vocabulary:

  - A :class:`Finding` is one rule violation at ``file:line`` with a
    severity and a human message.
  - A suppression is an inline comment ``# pdt: ignore[rule]`` (or
    ``# pdt: ignore[rule1, rule2]``, or ``# pdt: ignore[*]``) on the
    flagged line — or alone on the line directly above it, for lines too
    long to carry a trailing comment.  Suppressions are expected to carry
    a one-line justification after a ``--``:
    ``# pdt: ignore[lock-discipline] -- single-writer counter, racy reads ok``
  - A baseline file (JSON) records the *identity keys* of known findings
    so a rule can be introduced without fixing the whole backlog at once;
    keys are line-number independent (rule + path + message) so pure code
    motion does not resurrect baselined findings.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import re
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Finding",
    "SourceModule",
    "AnalysisPass",
    "AnalysisContext",
    "AnalysisResult",
    "collect_modules",
    "run_passes",
    "load_baseline",
    "write_baseline",
]

SEVERITY_ERROR = "error"
SEVERITY_WARNING = "warning"

# `# pdt: ignore[rule-a, rule-b]` with an optional `-- justification` tail
_SUPPRESS_RE = re.compile(r"#\s*pdt:\s*ignore\[([^\]]+)\]")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation, pinned to a repo-relative ``path:line``."""

    rule: str
    severity: str
    path: str  # posix, relative to the analysis root's parent (repo root)
    line: int
    message: str

    @property
    def key(self) -> str:
        """Line-independent identity used by baseline files."""
        return f"{self.rule}:{self.path}:{self.message}"

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.severity}[{self.rule}] {self.message}"


class SourceModule:
    """A parsed source file: path, text, AST, and its suppression map."""

    def __init__(self, path: Path, rel: str, source: str):
        self.path = path
        self.rel = rel  # posix path relative to repo root
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        self.suppressions = self._collect_suppressions()

    def _collect_suppressions(self) -> Dict[int, set]:
        """Map line number -> set of suppressed rule names ('*' = all).

        A comment on its own line suppresses the next line; a trailing
        comment suppresses its own line.  Both map through here so a
        finding only needs to check its own line number.
        """
        out: Dict[int, set] = {}
        for i, text in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(text)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            stripped = text.strip()
            target = i + 1 if stripped.startswith("#") else i
            out.setdefault(target, set()).update(rules)
        return out

    def is_suppressed(self, finding: Finding) -> bool:
        rules = self.suppressions.get(finding.line)
        if not rules:
            return False
        return "*" in rules or finding.rule in rules


@dataclasses.dataclass
class AnalysisContext:
    """Shared inputs handed to every pass."""

    package_root: Path  # the pytorch_distributed_training_tpu/ dir
    repo_root: Path  # its parent (where tests/ and config/ live)
    tests_dir: Optional[Path] = None  # overridable for fixture tests
    config_dir: Optional[Path] = None  # overridable for fixture tests

    def resolved_tests_dir(self) -> Path:
        return self.tests_dir if self.tests_dir is not None else self.repo_root / "tests"

    def resolved_config_dir(self) -> Path:
        return self.config_dir if self.config_dir is not None else self.repo_root / "config"


class AnalysisPass:
    """Base class: subclasses set ``rule``/``description`` and run()."""

    rule: str = ""
    description: str = ""

    def run(self, modules: Sequence[SourceModule], ctx: AnalysisContext) -> List[Finding]:
        raise NotImplementedError


@dataclasses.dataclass
class AnalysisResult:
    findings: List[Finding]  # every finding, suppressed or not
    unsuppressed: List[Finding]  # what the gate sees
    suppressed: List[Finding]
    baselined: List[Finding]
    wall_s: float
    files_scanned: int

    def rule_totals(self, which: str = "unsuppressed") -> Dict[str, int]:
        pool = getattr(self, which)
        out: Dict[str, int] = {}
        for f in pool:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out


def collect_modules(package_root: Path, repo_root: Path) -> List[SourceModule]:
    """Parse every .py file under the package tree (skipping caches)."""
    modules = []
    for path in sorted(package_root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(repo_root).as_posix()
        modules.append(SourceModule(path, rel, path.read_text()))
    return modules


def run_passes(
    passes: Sequence[AnalysisPass],
    ctx: AnalysisContext,
    baseline_keys: Optional[set] = None,
) -> AnalysisResult:
    """Run passes over the package tree and fold in suppressions/baseline."""
    t0 = time.perf_counter()
    modules = collect_modules(ctx.package_root, ctx.repo_root)
    by_rel = {m.rel: m for m in modules}
    findings: List[Finding] = []
    for p in passes:
        findings.extend(p.run(modules, ctx))
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    suppressed, baselined, live = [], [], []
    baseline_keys = baseline_keys or set()
    for f in findings:
        mod = by_rel.get(f.path)
        if mod is not None and mod.is_suppressed(f):
            suppressed.append(f)
        elif f.key in baseline_keys:
            baselined.append(f)
        else:
            live.append(f)
    return AnalysisResult(
        findings=findings,
        unsuppressed=live,
        suppressed=suppressed,
        baselined=baselined,
        wall_s=time.perf_counter() - t0,
        files_scanned=len(modules),
    )


def load_baseline(path: Path) -> set:
    data = json.loads(Path(path).read_text())
    if data.get("version") != 1:
        raise ValueError(f"unsupported baseline version in {path}: {data.get('version')!r}")
    return set(data.get("findings", []))


def write_baseline(path: Path, findings: Iterable[Finding]) -> None:
    keys = sorted({f.key for f in findings})
    Path(path).write_text(
        json.dumps({"version": 1, "findings": keys}, indent=2) + "\n"
    )


# --------------------------------------------------------------------------- #
# Shared AST helpers used by several passes.


def dotted_name(node: ast.AST) -> Optional[str]:
    """`a.b.c` for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def iter_child_statements(func: ast.AST) -> Iterable[ast.AST]:
    """Walk a function's own nodes WITHOUT descending into nested defs.

    Lambdas are treated as part of the enclosing function (they execute
    inline under the same tracing/locking context as often as not, and
    they cannot contain statements of their own).
    """
    stack = list(ast.iter_child_nodes(func))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def func_qualname(module: SourceModule, target: ast.AST) -> str:
    """Best-effort dotted qualname of a def/class node within its module."""
    path: List[str] = []

    def visit(node: ast.AST, trail: Tuple[str, ...]) -> bool:
        for child in ast.iter_child_nodes(node):
            if child is target:
                name = getattr(child, "name", "<anon>")
                path.extend(trail + (name,))
                return True
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                if visit(child, trail + (child.name,)):
                    return True
            else:
                if visit(child, trail):
                    return True
        return False

    visit(module.tree, ())
    return ".".join(path) if path else getattr(target, "name", "<anon>")
