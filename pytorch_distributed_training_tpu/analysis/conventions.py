"""marker-convention: the repo's test/telemetry structural conventions.

Migrated from the ad-hoc AST guard that used to live entirely inside
``tests/test_marker_convention.py`` (PRs 2-7 grew it one rule at a time);
the test file now just invokes this pass, so the rules run identically
from the CLI and the tier-1 gate.  Three sub-rules:

  - **fault-chaos**: a test touching the fault machinery
    (FaultInjector/watchdog/elastic/worker-pool kill paths) AND a heavy
    indicator (process spawns/kills, wall-clock sleeps) is a chaos test
    and must carry ``slow`` or ``chaos``.
  - **counter-store**: all observability counters flow through
    ``telemetry/registry.py``; assigning ``self._counters = {}`` (or a
    ``Counter()``/``defaultdict()``) anywhere else in the package
    reintroduces a private ledger the goodput snapshot cannot see.
  - **pass-registration**: every ``AnalysisPass`` subclass defined under
    ``analysis/`` must appear in the ``ALL_PASSES`` tuple in
    ``analysis/__init__.py``.  A pass that exists but is not registered
    silently runs nowhere — not in the CLI, not in the tier-1 gate —
    which is exactly the failure mode a lint
    framework must refuse to allow for itself.

The tests scan covers ``tests/test_*.py``; the counter scan covers the
package tree minus ``telemetry/`` (the one place ledgers may live) and
``analysis/`` (this package names the patterns it hunts).
"""
from __future__ import annotations

import ast
from typing import List, Sequence

from .core import (
    SEVERITY_ERROR,
    AnalysisContext,
    AnalysisPass,
    Finding,
    SourceModule,
)

__all__ = ["MarkerConventionPass", "is_counter_store"]

FAULT_MACHINERY = (
    "FaultInjector",
    "fault.install",
    "PDT_FAULT_SPEC",
    "StepWatchdog",
    "ProcessLoaderPool",
    "ElasticCoordinator",
    "IntegritySentinel",
    "kill_peer",
    "sdc_flip",
    "multihost_worker",
    "MH_ELASTIC",
    "ChaosSoakEngine",
    "ScenarioGenerator",
)
HEAVY_INDICATORS = ("time.sleep(", "os.kill(", "Process(", "subprocess")

# Files that NAME the machinery without driving it: the legacy guard file
# (kept as a wrapper) and the analyzer's own test battery (its fixtures
# quote the banned strings).
_EXEMPT_TEST_FILES = {"test_marker_convention.py", "test_static_analysis.py"}

_COUNTER_STORE_NAMES = ("_counters", "counters", "_counter_store")
_COUNTER_STORE_VALUES = ("dict", "Counter", "defaultdict", "OrderedDict")


def is_counter_store(node: ast.AST) -> bool:
    """An Assign/AnnAssign binding a counter-ish name to a fresh mapping."""
    if isinstance(node, ast.AnnAssign):
        targets, value = [node.target], node.value
    elif isinstance(node, ast.Assign):
        targets, value = node.targets, node.value
    else:
        return False
    named = False
    for t in targets:
        name = t.attr if isinstance(t, ast.Attribute) else (
            t.id if isinstance(t, ast.Name) else ""
        )
        if name in _COUNTER_STORE_NAMES or name.endswith("_counters"):
            named = True
    if not named or value is None:
        return False
    if isinstance(value, ast.Dict) and not value.keys:
        return True  # = {}
    if isinstance(value, ast.Call):
        fn = value.func
        fn_name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else ""
        )
        return fn_name in _COUNTER_STORE_VALUES
    return False


class MarkerConventionPass(AnalysisPass):
    rule = "marker-convention"
    description = (
        "fault-machinery tests are slow/chaos-marked, counters route "
        "through telemetry/registry, every pass is registered"
    )

    def run(self, modules: Sequence[SourceModule], ctx: AnalysisContext) -> List[Finding]:
        findings: List[Finding] = []
        findings.extend(self._check_tests(ctx))
        findings.extend(self._check_counter_stores(modules))
        findings.extend(self._check_pass_registration(modules))
        return findings

    # ------------------------------------------------------------------ #

    def _check_tests(self, ctx: AnalysisContext) -> List[Finding]:
        findings: List[Finding] = []
        tests_dir = ctx.resolved_tests_dir()
        if not tests_dir.is_dir():
            return findings
        for path in sorted(tests_dir.glob("test_*.py")):
            if path.name in _EXEMPT_TEST_FILES:
                continue
            rel = path.relative_to(ctx.repo_root).as_posix() if (
                ctx.repo_root in path.parents
            ) else path.name
            tree = ast.parse(path.read_text())
            for node in ast.walk(tree):
                if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if not node.name.startswith("test_"):
                    continue
                body_src = ast.unparse(node)
                decorators = [ast.unparse(d) for d in node.decorator_list]
                if (
                    any(m in body_src for m in FAULT_MACHINERY)
                    and any(h in body_src for h in HEAVY_INDICATORS)
                    and not any("slow" in d or "chaos" in d for d in decorators)
                ):
                    findings.append(
                        Finding(
                            rule=self.rule,
                            severity=SEVERITY_ERROR,
                            path=rel,
                            line=node.lineno,
                            message=(
                                f"{node.name} exercises the fault machinery "
                                "with process spawns/kills or sleeps but "
                                "carries neither @pytest.mark.slow nor "
                                "@pytest.mark.chaos"
                            ),
                        )
                    )
        return findings

    def _check_pass_registration(self, modules: Sequence[SourceModule]) -> List[Finding]:
        """Every AnalysisPass subclass under analysis/ is in ALL_PASSES."""
        findings: List[Finding] = []
        defined = []  # (class name, module, lineno)
        registered = None  # names in the ALL_PASSES tuple, if found
        for module in modules:
            parts = module.rel.split("/")
            if "analysis" not in parts:
                continue
            for node in ast.walk(module.tree):
                if isinstance(node, ast.ClassDef) and any(
                    (isinstance(b, ast.Name) and b.id == "AnalysisPass")
                    or (isinstance(b, ast.Attribute) and b.attr == "AnalysisPass")
                    for b in node.bases
                ):
                    defined.append((node.name, module, node.lineno))
                if (
                    module.path.name == "__init__.py"
                    and isinstance(node, ast.Assign)
                    and any(
                        isinstance(t, ast.Name) and t.id == "ALL_PASSES"
                        for t in node.targets
                    )
                    and isinstance(node.value, ast.Tuple)
                ):
                    registered = {
                        e.id if isinstance(e, ast.Name) else getattr(e, "attr", "")
                        for e in node.value.elts
                    }
        if registered is None:
            # No ALL_PASSES tuple in scope (e.g. a fixture subset) — the
            # pin only bites when the registry itself is being analyzed.
            return findings
        for name, module, lineno in defined:
            if name not in registered:
                findings.append(
                    Finding(
                        rule=self.rule,
                        severity=SEVERITY_ERROR,
                        path=module.rel,
                        line=lineno,
                        message=(
                            f"{name} subclasses AnalysisPass but is missing "
                            "from ALL_PASSES in analysis/__init__.py — an "
                            "unregistered pass runs nowhere (the CLI and the "
                            "tier-1 gate both iterate ALL_PASSES)"
                        ),
                    )
                )
        return findings

    def _check_counter_stores(self, modules: Sequence[SourceModule]) -> List[Finding]:
        findings: List[Finding] = []
        for module in modules:
            parts = module.rel.split("/")
            if "telemetry" in parts or "analysis" in parts:
                continue
            for node in ast.walk(module.tree):
                if is_counter_store(node):
                    findings.append(
                        Finding(
                            rule=self.rule,
                            severity=SEVERITY_ERROR,
                            path=module.rel,
                            line=node.lineno,
                            message=(
                                "ad-hoc counter store outside telemetry/ — "
                                "use telemetry.registry "
                                "(get_registry().counter(name) or a private "
                                "MetricsRegistry for instance-local counts)"
                            ),
                        )
                    )
        return findings
