"""Static convention guards: test markers and the one-ledger rule.

The rules themselves now live in the analysis framework
(``pytorch_distributed_training_tpu/analysis/conventions.py``, rule
``marker-convention``) so they run identically from the CLI and this
tier-1 gate.  This file is a thin wrapper kept under its historical name:
each test invokes the pass and asserts its slice of the findings is empty
(fault-machinery tests are slow/chaos-marked, no ad-hoc counter stores
outside telemetry/) plus the scan-coverage pin on the serving modules.
"""
import ast
import pathlib

from pytorch_distributed_training_tpu import analysis
from pytorch_distributed_training_tpu.analysis.conventions import (
    MarkerConventionPass,
    is_counter_store,
)

_REPO = pathlib.Path(__file__).parent.parent
_PKG = _REPO / "pytorch_distributed_training_tpu"


def _run_marker_pass():
    return analysis.run(rules=["marker-convention"])


def test_fault_injection_tests_are_slow_or_chaos_marked():
    """Fault-injection tests that spawn/kill real processes or wait out
    sleep-based watchdog timers must carry ``slow`` or ``chaos``."""
    offenders = [
        f.format()
        for f in _run_marker_pass().unsuppressed
        if "neither @pytest.mark.slow nor @pytest.mark.chaos" in f.message
    ]
    assert not offenders, offenders


def test_no_ad_hoc_counter_stores_outside_telemetry():
    """Every package module except ``telemetry/`` (and the analyzer,
    which names the patterns it hunts) must route counters through the
    registry — a private ``self._counters = {}`` ledger is invisible to
    the goodput snapshot."""
    offenders = [
        f.format()
        for f in _run_marker_pass().unsuppressed
        if "ad-hoc counter store" in f.message
    ]
    assert not offenders, offenders


def test_counter_guard_covers_new_serving_modules():
    """PR 7 added serving/scheduler.py and serving/kv_pool.py; pin that
    the package-wide counter-store scan actually reaches them (a
    rename/move that drops them out of scope should fail HERE, not
    silently stop scanning) and that their counters route through
    ServingMetrics / the telemetry registry."""
    for rel in ("serving/scheduler.py", "serving/kv_pool.py"):
        path = _PKG / rel
        assert path.exists(), f"{rel} moved — update the convention guards"
        assert path in set(_PKG.rglob("*.py")), f"{rel} escaped the scan"
        tree = ast.parse(path.read_text())
        assert not [
            node.lineno for node in ast.walk(tree) if is_counter_store(node)
        ], f"{rel} grew an ad-hoc counter store"
    # the scheduler must talk to the ledger, not keep private tallies
    sched_src = (_PKG / "serving" / "scheduler.py").read_text()
    assert "metrics.incr" in sched_src and "get_registry" in sched_src
    # and the pass itself must be scanning this package tree: the module
    # list the framework builds has to include both serving files
    ctx_modules = {
        m.rel
        for m in analysis.collect_modules(_PKG.resolve(), _REPO.resolve())
    }
    assert "pytorch_distributed_training_tpu/serving/scheduler.py" in ctx_modules
    assert "pytorch_distributed_training_tpu/serving/kv_pool.py" in ctx_modules


def test_marker_pass_registered_in_framework():
    """The migration keeps the rule in the default battery: dropping
    MarkerConventionPass from ALL_PASSES would silently disable the
    convention everywhere (CLI, this gate)."""
    assert MarkerConventionPass in analysis.ALL_PASSES


def test_every_pallas_call_in_ops_is_named():
    """A kernel's ``name=`` becomes its instruction's name in the compiled
    program and so in a profiler trace; without one the trace calls it
    after whatever transformation wrapped it (``%jvp__.1``) and the
    benchmark's per-kernel readers find nothing.  Names are the stable
    ones PERF.md lists, each used once."""
    names = []
    for path in sorted((_PKG / "ops").glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"
            ):
                continue
            named = [kw.value for kw in node.keywords if kw.arg == "name"]
            where = f"{path.name}:{node.lineno}"
            assert named, f"{where}: pallas_call without name="
            assert isinstance(named[0], ast.Constant), f"{where}: name= not a literal"
            names.append(named[0].value)
    assert sorted(names) == sorted([
        "flash_fwd", "flash_fwd_stream", "flash_bwd", "flash_bwd_dq",
        "flash_bwd_dkv", "flash_bwd_dq_stream", "flash_bwd_dkv_stream",
        "fused_ce_fwd", "fused_ce_bwd", "fused_add_ln", "fused_bias_gelu",
        "paged_decode", "mla_paged_decode",
    ])
