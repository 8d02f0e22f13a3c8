"""pdt-analyze battery: the tier-1 gate plus proof every pass catches its
seeded fixtures.

Layout:
  - the GATE: zero unsuppressed findings over the real package tree
    (the same invariant the CLI exit code carries);
  - per-pass clean/violation fixture pairs under tests/analysis_fixtures/
    (violation files are never imported, only parsed; the marker-pass
    fixture body is copied into a tmp tests dir under a ``test_*.py``
    name so pytest never collects the seeded violations);
  - suppression and baseline round-trips;
  - the JSON reporter schema pin;
  - the collective-order per-family extraction oracle (recorded in
    PERF.md as the baseline for the step-family unification work);
  - regression pins for the real findings this analyzer surfaced and
    fixed (watchdog fire counter, scheduler active(), elastic beat lock);
  - the v2 inference passes: thread-safety re-detecting both PR 8 races
    from fixtures WITHOUT annotations, resource-lifecycle exception-edge
    leaks, and the generated config schema validating the shipped YAMLs.
"""
import ast
import json
import pathlib
import shutil
import subprocess
import sys

import pytest

from pytorch_distributed_training_tpu import analysis
from pytorch_distributed_training_tpu.analysis import core, report
from pytorch_distributed_training_tpu.analysis.collectives import (
    CollectiveOrderPass,
    extract_collective_sequences,
)
from pytorch_distributed_training_tpu.analysis.configschema import (
    ConfigSchemaPass,
    extract_schema,
    schema_as_json,
)
from pytorch_distributed_training_tpu.analysis.conventions import MarkerConventionPass
from pytorch_distributed_training_tpu.analysis.donation import DonationSafetyPass
from pytorch_distributed_training_tpu.analysis.lifecycle import ResourceLifecyclePass
from pytorch_distributed_training_tpu.analysis.locks import LockDisciplinePass
from pytorch_distributed_training_tpu.analysis.purity import TracePurityPass
from pytorch_distributed_training_tpu.analysis.threads import ThreadSafetyPass

REPO = pathlib.Path(__file__).parent.parent
PKG = REPO / "pytorch_distributed_training_tpu"
FIXTURES = pathlib.Path(__file__).parent / "analysis_fixtures"


def _fixture_findings(pass_cls, *names):
    """Run one pass over just the named fixture files."""
    ctx = core.AnalysisContext(package_root=FIXTURES, repo_root=FIXTURES.parent)
    modules = [
        m
        for m in core.collect_modules(FIXTURES, FIXTURES.parent)
        if pathlib.Path(m.rel).name in names
    ]
    assert len(modules) == len(names), f"missing fixture(s) among {names}"
    return pass_cls().run(modules, ctx)


# --------------------------------------------------------------------- gate


def test_package_tree_has_zero_unsuppressed_findings():
    """THE gate: the analyzer over the real package tree is clean.  Any
    new impurity in a traced closure, naked guarded access, divergent
    collective, donation misuse, or convention break fails here."""
    result = analysis.run()
    assert not result.unsuppressed, "\n".join(
        f.format() for f in result.unsuppressed
    )
    assert result.files_scanned > 50  # the scan really covered the tree


# ----------------------------------------------------------- trace purity


def test_purity_pass_flags_seeded_violations():
    findings = _fixture_findings(TracePurityPass, "purity_violation.py")
    messages = "\n".join(f.message for f in findings)
    assert "time.time" in messages  # direct clock in a jitted def
    assert "np.random.normal" in messages  # host RNG
    assert "os.getenv" in messages  # env read via closure helper
    assert "print" in messages  # host I/O in a built step
    assert "global _STEP_COUNT" in messages  # module-global mutation
    assert "random.random" in messages  # RNG in a lax.scan body
    # the closure attribution names the helper AND its trace root
    assert any(
        "env_helper" in f.message and "step" in f.message for f in findings
    )
    assert len(findings) >= 6


def test_purity_pass_accepts_clean_fixture():
    assert _fixture_findings(TracePurityPass, "purity_clean.py") == []


# --------------------------------------------------------- lock discipline


def test_locks_pass_flags_seeded_violations():
    findings = _fixture_findings(LockDisciplinePass, "locks_violation.py")
    msgs = [f.message for f in findings]
    assert len(findings) == 4, msgs
    assert any("_count written" in m and "bump" in m for m in msgs)
    assert any("_count read" in m and "LeakyCounter.read" in m for m in msgs)
    # the hoisted-out-of-with read in watermark()
    assert any("_high_water read" in m and "watermark" in m for m in msgs)
    # the nested thread-target def: lock NOT held at call time
    assert any("_count written" in m and "start_worker" in m for m in msgs)


def test_locks_pass_accepts_clean_fixture():
    # _locked suffix, def-line guarded-by comment, and with-blocks all
    # count as holding the lock; __init__ is exempt
    assert _fixture_findings(LockDisciplinePass, "locks_clean.py") == []


# -------------------------------------------------------- collective order


def test_collectives_pass_flags_host_divergent_branches():
    findings = _fixture_findings(CollectiveOrderPass, "collectives_violation.py")
    msgs = [f.message for f in findings]
    assert len(findings) == 3, msgs
    assert any("psum" in m and "process_index" in m for m in msgs)
    assert any("all_gather" in m and "os.environ" in m for m in msgs)
    assert any("psum" in m and "process_count" in m for m in msgs)  # IfExp


def test_collectives_pass_accepts_uniform_branches():
    # config-driven branches are host-uniform: no finding
    assert _fixture_findings(CollectiveOrderPass, "collectives_clean.py") == []


def test_collective_extraction_reads_family_and_order():
    seqs = extract_collective_sequences(FIXTURES, FIXTURES.parent)
    bad = seqs["fixture-bad"]
    assert [c.op for c in bad["build_divergent_step"]] == ["psum", "pmean"]
    good = seqs["fixture-good"]
    assert [c.op for c in good["build_plain_step"]] == ["psum", "pmean"]
    assert all(c.axis == "'data'" for c in good["build_plain_step"])


# -------------------------------------------------------- donation safety


def test_donation_pass_flags_seeded_violations():
    findings = _fixture_findings(DonationSafetyPass, "donation_violation.py")
    msgs = [f.message for f in findings]
    assert len(findings) == 3, msgs
    assert any(
        "`state` used after being donated to `train_step`" in m for m in msgs
    )
    assert any(
        "`state` used after being donated to `apply_update`" in m for m in msgs
    )
    assert any("out of range" in m and "bad_arity_step" in m for m in msgs)


def test_donation_pass_accepts_consume_and_rebind():
    assert _fixture_findings(DonationSafetyPass, "donation_clean.py") == []


# ------------------------------------------------------- marker convention


def test_marker_pass_flags_seeded_test_violations(tmp_path):
    # the fixture body is stored under a non-test name; give it a
    # collectable name only inside the throwaway tests dir
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir()
    shutil.copy(
        FIXTURES / "marker_violation_body.py",
        tests_dir / "test_seeded_markers.py",
    )
    ctx = core.AnalysisContext(
        package_root=FIXTURES, repo_root=tmp_path, tests_dir=tests_dir
    )
    findings = MarkerConventionPass().run([], ctx)
    msgs = [f.message for f in findings]
    assert len(findings) == 1, msgs
    assert "test_unmarked_fault_chaos" in msgs[0]
    # the properly-marked twins must NOT be flagged
    assert not any("properly_marked" in m for m in msgs)


def test_marker_pass_flags_counter_stores():
    findings = _fixture_findings(
        MarkerConventionPass, "counter_store_violation.py"
    )
    counter_findings = [
        f for f in findings if "ad-hoc counter store" in f.message
    ]
    # self._counters = {} in __init__ and the module-level Counter()
    assert len(counter_findings) == 2, [f.format() for f in counter_findings]


# ----------------------------------------------------------- suppressions


def test_suppression_trailing_and_line_above_forms():
    ctx = core.AnalysisContext(package_root=FIXTURES, repo_root=FIXTURES.parent)
    modules = [
        m
        for m in core.collect_modules(FIXTURES, FIXTURES.parent)
        if pathlib.Path(m.rel).name == "suppression_mix.py"
    ]
    # run through run_passes-style folding by checking is_suppressed
    findings = TracePurityPass().run(modules, ctx)
    assert len(findings) == 3  # the pass itself sees all three
    mod = modules[0]
    live = [f for f in findings if not mod.is_suppressed(f)]
    dropped = [f for f in findings if mod.is_suppressed(f)]
    assert len(live) == 1 and "raw_violation" in live[0].message
    assert len(dropped) == 2


def test_wildcard_suppression(tmp_path):
    src = (
        "import time, jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x + time.time()  # pdt: ignore[*] -- fixture\n"
    )
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(src)
    result = analysis.run(package_root=pkg)
    assert not result.unsuppressed
    assert len(result.suppressed) == 1


# --------------------------------------------------------------- baseline


def test_baseline_round_trip(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    shutil.copy(FIXTURES / "donation_violation.py", pkg / "legacy.py")
    first = analysis.run(package_root=pkg)
    assert first.unsuppressed  # the violations are live...
    bl = tmp_path / "baseline.json"
    core.write_baseline(bl, first.unsuppressed)
    second = analysis.run(package_root=pkg, baseline=bl)
    assert not second.unsuppressed  # ...then adopted by the baseline
    assert len(second.baselined) == len(first.unsuppressed)
    # baseline keys are line-independent: prepending a comment moves
    # every line but resurrects nothing
    legacy = pkg / "legacy.py"
    legacy.write_text("# moved\n" + legacy.read_text())
    third = analysis.run(package_root=pkg, baseline=bl)
    assert not third.unsuppressed


def test_baseline_rejects_unknown_version(tmp_path):
    bad = tmp_path / "b.json"
    bad.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError):
        core.load_baseline(bad)


# ------------------------------------------------------------ JSON schema


def test_json_reporter_schema_pin():
    result = analysis.run(rules=["donation-safety"])
    payload = report.json_payload(result)
    assert payload["version"] == 1
    assert set(payload) == {"version", "findings", "summary"}
    assert set(payload["summary"]) == {
        "unsuppressed",
        "suppressed",
        "baselined",
        "by_rule",
        "files_scanned",
        "wall_s",
    }
    for f in payload["findings"]:
        assert set(f) == {"rule", "severity", "path", "line", "message"}
    # and it must be round-trippable text
    assert json.loads(report.render_json(result)) == payload


def test_unknown_rule_is_rejected():
    with pytest.raises(ValueError, match="unknown rule"):
        analysis.run(rules=["no-such-rule"])


# ------------------------------------------------------------------- CLI


def test_cli_exits_zero_on_package_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "pytorch_distributed_training_tpu.analysis"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "pdt-analyze:" in proc.stdout


def test_cli_exits_one_on_violations_and_emits_json(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    shutil.copy(FIXTURES / "purity_violation.py", pkg / "mod.py")
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytorch_distributed_training_tpu.analysis",
            "--root",
            str(pkg),
            "--format",
            "json",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["summary"]["unsuppressed"] > 0


# ----------------------------------------- collective-order family oracle


def test_collective_order_oracle_matches_perf_md():
    """The per-family collective sequences of the four step families,
    pinned as the baseline oracle for the step-family unification work
    (ROADMAP item 3, recorded in PERF.md).  A refactor that unifies the
    step builders must reproduce these sequences EXACTLY — reordering or
    dropping a collective changes multi-host semantics."""
    seqs = extract_collective_sequences(PKG)
    assert set(seqs) == {"dp", "sp", "tp", "pp"}

    def ops(family, builder):
        return [c.op for c in seqs[family][builder]]

    # dp/sp train steps: the objective's reduction inside the differentiated
    # function is the only gradient collective (shard_map's transpose does
    # the rest); dp's second pmean keeps local BN statistics replicated
    # when sync_bn is off.  This is the sequence the step traces, not a
    # union over option arms.
    assert ops("dp", "build_train_step") == ["pmean", "pmean"]
    assert ops("dp", "build_eval_step") == ["pmean"]
    assert ops("dp", "build_eval_step_exact") == ["psum"]
    assert ops("sp", "build_lm_train_step") == ["psum"]
    assert ops("sp", "build_lm_eval_step") == ["psum", "pmean"]
    assert ops("pp", "build_pp_lm_train_step") == [
        "ppermute",
        "psum",
        "ppermute",
        "ppermute",
        "psum",
    ]
    assert ops("pp", "build_pp_lm_eval_step") == [
        "ppermute",
        "psum",
        "psum",
        "psum",
    ]
    # TP is GSPMD-compiled: the partitioner inserts its collectives.  The one
    # the source spells out is the pmean closing the fused-CE shard_map island
    # (a Mosaic kernel cannot be partitioned automatically; PR 21).
    assert set(seqs["tp"]) == {"_token_ce"}
    assert ops("tp", "_token_ce") == ["pmean"]


# ------------------------------------- regression pins for the real fixes


def _method(tree, cls_name, meth_name):
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == cls_name:
            for item in node.body:
                if (
                    isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and item.name == meth_name
                ):
                    return item
    raise AssertionError(f"{cls_name}.{meth_name} not found")


def test_watchdog_fire_counter_updates_under_lock():
    """pdt-analyze finding (fixed this PR): StepWatchdog._run bumped
    ``self.fires`` outside ``self._lock`` — a racy read-modify-write
    against any thread polling the counter.  Pin that every ``fires``
    write outside __init__ sits inside a with-block."""
    src = (PKG / "engine" / "watchdog.py").read_text()
    tree = ast.parse(src)
    run = _method(tree, "StepWatchdog", "_run")
    writes = [
        n
        for n in ast.walk(run)
        for t in (
            n.targets if isinstance(n, ast.Assign) else [n.target]
            if isinstance(n, ast.AugAssign) else []
        )
        if isinstance(t, ast.Attribute) and t.attr == "fires"
    ]
    assert writes, "the fire-count bump disappeared from _run"
    with_lines = [
        (n.lineno, n.end_lineno) for n in ast.walk(run) if isinstance(n, ast.With)
    ]
    for w in writes:
        assert any(a <= w.lineno <= b for a, b in with_lines), (
            "self.fires bumped outside the lock again"
        )
    # and the declared guard means the analyzer itself now pins this too
    ctx = core.AnalysisContext(package_root=PKG, repo_root=REPO)
    modules = [
        m
        for m in core.collect_modules(PKG, REPO)
        if m.rel.endswith("engine/watchdog.py")
    ]
    assert LockDisciplinePass().run(modules, ctx) == []


def test_scheduler_active_snapshots_under_condition():
    """pdt-analyze audit finding (fixed this PR): ContinuousScheduler
    .active() read the slot list without the condition while
    _fail_inflight rebinds it wholesale under the lock.  Pin that the
    slot scan sits inside ``with self._cond``."""
    src = (PKG / "serving" / "scheduler.py").read_text()
    active = _method(ast.parse(src), "ContinuousScheduler", "active")
    withs = [n for n in ast.walk(active) if isinstance(n, ast.With)]
    assert withs, "active() no longer takes the condition"
    guarded_src = ast.unparse(withs[0])
    assert "self._cond" in guarded_src and "_slots" in guarded_src


def test_framework_registers_all_eight_passes():
    rules = {cls.rule for cls in analysis.ALL_PASSES}
    assert rules == {
        "trace-purity",
        "lock-discipline",
        "collective-order",
        "donation-safety",
        "marker-convention",
        "thread-safety",
        "resource-lifecycle",
        "config-schema",
    }


def test_unregistered_pass_fails_the_registration_pin(tmp_path):
    """A new AnalysisPass subclass that never lands in ALL_PASSES is
    itself a marker-convention finding — the framework refuses to let a
    pass exist that runs nowhere."""
    pkg = tmp_path / "pkg"
    ana = pkg / "analysis"
    ana.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (ana / "__init__.py").write_text("ALL_PASSES = ()\n")
    (ana / "rogue.py").write_text(
        "from ..core import AnalysisPass\n\n\n"
        "class RoguePass(AnalysisPass):\n"
        "    rule = 'rogue'\n"
    )
    ctx = core.AnalysisContext(package_root=pkg, repo_root=tmp_path)
    modules = core.collect_modules(pkg, tmp_path)
    findings = MarkerConventionPass().run(modules, ctx)
    assert any(
        "RoguePass" in f.message and "ALL_PASSES" in f.message for f in findings
    )


# --------------------------------------------- serving fault-tolerance gate


def test_cli_clean_on_serving_modules():
    """PR 9 gate: the serving tree (scheduler + resilience + kv pool +
    engine) passes every analysis pass — in particular lock-discipline
    over the supervisor's cross-thread restart counters and the
    scheduler's cond-guarded queue/drain/hang state."""
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytorch_distributed_training_tpu.analysis",
            "--root",
            str(PKG / "serving"),
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_serving_recovery_state_is_lock_annotated():
    """The cross-thread recovery state must stay VISIBLY guarded: the
    lock-discipline pass keys off ``# guarded by:`` annotations, so
    silently dropping them would also silently drop its coverage of the
    supervisor and scheduler."""
    sup = (PKG / "serving" / "resilience.py").read_text()
    assert sup.count("# guarded by: self._lock") >= 2  # _restarts, _exhausted
    sched = (PKG / "serving" / "scheduler.py").read_text()
    # queue/close/drain/hang state all ride the scheduler condition
    assert sched.count("# guarded by: self._cond") >= 5
    # the fleet router's shared state (outstanding requests, down-set,
    # failover queue, sticky map) rides the router lock — and the
    # declarations are what lets the lock-discipline pass police every
    # submit/deliver/failover path against it
    router = (PKG / "serving" / "router.py").read_text()
    assert router.count("# guarded by: self._lock") >= 6


# ------------------------------------ v2: inferred-lockset thread safety


def test_thread_pass_redetects_both_pr8_races_without_annotations():
    """THE v2 acceptance bar: the fixtures replay the watchdog fire-count
    bump and the scheduler slot snapshot — the two real races PR 8's
    annotation-based pass caught — with every ``# guarded by:`` comment
    stripped.  Inference alone must flag both."""
    src = (FIXTURES / "threads_violation.py").read_text()
    assert "guarded by" not in src  # nothing for the annotation pass to key off
    findings = _fixture_findings(ThreadSafetyPass, "threads_violation.py")
    messages = "\n".join(f.message for f in findings)
    assert "self.fires in RacyWatchdog" in messages  # PR 8 race shape #1
    assert "thread:_run" in messages
    assert "self._slots in RacyScheduler" in messages  # PR 8 race shape #2
    assert "thread:_loop" in messages
    # the lock-ridden queue in RacyScheduler must NOT be flagged: both
    # sides take self._lock, and the inferred locksets intersect
    assert "_queue" not in messages


def test_thread_pass_verifies_confinement_declarations():
    findings = _fixture_findings(ThreadSafetyPass, "threads_violation.py")
    messages = "\n".join(f.message for f in findings)
    # naming a root that does not exist is itself a finding...
    assert "_nonexistent" in messages
    # ...and so is an api-side write into loop-confined state
    assert "written from root api (in reset)" in messages
    assert len(findings) == 4  # the two races + the two confinement breaks


def test_thread_pass_clean_fixture_stays_clean():
    """Locked, confined-and-honored, and message-passing twins of the
    racy shapes produce zero findings."""
    assert _fixture_findings(ThreadSafetyPass, "threads_clean.py") == []


def test_thread_suppression_round_trip(tmp_path):
    """``# pdt: ignore[thread-safety]`` on the write line suppresses the
    race finding and is accounted as suppressed, not dropped."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "racy.py").write_text(
        "import threading\n\n\n"
        "class R:\n"
        "    def __init__(self):\n"
        "        self.n = 0\n"
        "        self._t = threading.Thread(target=self._run, daemon=True)\n"
        "        self._t.start()\n\n"
        "    def _run(self):\n"
        "        self.n += 1  # pdt: ignore[thread-safety]\n\n"
        "    def read(self):\n"
        "        return self.n\n"
    )
    result = analysis.run(package_root=pkg, rules=["thread-safety"])
    assert result.unsuppressed == []
    assert len(result.suppressed) == 1


# ----------------------------------------------- v2: resource lifecycle


def test_lifecycle_pass_flags_seeded_leaks():
    findings = _fixture_findings(ResourceLifecyclePass, "lifecycle_violation.py")
    messages = "\n".join(f.message for f in findings)
    # the in-flight-future bug class: a call between acquire and resolve
    # can raise, leaving the caller blocked on a future nobody resolves
    assert "leak_on_exception_edge" in messages and "exception edge" in messages
    assert "definite_future_leak" in messages and "never reaches" in messages
    assert "unjoined_worker" in messages and "join" in messages
    assert "file_leak_on_exception" in messages
    assert len(findings) == 4


def test_lifecycle_clean_fixture_stays_clean():
    """finally/except release, ownership escapes, daemon exemption and
    with-managed handles are all recognized as safe."""
    assert _fixture_findings(ResourceLifecyclePass, "lifecycle_clean.py") == []


def test_new_rules_baseline_round_trip(tmp_path):
    """A baseline written against the v2 findings silences exactly those
    findings on re-run — adoption path for a tree not yet clean."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    for name in ("threads_violation.py", "lifecycle_violation.py"):
        shutil.copy(FIXTURES / name, pkg / name)
    rules = ["thread-safety", "resource-lifecycle"]
    first = analysis.run(package_root=pkg, rules=rules)
    assert len(first.unsuppressed) == 8
    baseline = tmp_path / "baseline.json"
    core.write_baseline(baseline, first.unsuppressed)
    second = analysis.run(package_root=pkg, rules=rules, baseline=baseline)
    assert second.unsuppressed == []
    assert len(second.baselined) == 8


# --------------------------------------------------- v2: config schema


def _configschema_findings(fixture, config_dirname):
    ctx = core.AnalysisContext(
        package_root=FIXTURES,
        repo_root=FIXTURES.parent,
        config_dir=FIXTURES / config_dirname,
    )
    modules = [
        m
        for m in core.collect_modules(FIXTURES, FIXTURES.parent)
        if pathlib.Path(m.rel).name == fixture
    ]
    assert modules, f"missing fixture {fixture}"
    return ConfigSchemaPass().run(modules, ctx)


def test_configschema_flags_unknown_key_and_type_mismatch():
    findings = _configschema_findings("configschema_parser.py", "configs_violation")
    messages = "\n".join(f.message for f in findings)
    assert "unknown key training.widget.treshold" in messages  # the typo
    assert "type mismatch for training.widget.mode" in messages
    assert len(findings) == 2
    # both findings point into the YAML file, at the offending lines
    assert all(f.path.endswith("bad.yml") for f in findings)


def test_configschema_clean_yaml_validates():
    assert _configschema_findings("configschema_parser.py", "configs_clean") == []


def test_configschema_flags_dead_allowset_key():
    findings = _configschema_findings("configschema_dead_key.py", "no_such_configs")
    assert len(findings) == 1
    assert "retired_knob" in findings[0].message
    assert "dead key" in findings[0].message
    assert findings[0].path.endswith("configschema_dead_key.py")


def test_configschema_extraction_shape():
    """The generated schema records section closure, key types and
    defaults — the machine-readable config reference ``--schema`` dumps."""
    modules = [
        m
        for m in core.collect_modules(FIXTURES, FIXTURES.parent)
        if pathlib.Path(m.rel).name == "configschema_parser.py"
    ]
    dump = schema_as_json(extract_schema(modules))
    widget = dump["training.widget"]
    assert widget["closed"] is True
    assert set(widget["keys"]) == {"enabled", "threshold", "mode"}
    assert widget["keys"]["threshold"]["type"] == "float"
    assert widget["keys"]["mode"]["type"] == "str"


def test_shipped_configs_validate_against_generated_schema():
    """All shipped config/*.yml files validate against the schema
    inferred from the topology/from_config parsing surface — the
    config-schema slice of the tier-1 gate, pinned explicitly."""
    ctx = core.AnalysisContext(package_root=PKG, repo_root=REPO)
    modules = core.collect_modules(PKG, REPO)
    findings = ConfigSchemaPass().run(modules, ctx)
    assert findings == [], "\n".join(f.format() for f in findings)
    assert len(list((REPO / "config").glob("*.yml"))) == 20
    # and the real schema covers the sections the YAMLs actually use
    dump = schema_as_json(extract_schema(modules))
    for section in ("training", "serving.scheduler", "training.checkpoint"):
        assert section in dump, f"schema lost the {section} section"


def test_cli_schema_flag_dumps_json():
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytorch_distributed_training_tpu.analysis",
            "--schema",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    dump = json.loads(proc.stdout)
    assert "training" in dump and "serving.fleet" in dump
    assert dump["serving.fleet"]["closed"] is True  # the dict-pop idiom
    # training.comm is refused by the Runner, not parsed: no section, no leaf
    assert "training.comm" not in dump
    assert "comm" not in dump["training"]["keys"]


# ----------------------------- regression pin: elastic heartbeat beat lock


def test_elastic_generation_and_seq_update_under_beat_lock():
    """pdt-analyze v2 finding (fixed this PR): ElasticCoordinator.start()
    bumped ``self.generation`` while the beat thread read it — and
    close() joins with a TIMEOUT, so the final stopped-beat write can
    genuinely overlap a still-live loop iteration.  Pin that the beat
    payload writes sit inside ``with self._beat_lock`` and that both
    inference and annotation passes stay clean on the module."""
    src = (PKG / "engine" / "elastic.py").read_text()
    tree = ast.parse(src)
    assert src.count("# guarded by: self._beat_lock") >= 2  # generation, _seq
    write_beat = _method(tree, "ElasticCoordinator", "_write_beat")
    withs = [n for n in ast.walk(write_beat) if isinstance(n, ast.With)]
    assert withs and "self._beat_lock" in ast.unparse(withs[0])
    assert "_seq" in ast.unparse(withs[0])  # the payload build rides the lock
    ctx = core.AnalysisContext(package_root=PKG, repo_root=REPO)
    modules = [
        m
        for m in core.collect_modules(PKG, REPO)
        if m.rel.endswith("engine/elastic.py")
    ]
    assert ThreadSafetyPass().run(modules, ctx) == []
    assert LockDisciplinePass().run(modules, ctx) == []
