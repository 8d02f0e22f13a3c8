"""Unified telemetry layer (telemetry/): registry, spans, goodput,
retrace probe, sinks, on-demand capture, and the Telemetry facade.

Most of these run without JAX (the core modules are stdlib-only by
design); the retrace-probe tests build a real ``jax.jit`` function
because the probe's whole contract is reading jit's executable cache.
"""
import json
import logging
import os
import signal
import threading

import numpy as np
import pytest

from pytorch_distributed_training_tpu.engine import fault
from pytorch_distributed_training_tpu.telemetry import (
    GoodputTracker,
    Histogram,
    JitCacheProbe,
    JsonlSink,
    MetricsRegistry,
    OnDemandProfiler,
    SpanRecorder,
    Telemetry,
    TensorBoardSink,
    get_registry,
    parse_signal,
    reset_registry,
    set_recorder,
    span,
    summary_table,
)
from pytorch_distributed_training_tpu.telemetry.registry import _percentile


@pytest.fixture(autouse=True)
def _clean_registry():
    reset_registry()
    yield
    reset_registry()
    set_recorder(None)


# ------------------------------------------------------------------ registry
def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(4)
    assert reg.counter("hits").value == 5
    g = reg.gauge("depth")
    g.set(3)
    g.set(1)
    assert g.value == 1.0
    assert g.max == 3.0
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 5
    assert snap["gauges"]["depth"] == {"value": 1.0, "max": 3.0}


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


def test_registry_reset_keeps_instrument_identity():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.inc(7)
    reg.reset()
    assert c.value == 0
    c.inc()
    # the SAME object keeps flowing into the same name — call sites cache it
    assert reg.counter("n") is c
    assert reg.counter("n").value == 1


def test_percentile_matches_numpy_linear_interpolation():
    rng = np.random.default_rng(0)
    for n in (1, 2, 5, 100, 257):
        vals = sorted(rng.normal(size=n).tolist())
        for q in (50, 95, 99):
            assert _percentile(vals, q) == pytest.approx(
                float(np.percentile(vals, q)), rel=1e-12, abs=1e-12
            )


def test_histogram_exact_moments_bounded_sample():
    h = Histogram("t", reservoir_size=64)
    for v in range(1000):
        h.observe(float(v))
    snap = h.snapshot()
    # count/sum/mean/min/max are EXACT regardless of eviction
    assert snap["count"] == 1000
    assert snap["sum"] == pytest.approx(sum(range(1000)))
    assert snap["mean"] == pytest.approx(499.5)
    assert snap["min"] == 0.0 and snap["max"] == 999.0
    # storage stays bounded at the reservoir
    assert len(h._sample) == 64


def test_histogram_percentiles_stable_under_eviction():
    # uniform stream far beyond the reservoir: the Algorithm-R sample is a
    # uniform draw of the WHOLE stream, so percentiles track the true ones.
    # The reservoir RNG is seeded from hash(name), which varies per process;
    # at n=2048 the p50 estimator's std is ~2.2%, so 10% is >4 sigma.
    h = Histogram("u", reservoir_size=2048)
    for v in range(50_000):
        h.observe(float(v))
    snap = h.snapshot()
    assert len(h._sample) == 2048
    assert snap["p50"] == pytest.approx(25_000, rel=0.10)
    assert snap["p95"] == pytest.approx(47_500, rel=0.05)
    assert snap["p99"] == pytest.approx(49_500, rel=0.05)


def test_histogram_rejects_empty_reservoir():
    with pytest.raises(ValueError, match="reservoir_size"):
        Histogram("bad", reservoir_size=0)


def test_fault_counters_are_registry_views():
    fault.reset_counters()
    fault.bump("rollbacks", 2)
    assert fault.counters()["rollbacks"] == 2
    assert get_registry().counter("rollbacks").value == 2
    fault.reset_counters()
    # zeroed counters stay registered but vanish from the dict view — the
    # existing `"x" not in counters()` test assertions depend on this
    assert "rollbacks" not in fault.counters()


# --------------------------------------------------------------------- spans
def test_span_recorder_ring_and_file(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    rec = SpanRecorder(path=path, ring=4, host=3)
    with rec.span("data_wait", step=1):
        pass
    with rec.span("step_dispatch", step=1, what="train"):
        with rec.span("device_block", step=1):
            pass
    rec.close()
    lines = [json.loads(ln) for ln in open(path)]
    assert [r["kind"] for r in lines] == [
        "data_wait", "device_block", "step_dispatch",  # inner closes first
    ]
    r0 = lines[0]
    assert r0["step"] == 1 and r0["host"] == 3
    assert r0["ms"] >= 0.0 and "t" in r0 and "wall" in r0
    assert lines[2]["what"] == "train"


def test_span_recorder_ring_bounded():
    rec = SpanRecorder(ring=3)
    for i in range(10):
        with rec.span("k", step=i):
            pass
    recent = rec.recent(100)
    assert len(recent) == 3
    assert [r["step"] for r in recent] == [7, 8, 9]


def _sleep(seconds):
    import time

    time.sleep(seconds)


def _spin(seconds):
    import time

    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("work, on_cpu", [(_sleep, False), (_spin, True)],
                         ids=["sleep", "busy_loop"])
def test_span_cpu_option_records_the_threads_cpu_time(tmp_path, work, on_cpu):
    """``cpu=True``: ``cpu_ms`` near 0 around a sleep (a blocked thread
    burns no CPU time) and near the wall around a busy loop, in the ring and
    in the span file, and never among the fields a caller passed."""
    path = str(tmp_path / "spans.jsonl")
    rec = SpanRecorder(path=path, ring=4)
    with rec.span("step_dispatch", step=3, cpu=True, what="train"):
        work(0.05)
    rec.close()
    (line,) = [json.loads(ln) for ln in open(path)]
    assert line == rec.recent()[0]
    assert line["ms"] >= 50.0 and line["what"] == "train" and "cpu" not in line
    if on_cpu:
        # (a busy thread can lose its core to a neighbour for a while)
        assert 0.5 * line["ms"] <= line["cpu_ms"] <= line["ms"] + 0.5
    else:
        assert 0.0 <= line["cpu_ms"] < 10.0


def test_span_without_the_cpu_option_records_no_cpu_time():
    rec = SpanRecorder(ring=4)
    set_recorder(rec)
    with rec.span("step_dispatch", step=3):
        pass
    with span("step_dispatch", step=4, cpu=False):
        pass
    with span("step_dispatch", step=5, cpu=True):  # the free function too
        pass
    first, second, third = rec.recent()
    assert "cpu_ms" not in first and "cpu_ms" not in second
    assert "cpu" not in second and third["cpu_ms"] >= 0.0


def test_free_span_function_routes_to_current_recorder(tmp_path):
    rec = SpanRecorder(ring=8)
    set_recorder(rec)
    # deep call sites (checkpoint writer thread, elastic guard) use the
    # module-level span() without plumbing a recorder through constructors
    with span("ckpt_async_write", step=5):
        pass
    assert rec.recent(1)[0]["kind"] == "ckpt_async_write"


def test_span_from_worker_thread_lands_in_shared_ring():
    rec = SpanRecorder(ring=8)
    set_recorder(rec)

    def _work():
        with span("bg", step=0):
            pass

    t = threading.Thread(target=_work)
    t.start()
    t.join()
    recs = rec.recent(1)
    assert recs[0]["kind"] == "bg"
    assert recs[0]["thread"] != threading.main_thread().name


def test_span_parent_follows_nesting_per_thread_not_across_threads():
    rec = SpanRecorder(ring=16)
    set_recorder(rec)
    inside = threading.Event()
    release = threading.Event()

    def _other():
        # opened while the main thread is inside data_wait: no child of it
        inside.wait(timeout=10)
        with span("batch_assemble", n=4):
            pass
        release.set()

    t = threading.Thread(target=_other)
    t.start()
    with span("data_wait", step=3):
        inside.set()
        assert release.wait(timeout=10)
        with rec.span("loader_wait"):  # the method and the free function
            pass                       # share one stack of open spans
        with span("h2d_put", step=3, bytes=64):
            pass
    t.join(timeout=10)
    assert not t.is_alive()
    with span("step_dispatch", step=3):
        pass
    parents = {r["kind"]: r["parent"] for r in rec.recent()}
    assert parents == {
        "batch_assemble": None, "loader_wait": "data_wait",
        "h2d_put": "data_wait", "data_wait": None, "step_dispatch": None,
    }


def test_record_of_a_finished_interval_has_no_parent():
    import time

    rec = SpanRecorder(ring=4)
    set_recorder(rec)
    t0 = time.monotonic() - 0.25
    with span("tick", step=9):
        # a request's life is known at its retirement, inside a tick
        from pytorch_distributed_training_tpu.telemetry.spans import record

        record("request", t0, 0.25, req=17, tokens=5)
    first = rec.recent()[0]
    assert first["kind"] == "request" and first["parent"] is None
    assert first["req"] == 17 and first["tokens"] == 5
    assert first["ms"] == pytest.approx(250.0) and first["t"] == round(t0, 6)
    assert abs(first["wall"] - (time.time() - 0.25)) < 1.0


def test_span_lies_in_the_profilers_host_plane_with_its_step(tmp_path):
    """One call, two sinks: during a jax.profiler trace a span is also a
    TraceAnnotation, so the .xplane.pb's host plane holds it under its kind
    with ``step`` and the extra fields as the event's stats."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from pytorch_distributed_training_tpu.telemetry.capture import start_trace

    rec = SpanRecorder(ring=8)
    start_trace(str(tmp_path))  # python tracer off: annotations only
    try:
        with jax.profiler.StepTraceAnnotation("train", step_num=41):
            with rec.span("data_wait", step=41):
                with rec.span("h2d_put", step=41, bytes=4096):
                    pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    found = {}
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                names.add(event.name)
                if event.name in ("data_wait", "h2d_put", "train"):
                    found[event.name] = (
                        dict(event.stats), event.start_ns, event.duration_ns
                    )
    assert set(found) == {"data_wait", "h2d_put", "train"}
    assert found["data_wait"][0]["step"] == 41
    assert found["h2d_put"][0] == {"step": 41, "bytes": 4096}
    assert found["train"][0]["step_num"] == 41
    # one clock: the child lies inside its parent on the profiler's time
    outer, inner = found["data_wait"], found["h2d_put"]
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    # and no python frames were collected to name them
    assert not any(name.startswith("$") for name in names)
    assert [r["parent"] for r in rec.recent()] == ["data_wait", None]


def test_spans_import_and_record_where_jax_is_not_loaded():
    """Loader worker processes import telemetry.spans and never JAX: the
    annotation class is taken lazily, and only from a loaded jax."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from pytorch_distributed_training_tpu.telemetry.spans import "
        "SpanRecorder\n"
        "import pytorch_distributed_training_tpu.data.worker_pool\n"
        "rec = SpanRecorder(ring=4)\n"
        "with rec.span('batch_assemble', n=2):\n"
        "    with rec.span('inner'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print([(r['kind'], r['parent']) for r in rec.recent()])\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=root,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == (
        "[('inner', 'batch_assemble'), ('batch_assemble', None)]"
    )


# ------------------------------------------------------------------- goodput
def test_goodput_buckets_and_ratio():
    g = GoodputTracker()
    g.note_step(2.0)                       # productive
    g.note_step(1.0, replayed=True)        # paid-again work after rollback
    g.note_step(0.5, applied=False)        # anomaly-skipped
    g.note_lost("rollback", 1.5)           # restore/rebuild wall time
    snap = g.snapshot()
    assert snap["steps"] == 3
    assert snap["replayed_steps"] == 1
    assert snap["skipped_steps"] == 1
    assert snap["productive_s"] == pytest.approx(2.0)
    assert snap["replay_s"] == pytest.approx(1.0)
    assert snap["skipped_s"] == pytest.approx(0.5)
    assert snap["lost_rollback_s"] == pytest.approx(1.5)
    assert snap["goodput_ratio"] == pytest.approx(2.0 / 5.0)


def test_goodput_empty_snapshot():
    g = GoodputTracker()
    snap = g.snapshot()
    assert snap["steps"] == 0
    assert "goodput_ratio" not in snap  # no time billed -> no ratio claimed
    assert g.ratio() is None


# ------------------------------------------------------------- retrace probe
def test_jit_cache_probe_counts_compiles_and_warns(caplog):
    import jax
    import jax.numpy as jnp

    probe = JitCacheProbe(warn_threshold=2)
    reg = MetricsRegistry()

    @jax.jit
    def f(x):
        return x * 2

    probe.register("bench_step", f)
    f(jnp.zeros((2,)))
    probe.poll(reg)
    assert reg.counter("compiles/bench_step").value == 1
    # new shape every call = the classic retrace storm
    with caplog.at_level(logging.WARNING):
        f(jnp.zeros((3,)))
        f(jnp.zeros((4,)))
        totals = probe.poll(reg)
    assert totals["bench_step"] == 3
    assert reg.counter("compiles/bench_step").value == 3
    assert any("RETRACE STORM" in r.message for r in caplog.records)
    # stable signature: no further compiles, no duplicate warning
    caplog.clear()
    f(jnp.zeros((4,)))
    probe.poll(reg)
    assert reg.counter("compiles/bench_step").value == 3
    assert not caplog.records


def test_jit_cache_probe_weakref_does_not_pin_fns():
    import jax

    probe = JitCacheProbe()

    def build():
        @jax.jit
        def g(x):
            return x + 1

        return probe.register("ephemeral", g)

    build()
    import gc

    gc.collect()
    assert "ephemeral" not in probe.poll(MetricsRegistry())


def test_probe_register_dedupes_live_names():
    probe = JitCacheProbe()

    def f():
        return None

    def g():
        return None

    probe.register("step", f)
    probe.register("step", g)  # f still alive -> suffixed key
    keys = set(probe._entries)
    assert keys == {"step", "step#2"}


# --------------------------------------------------------------------- sinks
def test_jsonl_sink_and_summary_table(tmp_path):
    reg = get_registry()
    reg.counter("rollbacks").inc(2)
    reg.gauge("ckpt_async_inflight").set(1)
    reg.histogram("ckpt_async_stall_ms").observe(12.5)
    snap = reg.snapshot()
    snap["goodput"] = {"steps": 4, "goodput_ratio": 0.75}
    snap["compiles"] = {"train_step/gspmd": 1}

    path = str(tmp_path / "snapshots.jsonl")
    sink = JsonlSink(path)
    sink.emit(snap, step=9)
    sink.emit(snap, step=19)
    sink.close()
    lines = [json.loads(ln) for ln in open(path)]
    assert [l["step"] for l in lines] == [9, 19]
    assert lines[0]["counters"]["rollbacks"] == 2
    assert lines[0]["histograms"]["ckpt_async_stall_ms"]["count"] == 1

    table = summary_table(snap)
    assert "rollbacks" in table
    assert "goodput.ratio" in table
    assert "ckpt_async_stall_ms" in table


def test_summary_table_empty():
    assert "no telemetry" in summary_table(
        {"counters": {}, "gauges": {}, "histograms": {}}
    )


def test_tensorboard_sink_writes_scalars():
    class FakeWriter:
        def __init__(self):
            self.scalars = {}

        def add_scalar(self, tag, value, step):
            self.scalars[tag] = (value, step)

    w = FakeWriter()
    sink = TensorBoardSink(w)
    sink.emit(
        {
            "counters": {"rollbacks": 2},
            "gauges": {"depth": {"value": 1.0, "max": 3.0}},
            "histograms": {"lat": {"count": 2, "p50": 5.0, "p95": 9.0, "p99": 9.9}},
            "goodput": {"goodput_ratio": 0.5},
        },
        step=7,
    )
    assert w.scalars["telemetry/counters/rollbacks"] == (2, 7)
    assert w.scalars["telemetry/gauges/depth"] == (1.0, 7)
    assert w.scalars["telemetry/lat/p50"] == (5.0, 7)
    assert w.scalars["telemetry/goodput_ratio"] == (0.5, 7)


# ------------------------------------------------------------------- capture
def test_parse_signal_forms():
    assert parse_signal(None) is None
    assert parse_signal("SIGUSR2") == signal.SIGUSR2.value
    assert parse_signal("usr2") == signal.SIGUSR2.value
    assert parse_signal(int(signal.SIGUSR1)) == signal.SIGUSR1.value
    with pytest.raises(ValueError, match="unknown capture signal"):
        parse_signal("NOTASIG")


def test_on_demand_profiler_window_bookkeeping(tmp_path, monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: calls.append(("start", d)),
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",))
    )
    prof = OnDemandProfiler(str(tmp_path), n_iters=2, at_iter=3)
    for it in range(6):
        prof.after_step(it)
    # armed after step 2 (it+1 == 3), window covers steps 3..4, closed at 4
    assert [c[0] for c in calls] == ["start", "stop"]
    assert calls[0][1].endswith("capture_0_iter3")
    assert os.path.isdir(calls[0][1])
    assert not prof.tracing
    prof.close()


@pytest.mark.parametrize("window", ["on_demand", "config_window"])
@pytest.mark.parametrize("python_tracer", [False, True])
def test_profiler_windows_pass_the_python_tracer_switch(
    tmp_path, monkeypatch, window, python_tracer
):
    """Both windows start through telemetry.capture.start_trace: the python
    tracer is off unless their section says ``python_tracer: true``."""
    import jax

    from pytorch_distributed_training_tpu.engine import TraceProfiler
    from pytorch_distributed_training_tpu.engine.topology import (
        parse_telemetry,
    )

    seen = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: seen.append(profiler_options),
    )
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    section = {"dir": str(tmp_path)}
    if python_tracer:
        section["python_tracer"] = True
    if window == "on_demand":
        import types

        r = types.SimpleNamespace()
        parse_telemetry(r, {"telemetry": {
            "dir": str(tmp_path),
            "capture": dict(section, at_iter=1, signal=None),
        }})
        tel = Telemetry(
            dir=r.telemetry_dir, use_tensorboard=False,
            capture_signal=None, capture_at_iter=r.telemetry_capture_at_iter,
            capture_python_tracer=r.telemetry_capture_python_tracer,
        )
        tel.after_step(0)
        tel.close()
    else:
        prof = TraceProfiler.from_config({"profile": dict(section, start_iter=0)})
        prof.after_step(0)
        prof.stop()
    (options,) = seen
    assert options.python_tracer_level == (1 if python_tracer else 0)


def test_on_demand_profiler_signal_arm_and_restore(tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d, profiler_options=None: None
    )
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    prev = signal.getsignal(signal.SIGUSR2)
    prof = OnDemandProfiler(
        str(tmp_path), n_iters=1, signum=signal.SIGUSR2.value
    )
    assert signal.getsignal(signal.SIGUSR2) == prof._on_signal
    os.kill(os.getpid(), signal.SIGUSR2)  # handler only latches the flag
    assert prof._armed.wait(timeout=5.0)
    prof.after_step(0)
    assert prof.tracing
    prof.after_step(1)
    assert not prof.tracing
    prof.close()
    assert signal.getsignal(signal.SIGUSR2) == prev


def test_on_demand_profiler_start_failure_is_nonfatal(tmp_path, monkeypatch):
    import jax

    def boom(d, profiler_options=None):
        raise RuntimeError("another trace is live")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    prof = OnDemandProfiler(str(tmp_path), n_iters=1, at_iter=1)
    prof.after_step(0)  # must warn and continue, not raise
    assert not prof.tracing
    prof.close()


# ------------------------------------------------------------------- facade
def test_telemetry_facade_end_to_end(tmp_path):
    tel = Telemetry(
        enabled=True, dir=str(tmp_path), host=0, is_rank0=True,
        snapshot_interval=2, span_ring=16, use_tensorboard=False,
    )
    fault.bump("rollbacks")
    for it in range(4):
        with tel.span("data_wait", step=it):
            pass
        with tel.span("step_dispatch", step=it, cpu=True):  # as the Runner
            pass
        tel.note_step(0.01, applied=True, replayed=it == 1)
        tel.after_step(it)
    diag = tel.diagnostics(n_spans=4)
    assert "step_dispatch" in diag and "rollbacks" in diag
    tel.close(step=3)
    tel.close(step=3)  # idempotent

    snaps = [
        json.loads(ln) for ln in open(os.path.join(tmp_path, "snapshots.jsonl"))
    ]
    # interval exports at steps 1 and 3, plus the final close export
    assert [s["step"] for s in snaps] == [1, 3, 3]
    last = snaps[-1]
    assert last["counters"]["rollbacks"] == 1
    assert last["goodput"]["steps"] == 4
    assert last["goodput"]["replayed_steps"] == 1
    assert last["goodput"]["goodput_ratio"] == pytest.approx(0.75)
    span_lines = [
        json.loads(ln)
        for ln in open(os.path.join(tmp_path, "spans_rank0.jsonl"))
    ]
    assert len(span_lines) == 8
    assert [("cpu_ms" in ln) for ln in span_lines] == [False, True] * 4
    assert "summary" not in last  # snapshot stays structured; table is human


def test_telemetry_disabled_is_inert(tmp_path):
    tel = Telemetry(enabled=False, dir=str(tmp_path / "never"))
    with tel.span("data_wait", step=0):
        pass
    tel.note_step(1.0)
    tel.after_step(0)
    tel.flush()
    tel.close()
    assert not os.path.exists(str(tmp_path / "never"))


def test_telemetry_broken_sink_does_not_stop_export(tmp_path):
    tel = Telemetry(
        enabled=True, dir=str(tmp_path), use_tensorboard=False,
        snapshot_interval=1,
    )

    class Broken:
        def emit(self, snap, step):
            raise RuntimeError("boom")

        def close(self):
            pass

    tel._sinks.insert(0, Broken())
    tel.after_step(0)  # must not raise
    tel.close(step=0)
    assert os.path.exists(os.path.join(tmp_path, "snapshots.jsonl"))


# ------------------------------------------------------- config parse surface
def test_parse_telemetry_defaults_and_validation():
    from pytorch_distributed_training_tpu.engine.topology import parse_telemetry

    class R:
        pass

    r = R()
    parse_telemetry(r, {})
    assert r.telemetry_enabled is True  # in-memory layer is on by default
    assert r.telemetry_dir is None
    assert r.telemetry_interval == 100
    assert r.telemetry_capture_signal is None  # no capture w/o a section

    r = R()
    parse_telemetry(r, {"telemetry": {
        "dir": "/tmp/t", "capture": {"n_iters": 3, "at_iter": 10},
    }})
    assert r.telemetry_capture_signal == signal.SIGUSR2.value
    assert r.telemetry_capture_iters == 3
    assert r.telemetry_capture_at_iter == 10

    with pytest.raises(ValueError, match="unknown key"):
        parse_telemetry(R(), {"telemetry": {"bogus": 1}})
    with pytest.raises(ValueError, match="unknown key"):
        parse_telemetry(R(), {"telemetry": {"capture": {"bogus": 1}}})
    with pytest.raises(ValueError, match="snapshot_interval"):
        parse_telemetry(R(), {"telemetry": {"snapshot_interval": 0}})
    with pytest.raises(ValueError, match="somewhere to write"):
        parse_telemetry(R(), {"telemetry": {"capture": {"at_iter": 5}}})
