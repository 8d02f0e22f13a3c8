"""The readers PR 38 added (``benchmark/tick_spans.py`` and the seven metric
files over it): each over a hand-made trace whose answers are worked out by
hand here, over a parent-style trace (every reader None), through ``--dry``,
and in the manifest."""
import gzip
import json
import os

import pytest

from benchmark import tick_spans
from benchmark.common import Run, load_json, load_module
from benchmark.tests import dryrun

SERVING_READERS = {
    "decode_launch_lag_ms_p50": "device_trace",
    "decode_return_lag_ms_p50": "device_trace",
    "readback_extra_reads_ms_p50": "program_span",
    "tick_gap_ms_p50": "program_span",
    "prefill_stalled_gap_pct": "program_span",
    "prefill_padding_pct": "program_span",
}
TRAINING_READER = "host_dispatch_off_cpu_ms_per_step"
MS = 1e-3


def read(name, run):
    return load_module("metrics", name).read(run)


def run_over(path):
    tick_spans._LOADED.clear()
    run = Run(cell={}, kind="serve", seconds=1.0, chips=1, out_dir="")
    run.notes["xplane"] = path
    return run


# the device's plane keeps a clock of its own: every module stamp of the
# hand-made traces lies 2 ms BEHIND the host's instant (the chip's traces do)
DEVICE_CLOCK = -2.0 * MS


def write(path, spans, device=None):
    """``device``: ``(modules, launches, completions)`` or None (a CPU)."""
    listed = {"spans": spans}
    if device is not None:
        listed.update(zip(("modules", "launches", "completions"), device))
    with gzip.open(path, "wt") as fp:
        json.dump(listed, fp)
    return path


def device_run(run_id, name, enqueued, duration, notice=0.3):
    """A program enqueued at ``enqueued`` (seconds, the host's clock) on an
    idle device that runs it ``duration`` ms: its module event (on the
    device's clock), the runtime's launch event (0.07 ms long, ending at the
    enqueue) and the completion the host hears of ``notice`` ms after the end."""
    end = enqueued + duration * MS
    return (
        {"name": name, "run_id": run_id, "start_s": enqueued + DEVICE_CLOCK,
         "end_s": end + DEVICE_CLOCK},
        {"run_id": run_id, "start_s": enqueued - 0.07 * MS, "end_s": enqueued},
        {"run_id": run_id, "start_s": end + notice * MS,
         "end_s": end + (notice + 0.1) * MS},
    )


def sync_tick(step, t0, active, launch, module, ret, extra, prefill=None,
              cut_after_dispatch=False):
    """One tick of the sync body from ``t0`` (seconds; the parts in ms): the
    spans it leaves and its device run (``device_run``).  ``decode_step``
    lasts 1.0 ms from 0.1 ms into the tick (after the ``prefill``, where it
    has one) and its program is enqueued ``launch`` ms into it; the
    ``readback`` opens 0.02 ms after the span and its child 0.01 ms later; the
    tick closes 0.3 ms after the ``readback``.  Returns (spans, the run, the
    tick's end)."""
    spans = {}
    at = t0 + 0.1 * MS
    if prefill:
        fields = {k: v for k, v in prefill.items() if k != "ms"}
        spans["prefill"] = dict(fields, step=step, start_s=at,
                                end_s=at + prefill["ms"] * MS)
        at = spans["prefill"]["end_s"] + 0.05 * MS
    spans["decode_step"] = {"step": step, "active": active, "start_s": at,
                            "end_s": at + 1.0 * MS}
    ran = device_run(step, "jit_decode_step(77)", at + launch * MS, module)
    if cut_after_dispatch:  # the trace ended inside the device's step
        return spans, ran[:2] + (None,), None
    wait_end = at + (launch + module + ret) * MS
    spans["readback"] = {"step": step, "for_step": step,
                         "start_s": at + 1.02 * MS, "end_s": wait_end + extra * MS}
    spans["readback_wait"] = {"step": step, "for_step": step,
                              "start_s": at + 1.03 * MS, "end_s": wait_end}
    end = spans["readback"]["end_s"] + 0.3 * MS
    spans["tick"] = {"step": step, "start_s": t0, "end_s": end}
    return spans, ran, end


def hand_made(on_a_chip=True):
    """Seven ticks of a traced window:

    10  cut by the trace's start: only its module, read and wait are there
    11  plain                 launch 0.5  module 2.0  return 0.4  extra 0.6
    12  a prefill, 3 rows stalled (700 of 2,048 tokens real)
                              launch 0.7  module 2.2  return 0.2  extra 0.8
        ... the loop sleeps 50 ms ...
    13  finds nothing to do (no decode_step under it)
    14  plain                 launch 0.3  module 2.0  return 0.6  extra 0.2
    15  a prefill into rows none of which decodes (100 of 256 tokens real)
                              launch 0.5  module 2.4  return 0.4  extra 0.6
    16  cut by the trace's end inside the device's step
    gaps between ticks: 11->12 0.2 ms, 14->15 0.4 ms, 15->16 0.3 ms."""
    spans = {kind: [] for kind in tick_spans.KINDS}
    device = ([], [], [])

    def put(found, ran):
        for kind, span in found.items():
            spans[kind].append(span)
        for events, event in zip(device, ran):
            if event is not None:
                events.append(event)

    # tick 10: the trace opened after its dispatch, so its launch is not there
    module, _, completion = device_run(10, "jit_decode_step(77)", 1.0001, 2.0)
    put({}, (module, None, completion))
    spans["readback"].append({"step": 10, "for_step": 10, "start_s": 1.0002, "end_s": 1.0030})
    spans["readback_wait"].append({"step": 10, "for_step": 10, "start_s": 1.0003, "end_s": 1.0025})
    found, mod, end = sync_tick(11, 1.0100, 3, 0.5, 2.0, 0.4, 0.6)
    put(found, mod)
    found, mod, end = sync_tick(
        12, end + 0.2 * MS, 5, 0.7, 2.2, 0.2, 0.8,
        prefill={"rows": 2, "tokens": 700, "bucket": 1024, "stalled": 3,
                 "padded_tokens": 2048, "ms": 30.0})
    put(found, mod)
    spans["loop_idle"].append({"start_s": end + 0.1 * MS, "end_s": end + 50.1 * MS})
    spans["tick"].append({"step": 13, "start_s": end + 50.2 * MS, "end_s": end + 50.3 * MS})
    found, mod, end = sync_tick(14, end + 50.5 * MS, 5, 0.3, 2.0, 0.6, 0.2)
    put(found, mod)
    found, mod, end = sync_tick(
        15, end + 0.4 * MS, 6, 0.5, 2.4, 0.4, 0.6,
        prefill={"rows": 1, "tokens": 100, "bucket": 256, "stalled": 0,
                 "padded_tokens": 256, "ms": 10.0})
    put(found, mod)
    found, mod, _ = sync_tick(16, end + 0.3 * MS, 6, 0.5, 2.0, 0.0, 0.0,
                              cut_after_dispatch=True)
    put(found, mod)
    # tick 12's prefill program: enqueued and over before the tick's decode
    # step is dispatched, and no decode step's module
    put({}, device_run(7, "jit_prefill(5)", spans["prefill"][0]["start_s"] + 0.5 * MS, 29.0))
    return spans, (device if on_a_chip else None)


def test_each_reader_over_a_hand_made_trace(tmp_path):
    spans, device = hand_made()
    run = run_over(write(str(tmp_path / "hand.json.gz"), spans, device))
    # medians of [0.3, 0.5, 0.5, 0.7], [0.2, 0.4, 0.4, 0.6], [0.2, 0.6, 0.6, 0.8]
    assert read("decode_launch_lag_ms_p50", run) == pytest.approx(0.5)
    assert read("decode_return_lag_ms_p50", run) == pytest.approx(0.4)
    assert read("readback_extra_reads_ms_p50", run) == pytest.approx(0.6)
    # 11->12 and 14->15; 12->13->14 has the sleep and the idle tick in it,
    # and tick 16 has no span of its own
    assert read("tick_gap_ms_p50", run) == pytest.approx(0.3)
    # 3 rows stalled of 3 + 5 + 5 + 6 + 6 rows stepped
    assert read("prefill_stalled_gap_pct", run) == pytest.approx(12.0)
    # 800 real tokens of 2,048 + 256 run
    assert read("prefill_padding_pct", run) == pytest.approx(100 * (1 - 800 / 2304))
    # four steps paired; dropped: tick 16's span, and the modules of 10 and 16
    assert run.notes["tick_spans"] == {
        "paired": 4, "dropped": 3, "unsound": 0,
        "notice_ms_p50": pytest.approx(0.3), "clock_offset_ms_p50": pytest.approx(2.0)}
    # parts 3.5, 3.9, 3.1, 3.9 ms over phases 0.02 ms shorter each
    assert run.notes[tick_spans.IDENTITY] == pytest.approx(3.7 / 3.68)
    low, high = tick_spans.IDENTITY_RANGE
    assert low <= run.notes[tick_spans.IDENTITY] <= high


def test_a_trace_without_module_events_keeps_the_span_readers(tmp_path):
    """A CPU's trace: the host plane's annotations and no ``XLA Modules``."""
    spans, _ = hand_made(on_a_chip=False)
    run = run_over(write(str(tmp_path / "cpu.json.gz"), spans))
    assert read("decode_launch_lag_ms_p50", run) is None
    assert read("decode_return_lag_ms_p50", run) is None
    assert read("readback_extra_reads_ms_p50", run) == pytest.approx(0.6)
    assert read("tick_gap_ms_p50", run) == pytest.approx(0.3)
    assert read("prefill_stalled_gap_pct", run) == pytest.approx(12.0)
    assert run.notes["tick_spans"] == {
        "paired": 4, "dropped": 1, "unsound": 0, "notice_ms_p50": None,
        "clock_offset_ms_p50": None}
    assert tick_spans.IDENTITY not in run.notes


def test_a_host_that_knows_before_the_device_is_through_is_counted(tmp_path):
    """The device's stamps are never laid on the host's clock; where the
    host's own events contradict the queue built from them (it hears of a
    step's end, or holds its tokens, before that end), the step counts as
    unsound: the pairing is wrong, and the builder mends it."""
    spans, device = hand_made()
    # tick 14's completion 1.5 ms early: before its 2.0 ms step can be over
    next(c for c in device[2] if c["run_id"] == 14)["start_s"] -= 1.5 * MS
    # tick 11's first read returns 1.0 ms early: 0.6 ms before the step ends
    next(w for w in spans["readback_wait"] if w["for_step"] == 11)["end_s"] -= 1.0 * MS
    run = run_over(write(str(tmp_path / "unsound.json.gz"), spans, device))
    assert read("decode_return_lag_ms_p50", run) == pytest.approx(0.3)  # -0.6, .2, .4, .6
    assert run.notes["tick_spans"]["unsound"] == 2


def test_a_parent_style_trace_gives_every_reader_nothing(tmp_path):
    """The parent's spans: no ``readback_wait``, no ``loop_idle``, no
    ``for_step``, ``stalled`` or ``padded_tokens``."""
    spans, device = hand_made()
    spans["readback_wait"], spans["loop_idle"] = [], []
    for kind, drop in (("readback", ("for_step",)),
                       ("prefill", ("stalled", "padded_tokens"))):
        spans[kind] = [{k: v for k, v in s.items() if k not in drop}
                       for s in spans[kind]]
    run = run_over(write(str(tmp_path / "parent.json.gz"), spans, device))
    for name in SERVING_READERS:
        assert read(name, run) is None, name
    assert "tick_spans" not in run.notes
    # and a run with no trace at all
    for name in SERVING_READERS:
        assert read(name, Run(cell={}, kind="serve", seconds=1.0, chips=1,
                              out_dir="")) is None


def test_the_ring_s_reads_are_paired_by_the_tick_they_drain(tmp_path):
    """``async_depth`` 1: tick k dispatches step k and drains step k - 1;
    the read of a step lies in a later tick and names it by ``for_step``."""
    def step(n, start):
        return {"step": n, "active": 2, "start_s": start * MS, "end_s": (start + 1.0) * MS}

    def drain(tick, for_step, start, wait_end, end):
        return ({"step": tick, "for_step": for_step, "start_s": start * MS, "end_s": end * MS},
                {"step": tick, "for_step": for_step, "start_s": (start + 0.01) * MS,
                 "end_s": wait_end * MS})

    reads = [drain(22, 21, 2.4, 3.6, 3.8), drain(23, 22, 5.1, 6.6, 6.9),
             drain(24, 23, 7.0, 9.6, 9.8)]
    spans = {
        "decode_step": [step(21, 0.0), step(22, 1.3), step(23, 4.0)],
        "readback": [r for r, _ in reads],
        "readback_wait": [w for _, w in reads],
    }
    # enqueued 0.5 ms into each dispatch, 3.0 ms a step: the device runs them
    # back to back, 0.5-3.5, 3.5-6.5, 6.5-9.5 ms, and the second and third
    # wait for the step before (the module stamps say when they were enqueued)
    device = [list(events) for events in zip(*(
        device_run(n, "jit_decode_step_fed(1)", at * MS, 3.0)
        for n, at in ((21, 0.5), (22, 1.8), (23, 4.5))))]
    for completion, over in zip(device[2], (3.5, 6.5, 9.5)):
        completion.update(start_s=(over + 0.05) * MS, end_s=(over + 0.08) * MS)
    run = run_over(write(str(tmp_path / "ring.json.gz"), spans, device))
    # 0.5 - 0.0, 3.5 - 1.3, 6.5 - 4.0
    assert read("decode_launch_lag_ms_p50", run) == pytest.approx(2.2)
    assert read("decode_return_lag_ms_p50", run) == pytest.approx(0.1)
    # 3.8 - 3.6, 6.9 - 6.6, 9.8 - 9.6
    assert read("readback_extra_reads_ms_p50", run) == pytest.approx(0.2)
    assert (run.notes["tick_spans"]["paired"], run.notes["tick_spans"]["dropped"],
            run.notes["tick_spans"]["unsound"]) == (3, 0, 0)


def test_off_cpu_time_of_the_dispatch_over_a_hand_made_span_list():
    spans = []
    for step in range(10):
        t = 100.0 + 0.1 * step
        # 40 ms in the dispatch, 4 of them blocked on the device, 6 + 0.1 x
        # step computing: 30 - 0.1 x step ms neither
        spans.append({"kind": "device_block", "step": step, "t": t + 0.03, "ms": 4.0,
                      "parent": "step_dispatch"})
        spans.append({"kind": "step_dispatch", "step": step, "t": t, "ms": 40.0,
                      "parent": None, "cpu_ms": 6.0 + 0.1 * step})
    window = {"t0": 100.2, "t1": 100.8, "steps": 6, "first_step": 2,
              "last_step": 7, "seconds": 0.6}
    run = Run(cell={}, kind="train", seconds=1.0, chips=1, out_dir="",
              spans=spans, window=window)
    # steps 2..7: 29.8 .. 29.3, median 29.55
    assert read(TRAINING_READER, run) == pytest.approx(29.55)
    assert read("host_dispatch_ms_per_step", run) == pytest.approx(36.0)
    # a step that never blocked on the device: the wall less the CPU time
    run.spans = [s for s in spans if s["kind"] == "step_dispatch"]
    assert read(TRAINING_READER, run) == pytest.approx(33.55)
    # the parent's span file has no cpu_ms; an unfilled window reads nothing
    run.spans = [{k: v for k, v in s.items() if k != "cpu_ms"} for s in spans]
    assert read(TRAINING_READER, run) is None
    run.spans, run.window = spans, None
    assert read(TRAINING_READER, run) is None


def test_manifest_lists_the_seven_readers_with_their_cells():
    manifest = load_json(os.path.join(dryrun.REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    serving = [w["name"] for w in manifest["workloads"]
               if w["traffic"].startswith("serve.")]
    assert len(serving) >= 4
    for name, source in SERVING_READERS.items():
        entry = by_name[name]
        assert entry["workloads"][:4] == serving[:4]
        assert (entry["source"], entry["layer"]) == (source, "serving engine")
        assert entry["better"] == "lower"
        assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert entry["moves"] == ("serve_goodput_tokens_per_s"
                                  if name == "prefill_padding_pct"
                                  else "serve_itl_p95_ms")
        assert load_module("metrics", name).META == {"source": source}
    entry = by_name[TRAINING_READER]
    assert entry["workloads"][:2] == ["lm271m.train.b8s2048", "resnet50.train.b128"]
    assert (entry["source"], entry["layer"], entry["unit"], entry["better"]) == (
        "program_span", "runner loop", "ms", "lower")
    assert entry["moves"] == "train_samples_per_s_per_chip"


# ------------------------------------------------------------------ dry

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return dryrun.make_copy(str(tmp_path_factory.mktemp("tick_spans")))


def lines_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    notes = next(json.loads(ln[6:]) for ln in lines if ln.startswith("notes "))
    return json.loads(lines[-1])["metrics"], notes


def test_serving_readers_through_dry(copy):
    metrics, notes = lines_of(
        dryrun.run_cell(copy, "lmtiny.serve.dry", "--trace", "1"))
    for name, source in SERVING_READERS.items():
        if source == "device_trace":
            # a CPU's trace has no XLA Modules line: these two stand on the
            # hand-made trace alone
            assert name not in metrics
            continue
        assert metrics[name]["value"] >= 0.0, name
        assert metrics[name]["unit"] == ("%" if name.endswith("_pct") else "ms")
    assert metrics["prefill_padding_pct"]["value"] < 100.0
    assert notes["tick_spans"]["paired"] > 0
    assert metrics["readback_extra_reads_ms_p50"]["value"] \
        <= metrics["tick_readback_ms_p50"]["value"] + 0.5


@pytest.mark.parametrize("cell", ["lmtiny.train.dry", "resnet50tiny.train.imgdry"])
def test_training_reader_through_dry(copy, cell):
    metrics, _ = lines_of(dryrun.run_cell(copy, cell, "--trace", "1"))
    assert metrics[TRAINING_READER]["unit"] == "ms"
    # what is off the CPU is part of the dispatch (rounding of 0.001 ms a span)
    assert -0.01 <= metrics[TRAINING_READER]["value"] \
        <= metrics["host_dispatch_ms_per_step"]["value"] + 0.01
