"""The yardstick's arithmetic: trace reduction against a trace recorded on
the chip, the span window, the load generator's percentiles and due times,
and the operation counts against hand-worked values."""
import gzip
import json
import math
import os

import pytest

from benchmark import loadgen, spans, trace
from benchmark.common import load_json, worst_leaf_gap
from benchmark.kernels import lm_step, peaks, resnet50_step
from benchmark.tests import dryrun

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# ------------------------------------------------------------------- trace

@pytest.fixture(scope="module")
def recorded():
    """One step of lm271m.train.b8s2048 on a TPU v5 lite (PR 23, my chip
    run), cut from the profiler's trace with ``trace.cut`` around the
    second traced execution of ``jit_train_step``."""
    with gzip.open(os.path.join(DATA, "lm_train_step.trace.json.gz")) as fp:
        planes = json.load(fp)
    return {
        plane: {line: [tuple(e) for e in events] for line, events in lines.items()}
        for plane, lines in planes.items()
    }


def test_recorded_trace_busy_idle_and_programs(recorded):
    reduced = trace.reduce(recorded)
    assert reduced["devices"] == 1
    # the device ran 260.75 ms of a 260.82 ms window: idle share 0.025%
    assert reduced["busy_s"] == pytest.approx(0.260751, abs=1e-5)
    assert reduced["window_s"] == pytest.approx(0.260817, abs=1e-5)
    assert reduced["busy_s"] <= reduced["window_s"]
    assert trace.main_program(reduced) == "jit_train_step"
    assert reduced["programs"]["jit_train_step"]["total_s"] == pytest.approx(
        0.260799, abs=1e-5)
    assert reduced["collective_s"] == 0.0


def test_recorded_trace_mosaic_sum(recorded):
    ops = recorded["/device:TPU:0"][trace.OPS_LINE]
    mosaic = [e for e in ops if trace.MOSAIC.match(e[0])]
    # 16 x (flash forward + fused backward) + CE forward + CE backward
    assert len(mosaic) == 34
    reduced = trace.reduce(recorded)
    assert reduced["mosaic_s"] == pytest.approx(sum(e[2] for e in mosaic))
    assert reduced["mosaic_s"] == pytest.approx(0.048333, abs=1e-5)
    names = [name for name, _ in reduced["device_ops"]]
    assert all(len(name) < 80 for name in names)  # not the whole instruction


def test_idle_gaps_are_named_by_the_shortest_covering_host_frame():
    planes = {
        "/device:TPU:0": {
            trace.OPS_LINE: [("%a", 0.0, 1.0), ("%b", 3.0, 1.0)],
            trace.MODULES_LINE: [("jit_step(1)", 0.0, 1.0), ("jit_step(1)", 3.0, 1.0)],
        },
        trace.HOST_PLANE: {"python3": [
            ("loop", 0.0, 4.0), ("next_batch", 0.9, 2.0), ("tiny", 1.0, 0.1),
        ]},
    }
    reduced = trace.reduce(planes)
    assert reduced["window_s"] == 4.0 and reduced["busy_s"] == 2.0
    assert reduced["idle_gaps"] == [["next_batch", 2.0]]
    assert reduced["programs"]["jit_step"]["count"] == 2


def test_gap_naming_survives_a_flooded_host_plane():
    """The runtime logs every piece of a batch it transposes for the device:
    300,000 short events on a worker's line.  Naming 40 gaps against them by
    a python loop a gap took minutes on the chip and ran a checked run past
    its time limit; one numpy pass a gap takes well under a second."""
    import time

    ops = [("%op", 2.0 * i, 1.0) for i in range(41)]
    flood = [("Transpose", 1e-4 * i, 5e-5) for i in range(300_000)]
    planes = {
        "/device:TPU:0": {trace.OPS_LINE: ops},
        trace.HOST_PLANE: {
            "python3": [("runner.loop_outside_train_iter", 2.0 * i + 0.9, 1.2)
                        for i in range(40)],
            "worker": flood,
        },
    }
    start = time.monotonic()
    reduced = trace.reduce(planes)
    assert time.monotonic() - start < 20.0
    assert reduced["idle_gaps"] == [
        ["runner.loop_outside_train_iter", pytest.approx(40.0)]
    ]


def test_op_names_are_tagged():
    assert trace.op_name(
        '%jvp__.1 = (f32[8]) custom-call(f32[8] %custom-call.3), '
        'custom_call_target="tpu_custom_call"') == "mosaic:%jvp__.1"
    assert trace.op_name(
        "%fusion.2 = f32[8] fusion(f32[8] %custom-call.92)") == "%fusion.2"
    assert trace.op_name(
        "%all-gather.3 = f32[8] all-gather(f32[2] %p)") == "collective:%all-gather.3"


def test_exposed_collective_time():
    planes = {"/device:TPU:0": {trace.OPS_LINE: [
        ("%mm", 0.0, 2.0), ("collective:%all-gather.1", 1.0, 2.0),
    ]}}
    reduced = trace.reduce(planes)
    assert reduced["collective_s"] == 2.0
    assert reduced["collective_exposed_s"] == pytest.approx(1.0)


# ------------------------------------------------------------------- spans

def synthetic_spans(step_ms=100.0, steps=40, sync_every=5):
    out, t = [], 10.0
    for step in range(steps):
        out.append({"kind": "data_wait", "step": step, "t": t, "ms": 2.0})
        t += 0.002
        synced = step % sync_every == 0
        ms = step_ms if synced else 4.0
        out.append({"kind": "step_dispatch", "step": step, "t": t, "ms": ms})
        if synced:
            out.append({"kind": "device_block", "step": step, "t": t + 0.003,
                        "ms": ms - 3.0})
        t += ms / 1e3
    return out


def test_window_runs_between_synced_steps():
    table = synthetic_spans()
    window = spans.find_window(table, warmup_steps=7, seconds=1.0)
    # opens at the end of step 10 (first synced step >= 7); a synced step
    # ends every 0.126 s, so the first 1.0 s or more later is step 50...
    assert window is None  # ...which this run of 40 steps never reaches
    window = spans.find_window(table, warmup_steps=7, seconds=0.3)
    assert (window["first_step"], window["last_step"]) == (11, 25)
    assert window["steps"] == 15
    assert window["seconds"] == pytest.approx(3 * (0.1 + 4 * 0.004 + 5 * 0.002))
    waits = spans.per_step_ms(table, window, "data_wait")
    assert len(waits) == 15 and spans.median_ms(waits) == 2.0
    host = spans.per_step_ms(table, window, "step_dispatch", "device_block")
    assert sorted(set(host)) == [3.0, 4.0]


# ----------------------------------------------------------------- loadgen

MIX = {"rate_rps": 10.0, "lead_in_s": 1.0, "prompt_min": 4, "prompt_max": 64,
       "gen_min": 2, "gen_max": 16, "tail_alpha": 1.8, "mix_seed": 3}


def test_arrivals_are_fixed_by_the_traffic_file_and_prompts_by_the_seed():
    a = loadgen.make_trace(MIX, 5.0)
    assert a == loadgen.make_trace(MIX, 5.0)
    assert a != loadgen.make_trace(dict(MIX, mix_seed=4), 5.0)
    counted = [x for x in a if x.counted]
    assert len(counted) == 50 and len(a) == 60
    assert counted[-1].due_s == pytest.approx(5.0)
    assert all(x.due_s <= 0 for x in a if not x.counted)
    assert all(4 <= x.prompt_len <= 64 and 2 <= x.gen_len <= 16 for x in a)
    p1, p2 = (loadgen.make_prompts(a, MIX, 512, seed) for seed in (1, 2))
    assert [len(p) for p in p1] == [x.prompt_len for x in a]
    assert any((x != y).any() for x, y in zip(p1, p2))
    again = loadgen.make_prompts(a, MIX, 512, 1)
    assert all((x == y).all() for x, y in zip(p1, again))


def test_percentile_and_due_time_arithmetic_on_a_scripted_stream():
    def served(index, due, submitted, times, gen):
        arrival = loadgen.Arrival(index, due, 8, gen, None, True)
        rec = loadgen.Served(arrival, due=due, submitted=submitted,
                             token_times=times, tokens=list(range(gen)))
        rec.finished = times[-1] if times else None
        return rec

    records = [
        # due at 1.0, sent 5 ms late, first token at 1.2: TTFT counts from DUE
        served(0, 1.0, 1.005, [1.2, 1.3, 1.5], 3),
        served(1, 2.0, 2.0, [2.1, 2.15], 2),
        served(2, 3.0, 3.0, [3.4], 3),   # one token of three: failed
    ]
    assert loadgen.failed(records[2]) and not loadgen.failed(records[0])
    ttft = loadgen.ttft_ms(records)
    assert ttft[:2] == pytest.approx([200.0, 100.0]) and math.isinf(ttft[2])
    assert loadgen.percentile(ttft, 50) == pytest.approx(200.0)
    assert math.isinf(loadgen.percentile(ttft, 95))  # a failure misses any limit
    assert loadgen.gaps_ms(records) == pytest.approx([100.0, 200.0, 50.0])
    assert loadgen.lag_ms(records) == pytest.approx([5.0, 0.0, 0.0])
    # window [1, 3]: five tokens were stamped inside it, in 2 s
    assert loadgen.tokens_per_s(records, 1.0, 2.0) == pytest.approx(2.5)
    assert loadgen.tokens_per_s(records, 1.0, 0.25) == pytest.approx(4.0)
    assert loadgen.percentile(list(range(1, 101)), 95) == 95
    assert loadgen.percentile([], 95) is None


def scripted(index, due, times, gen, counted=True):
    arrival = loadgen.Arrival(index, due, 8, gen, None, counted)
    return loadgen.Served(arrival, due=due, submitted=due, token_times=times,
                          tokens=list(range(len(times))))


GOODPUT_CASES = {
    # window [10, 12]; the old count reads every stamp inside it
    "a_lead_in_request_counts_nothing":
        ([scripted(0, 9.0, [10.1, 10.2, 10.3], 3, counted=False)], 0, 3),
    "a_request_straddling_the_close_counts_its_inside_stamps":
        ([scripted(0, 11.0, [11.5, 12.0, 12.5, 13.0], 4)], 2, 2),
    "a_request_that_came_back_short_counts_nothing":
        ([scripted(0, 10.5, [10.6, 10.7], 3)], 0, 2),
    "a_stamp_before_the_window_opens_is_outside":
        ([scripted(0, 10.0, [9.99, 10.0, 11.0], 3)], 2, 2),
    "all_together": ([
        scripted(0, 9.0, [9.5, 10.1, 10.2], 3, counted=False),
        scripted(1, 10.0, [10.4, 10.8, 11.2], 3),
        scripted(2, 11.0, [11.5, 12.0, 12.5, 13.0], 4),
        scripted(3, 11.2, [11.3], 2),
    ], 5, 8),
}


@pytest.mark.parametrize("case", sorted(GOODPUT_CASES))
def test_goodput_counts_the_window_s_own_requests(case):
    records, goodput_tokens, stamped_tokens = GOODPUT_CASES[case]
    assert loadgen.goodput_tokens_per_s(records, 10.0, 2.0) == pytest.approx(
        goodput_tokens / 2.0)
    assert loadgen.tokens_per_s(records, 10.0, 2.0) == pytest.approx(
        stamped_tokens / 2.0)


def test_a_refused_request_counts_nothing_in_the_goodput():
    rec = scripted(0, 10.5, [10.6, 10.7], 2)
    assert loadgen.goodput_tokens_per_s([rec], 10.0, 2.0) == 1.0
    rec.error = "RuntimeError('shed')"
    assert loadgen.goodput_tokens_per_s([rec], 10.0, 2.0) == 0.0
    assert loadgen.tokens_per_s([rec], 10.0, 2.0) == 1.0


# The replay of PERF.md section 6 (PR 36): each cell's arrivals served by an
# engine of the cell's slots at a fixed gap a token, slowest first.  The
# numbers are the replay's own (no chip): tokens/s as (goodput, every stamp).
REPLAY = {
    "serve.steady32": ("deepseek-v2-lite", {
        37.5: (439.1, 540.9), 31.7: (450.2, 539.0), 21.0: (466.3, 523.5),
        10.0: (478.8, 515.4)}),
    "serve.steady": ("lm271m", {
        12.5: (463.5, 469.5), 9.6: (464.1, 468.1), 6.7: (464.9, 467.3),
        4.0: (465.6, 467.5)}),
    "serve.long32": ("solar-open2-250b", {
        25.0: (315.8, 360.4), 19.3: (322.8, 363.5), 9.0: (333.5, 359.3)}),
    "serve.burst32": ("nemotron-3-super-120b", {
        30.0: (531.8, 600.2), 25.5: (539.9, 599.9), 15.0: (568.4, 603.0)}),
}


@pytest.mark.parametrize("traffic_name", sorted(REPLAY))
def test_replay_goodput_rises_as_the_token_gap_shortens(traffic_name):
    config_name, expected = REPLAY[traffic_name]
    traffic = load_json(os.path.join(dryrun.BENCH, "traffic", traffic_name + ".json"))
    config = load_json(os.path.join(dryrun.BENCH, "configs", config_name + ".json"))
    slots = config["serve"]["serving"]["scheduler"]["slots"]
    trace = loadgen.make_trace(traffic, 30.0)
    offered = sum(a.gen_len for a in trace if a.counted) / 30.0
    goodput, stamped = [], []
    for gap_ms in sorted(expected, reverse=True):
        records = loadgen.replay(trace, slots, gap_ms / 1e3)
        assert not any(loadgen.failed(r) for r in records)
        goodput.append(loadgen.goodput_tokens_per_s(records, 0.0, 30.0))
        stamped.append(loadgen.tokens_per_s(records, 0.0, 30.0))
        assert goodput[-1] == pytest.approx(expected[gap_ms][0], rel=0.01)
        assert stamped[-1] == pytest.approx(expected[gap_ms][1], rel=0.01)
    # a faster engine leaves less of the window's work for the drain: the
    # goodput rises with every step and stays under what was offered ...
    assert goodput == sorted(goodput) and len(set(goodput)) == len(goodput)
    assert goodput[-1] < offered
    # ... where the count of every stamp can read ABOVE the offered rate
    # (the lead-in's backlog) and in no cell rises with every step
    assert stamped != sorted(stamped)
    if traffic_name == "serve.steady32":
        # what refused PR 27: 31.7 -> 21.0 ms a token read 2.9% FEWER stamps
        assert stamped == sorted(stamped, reverse=True)
        assert stamped[0] > offered > goodput[-1]
        assert stamped[2] < 0.98 * stamped[1] and goodput[2] > 1.03 * goodput[1]


# ----------------------------------------------------------------- kernels

def test_lm_flops_by_hand():
    model = {"embed_dim": 1024, "depth": 16, "num_heads": 8, "max_len": 2048}
    # a block: qkv 3E^2 + proj E^2 + fc1 4E^2 + fc2 4E^2 = 12 E^2 = 12,582,912
    # 16 blocks = 201,326,592; the head E x V = 33,554,432
    assert lm_step.matmul_params(model, 32768) == 234_881_024
    # 6 N + 12 L S E = 1,409,286,144 + 402,653,184
    assert lm_step.flops_per_token(model, 32768, 2048) == 1_811_939_328
    config = {"model": model, "vocab_size": 32768}
    assert lm_step.flops_per_sample(config, {"seq_len": 2048}) == 2048 * 1_811_939_328


def test_resnet50_flops_by_hand():
    # torchvision's ResNet-50 is quoted at 4.09 G multiply-adds an image
    # (4,089,184,256 with the classifier, counting convolutions only)
    assert resnet50_step.forward_macs(224, 1000) == 4_089_184_256
    # the stem by hand: 112 x 112 outputs x 64 channels x (3 x 49) taps
    assert 112 * 112 * 64 * 147 == 118_013_952
    config = {"n_classes": 1000}
    assert resnet50_step.flops_per_sample(config, {"image_size": 224}) == (
        6 * 4_089_184_256)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("_source")


# ---------------------------------------------------------------- compare

def test_worst_leaf_gap_is_a_gap_of_norms_floored_by_the_median_leaf():
    reference = {"a": 10.0, "b": 1.0, "c": 1e-9}
    program = {"a": 10.5, "b": 1.0, "c": 5e-9}
    gap, leaf = worst_leaf_gap(program, reference)
    # c's own norm is all but zero: it is measured against the median leaf
    assert leaf == "a" and gap == pytest.approx(0.05)
    gap, leaf = worst_leaf_gap({"a": 10.0, "b": float("nan"), "c": 0.0}, reference)
    assert leaf == "b" and math.isnan(gap)
    with pytest.raises(ValueError):
        worst_leaf_gap({"a": 1.0}, reference)
