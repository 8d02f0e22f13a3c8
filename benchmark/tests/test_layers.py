"""The readers PR 24 added: the span readers over a hand-made span list, the
serving readers over a hand-made snapshot, the kernel and scope readers over
a cut of that PR's own chip trace (one step of lm271m.train.b8s2048 on a TPU
v5 lite), the wire-format reader over a file made here, the operation and
byte counts by hand, and every one of them through ``--dry``."""
import gzip
import json
import os

import pytest

from benchmark import trace, xplane
from benchmark.common import Run, load_json, load_module
from benchmark.kernels import flash, fused_ce
from benchmark.tests import dryrun

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CUT = os.path.join(DATA, "lm_train_step_named.ops.json.gz")
LM = "lm271m.train.b8s2048"


def read(name, run):
    return load_module("metrics", name).read(run)


def make_run(**fields):
    base = dict(cell={}, kind="train", seconds=1.0, chips=1, out_dir="")
    return Run(**{**base, **fields})


# ------------------------------------------------------------------- spans

def test_span_readers_over_a_hand_made_span_list():
    spans = []
    t = 100.0
    for step in range(10):
        spans.append({"kind": "data_wait", "step": step, "t": t, "ms": 50.0,
                      "parent": None})
        spans.append({"kind": "loader_wait", "step": None, "t": t, "ms": 10.0,
                      "parent": "data_wait"})
        spans.append({"kind": "h2d_put", "step": step, "t": t + 0.010,
                      "ms": 38.0, "parent": "data_wait"})
        # the producer, on its own thread and at its own pace
        spans.append({"kind": "batch_assemble", "step": None, "t": t + 0.001,
                      "ms": 80.0 + step, "parent": None, "n": 128})
        t += 0.1
    # steps 2..7 are the window; what lies outside it is not read
    window = {"t0": 100.2, "t1": 100.8, "steps": 6, "first_step": 2,
              "last_step": 7, "seconds": 0.6}
    run = make_run(spans=spans, window=window)
    assert read("loader_wait_ms_per_step", run) == pytest.approx(10.0)
    assert read("h2d_put_ms_per_step", run) == pytest.approx(38.0)
    # six batches assembled in the window: 82..87 ms
    assert read("batch_assemble_ms", run) == pytest.approx(84.5)
    # the two children account for the parent
    both = read("loader_wait_ms_per_step", run) + read("h2d_put_ms_per_step", run)
    assert both == pytest.approx(48.0)
    assert read("data_wait_ms_per_step", run) == pytest.approx(50.0)
    # a program without these spans (the parent commit): nothing, no error
    old = make_run(spans=[s for s in spans if s["kind"] == "data_wait"],
                   window=window)
    for name in ("loader_wait_ms_per_step", "h2d_put_ms_per_step",
                 "batch_assemble_ms"):
        assert read(name, old) is None
        assert read(name, make_run()) is None  # no window at all


# ---------------------------------------------------------------- snapshot

SERVE_READERS = {
    "queue_wait_ms_p95": "queue_wait_ms_p95",
    "engine_ttft_ms_p95": "ttft_ms_p95",
    "engine_itl_ms_p95": "itl_ms_p95",
    "prefill_stall_ms_p95": "prefill_stall_ms_p95",
    "tick_prep_ms_p50": "tick_prep_ms_p50",
    "tick_readback_ms_p50": "tick_readback_ms_p50",
    "tick_deliver_ms_p50": "tick_deliver_ms_p50",
}


def test_serving_readers_over_a_hand_made_snapshot():
    snapshot = {key: 1.0 + i for i, key in enumerate(SERVE_READERS.values())}
    run = make_run(kind="serve", serve={"snapshot": snapshot, "records": []})
    for i, name in enumerate(SERVE_READERS):
        assert read(name, run) == 1.0 + i
    # the parent's snapshot has none of these keys; a training run no snapshot
    older = make_run(kind="serve", serve={"snapshot": {"tick_host_ms_p50": 5.5}})
    for name in SERVE_READERS:
        assert read(name, older) is None
        assert read(name, make_run()) is None


# ------------------------------------------------------------- wire format

def _varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, events, line="XLA Ops", t0_ns=5_000_000_000):
    """``events``: (instruction, op_name or None, offset_ps, duration_ps)."""
    stat_meta = _field(5, _field(1, 7) + _field(2, _field(1, 7) + _field(2, "tf_op")))
    stat_meta += _field(5, _field(1, 8) + _field(2, _field(1, 8) + _field(2, "flops")))
    event_meta, line_events = b"", b""
    for i, (instruction, op_name, offset, dur) in enumerate(events, start=1):
        stats = _field(5, _field(1, 8) + _field(3, 99))
        if op_name is not None:
            stats += _field(5, _field(1, 7) + _field(5, op_name))
        meta = _field(1, i) + _field(2, instruction) + stats
        event_meta += _field(4, _field(1, i) + _field(2, meta))
        line_events += _field(4, _field(1, i) + _field(2, offset) + _field(3, dur))
    xline = _field(1, 1) + _field(2, line) + _field(3, t0_ns) + line_events
    return _field(1, _field(2, name) + _field(3, xline) + event_meta + stat_meta)


def test_wire_reader_takes_names_scopes_and_times_from_the_metadata(tmp_path):
    mosaic = ('%flash_fwd.3 = (bf16[64,2048,128]) custom-call(bf16[64,2048,128] '
              '%bitcast.1), custom_call_target="tpu_custom_call"')
    raw = _plane("/device:TPU:0", [
        (mosaic, "jit(train_step)/jvp(forward)/TransformerLM/block0/attn/"
                 "flash_fwd/pallas_call:", 1_000_000, 2_000_000),
        ("%fusion.7 = f32[8] fusion(f32[8] %p)",
         "jit(train_step)/transpose(jvp(loss_head))/mul:", 4_000_000, 500_000),
        ("%copy.1 = f32[8] copy(f32[8] %p)", None, 5_000_000, 250_000),
    ])
    raw += _plane("/host:CPU", [("ignored", None, 0, 1)], line="python3")
    raw += _plane("/device:TPU:0 ", [("not a device plane", None, 0, 1)])
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(raw)
    ops = xplane.load(str(path))
    assert list(ops) == ["/device:TPU:0"]
    first, second, third = ops["/device:TPU:0"]
    assert first.name == "%flash_fwd.3" and first.mosaic
    assert first.scope.endswith("attn/flash_fwd/pallas_call")
    assert first.start_s == pytest.approx(5.000001) and first.dur_s == 2e-6
    assert (second.name, second.mosaic, third.scope) == ("%fusion.7", False, "")
    assert xplane.seconds(ops, xplane.kernel("flash_")) == pytest.approx(2e-6)
    assert xplane.seconds(ops, xplane.in_scope("loss_head")) == pytest.approx(5e-7)
    assert xplane.seconds(ops, xplane.in_scope("forward")) == pytest.approx(2e-6)
    assert xplane.seconds(ops, xplane.in_scope("optimizer")) == 0.0
    assert xplane.load(str(path)) is ops  # read once a process


@pytest.mark.parametrize("instruction,op_name,prefix,expected", [
    # under a scope the instruction takes the kernel's name
    ("%fused_ce_bwd.1", "jit(s)/transpose(jvp(loss_head))/fused_ce_bwd/pallas_call",
     "fused_ce_", True),
    # bare, the kernel's name is the wrapped outermost component
    ("%transpose_jvp_flash_bwd__.1", "jit(f)/transpose(jvp(flash_bwd))/pallas_call",
     "flash_", True),
    ("%jvp_flash_fwd_.2", "", "flash_", True),  # no op_name kept: the instruction
    ("%flash_fwd.3", "jit(s)/jvp(forward)/attn/flash_fwd/pallas_call", "fused_ce_", False),
    ("%attn.55", "jit(s)/jvp(TransformerLM)/block8/attn/pallas_call", "flash_", False),
])
def test_kernels_are_found_by_their_name(instruction, op_name, prefix, expected):
    op = xplane.Op(instruction, op_name, True, 0.0, 1.0)
    assert xplane.kernel(prefix)(op) is expected
    assert not xplane.kernel(prefix)(op._replace(mosaic=False))


# ------------------------------------------------------ the recorded chip step

@pytest.fixture(scope="module")
def recorded():
    """One execution of ``jit_train_step`` of lm271m.train.b8s2048 on a TPU
    v5 lite (PR 24, my chip run), cut by ``python benchmark/xplane.py``:
    the device's planes as benchmark/trace.py loads them, and the same
    interval's operations with their names."""
    with gzip.open(CUT, "rt") as fp:
        planes = json.load(fp)["planes"]
    planes = {
        plane: {line: [tuple(e) for e in events] for line, events in lines.items()}
        for plane, lines in planes.items()
    }
    cell = {"config_file": load_json(os.path.join(dryrun.BENCH, "configs", "lm271m.json")),
            "traffic_file": load_json(os.path.join(dryrun.BENCH, "traffic", "train.b8s2048.json"))}
    run = make_run(cell=cell, trace=trace.reduce(planes))
    run.notes["xplane"] = CUT
    run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return run


def test_flash_and_fused_ce_are_the_whole_of_the_mosaic_time(recorded):
    assert recorded.trace["programs"]["jit_train_step"]["count"] == 1
    ops = xplane.ops_of(recorded)["/device:TPU:0"]
    kernels = [op for op in ops if op.mosaic]
    assert len(kernels) == 34  # 16 x (forward + fused backward) + CE fwd + bwd
    names = sorted({op.name.split(".")[0] for op in kernels})
    assert names == ["%flash_bwd", "%flash_fwd", "%fused_ce_bwd", "%fused_ce_fwd"]
    flash_ms = read("flash_attention_ms_per_step", recorded)
    ce_ms = read("fused_ce_ms_per_step", recorded)
    mosaic_ms = read("mosaic_ms_per_step", recorded)
    assert flash_ms + ce_ms == pytest.approx(mosaic_ms, rel=1e-6)
    assert 30.0 < flash_ms < 45.0 and 8.0 < ce_ms < 11.0


def test_roofline_shares_of_the_recorded_step(recorded):
    flash_pct = read("flash_roofline_pct", recorded)
    ce_pct = read("fused_ce_roofline_pct", recorded)
    assert 1.0 < flash_pct < 100.0 and 1.0 < ce_pct < 100.0
    flash_ms = read("flash_attention_ms_per_step", recorded)
    assert flash_pct == pytest.approx(100 * 3.850e12 / 197e12 * 1e3 / flash_ms, rel=1e-3)
    ce_ms = read("fused_ce_ms_per_step", recorded)
    assert ce_pct == pytest.approx(100 * 6.4428e9 / 819e9 * 1e3 / ce_ms, rel=1e-3)


def test_scopes_of_the_recorded_step(recorded):
    head_ms = read("loss_head_ms_per_step", recorded)
    opt_ms = read("optimizer_ms_per_step", recorded)
    step_ms = read("device_step_ms", recorded)
    # the head holds the two CE kernels and the logits matmuls beside them
    assert read("fused_ce_ms_per_step", recorded) < head_ms < 0.3 * step_ms
    assert head_ms == pytest.approx(33.59, abs=0.05)
    # XLA fuses each leaf's AdamW update into the fusion that makes its
    # gradient, and the fusion keeps the gradient's op_name: what is left
    # under ``optimizer`` is the unfused remainder (PERF.md, PR 24)
    assert opt_ms == pytest.approx(1.525, abs=0.01)
    ops = xplane.ops_of(recorded)["/device:TPU:0"]
    scoped = sum(op.dur_s for op in ops if op.scope)
    assert scoped > 0.9 * sum(op.dur_s for op in ops)
    # the device readers find nothing where the program has no such names
    renamed = {"p": [op._replace(name="%jvp__.1", scope="jit(train_step)/jvp()")
                     for op in ops]}
    assert xplane.seconds(renamed, xplane.kernel("flash_")) == 0.0
    assert xplane.seconds(renamed, xplane.in_scope("loss_head")) == 0.0
    untraced = make_run(cell=recorded.cell, trace=recorded.trace)
    for name in ("flash_attention_ms_per_step", "fused_ce_ms_per_step",
                 "loss_head_ms_per_step", "optimizer_ms_per_step",
                 "flash_roofline_pct", "fused_ce_roofline_pct"):
        assert read(name, untraced) is None


# ------------------------------------------------------------------ counts

def test_flash_flops_by_hand():
    config = {"model": {"embed_dim": 1024, "depth": 16, "num_heads": 8}}
    traffic = {"batch_size": 8, "seq_len": 2048}
    # pairs on and under the diagonal: 2048 * 2049 / 2 = 2,098,176
    # a head, one layer: 7 matmuls x 2 FLOPs x 128 x 2,098,176 = 3,759,931,392
    # 8 sequences x 8 heads x 16 layers = 1024 of them
    assert flash.causal_flops_per_step(config, traffic) == 1024 * 3_759_931_392
    # a hair over half of what the whole square would cost: the diagonal
    square = 7 * 2 * 128 * 2048 * 2048 * 1024
    assert 0.5 < flash.causal_flops_per_step(config, traffic) / square < 0.5003


def test_fused_ce_bytes_by_hand():
    config, traffic = {"vocab_size": 32768}, {"batch_size": 8, "seq_len": 2048}
    # 16384 x 32768 float32 logits = 2,147,483,648 bytes, three times over,
    # and 24 bytes a row
    assert fused_ce.bytes_per_step(config, traffic) == 3 * 2_147_483_648 + 16384 * 24


# --------------------------------------------------------------------- dry

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return dryrun.make_copy(str(tmp_path_factory.mktemp("layers")))


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_new_training_readers_through_dry(copy):
    metrics = result_of(
        dryrun.run_cell(copy, "lmtiny.train.dry", "--trace", "1"))["metrics"]
    for name in ("loader_wait_ms_per_step", "h2d_put_ms_per_step",
                 "batch_assemble_ms"):
        assert metrics[name]["value"] >= 0.0 and metrics[name]["unit"] == "ms"
    inside = (metrics["loader_wait_ms_per_step"]["value"]
              + metrics["h2d_put_ms_per_step"]["value"])
    assert inside <= 1.5 * metrics["data_wait_ms_per_step"]["value"] + 1.0
    # the CPU's trace has no device plane: the device readers report nothing
    for name in ("flash_attention_ms_per_step", "fused_ce_ms_per_step",
                 "loss_head_ms_per_step", "optimizer_ms_per_step",
                 "flash_roofline_pct", "fused_ce_roofline_pct"):
        assert name not in metrics


def test_new_serving_readers_through_dry(copy):
    metrics = result_of(
        dryrun.run_cell(copy, "lmtiny.serve.dry", "--trace", "1"))["metrics"]
    for name in SERVE_READERS:
        assert metrics[name]["value"] >= 0.0 and metrics[name]["unit"] == "ms"
    assert metrics["engine_ttft_ms_p95"]["value"] >= metrics["queue_wait_ms_p95"]["value"]


def test_manifest_lists_the_readers_by_name_with_their_cells():
    """By name and cell, wherever an entry stands: later PRs add entries
    behind these and cells to their lists."""
    manifest = load_json(os.path.join(dryrun.REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert len(by_name) == len(manifest["per_layer"])
    train = [LM, "resnet50.train.b128"]
    for name in ("loader_wait_ms_per_step", "batch_assemble_ms",
                 "h2d_put_ms_per_step", "optimizer_ms_per_step"):
        assert by_name[name]["workloads"] == train
    for name in ("flash_attention_ms_per_step", "fused_ce_ms_per_step",
                 "loss_head_ms_per_step", "flash_roofline_pct",
                 "fused_ce_roofline_pct"):
        assert by_name[name]["workloads"] == [LM]
    serving = [w["name"] for w in manifest["workloads"]
               if w["traffic"].startswith("serve.")]
    assert serving[0] == "lm271m.serve.steady" and len(serving) >= 4
    for name in SERVE_READERS:
        assert by_name[name]["workloads"] == serving
        assert by_name[name]["source"] == "program_counter"
