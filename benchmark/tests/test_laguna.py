"""The laguna-xs2 configuration's files at a size the CPU holds: its
reference's control flow through ``--dry``, what ``correct`` rests on, the
counts of ``kernels/window_attention.py`` against hand arithmetic, the
readers of its per-layer metrics over a hand-made trace, and the trace of
the traffic its cell runs.

ISSUE 47's mix draws prompts with a Pareto tail of 1.2 and answers with one
of 1.8; ``loadgen.make_trace`` reads ONE ``tail_alpha`` for both, so the cell's
traffic file is of the kind ``serve_two_tails`` (``drivers/serve_two_tails.py``:
the serving driver under the same generator, a tail a length).  The toy cell
below runs under a toy traffic file of that kind and reports what the real
one reports."""
import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark import host_spans, loadgen, xplane
from benchmark.common import Run, load_cell, load_json, load_module
from benchmark.drivers import serve_two_tails
from benchmark.kernels import window_attention
from benchmark.tests import dryrun

CELL = "laguna-xs2.serve.code32"
TOY = "laguna-tiny.serve.dry"
NEW = ("window_attention_ms_per_decode_step", "full_attention_ms_per_decode_step",
       "window_attention_roofline_pct", "full_attention_roofline_pct")
# what the cell reports beside ``setup_s`` and ``setup_compile_s``: the
# two serving end-to-end metrics, the readers of the serving engine and the
# device that every serving cell shares, this family's layers, and NEW
REPORTS = (
    "serve_itl_p95_ms", "serve_goodput_tokens_per_s",
    "tick_host_ms_p50", "prefill_tokens_per_s", "decode_step_device_ms",
    "generator_lag_ms_p95", "device_idle_pct.serve", "ttft_p95_ms", "ttft_p50_ms",
    "queue_wait_ms_p95", "engine_ttft_ms_p95", "engine_itl_ms_p95",
    "prefill_stall_ms_p95", "tick_prep_ms_p50", "tick_readback_ms_p50",
    "tick_deliver_ms_p50", "decode_launch_lag_ms_p50", "decode_return_lag_ms_p50",
    "readback_extra_reads_ms_p50", "tick_gap_ms_p50", "prefill_stalled_gap_pct",
    "prefill_padding_pct", "decode_overlap_pct",
    "gqa_attention_ms_per_decode_step", "moe_ms_per_decode_step",
    "moe_load_max_over_mean", *NEW)
CONFIG = os.path.join(dryrun.BENCH, "configs", "laguna-xs2.json")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with the toy of this configuration listed in its
    manifest: the toy cell reports what the real one reports."""
    root = dryrun.make_copy(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    manifest = load_json(path)
    manifest["configs"].append({
        "name": "laguna-tiny", "source": "test only",
        "file": "benchmark/configs/laguna-tiny.json",
        "reduced": [], "why": "toy sizes for the CPU tests"})
    manifest["workloads"].append({
        "name": TOY, "config": "laguna-tiny", "traffic": "serve.dry2tails",
        "chips": 1, "why": "control flow of the window-and-global cell on the CPU"})
    gqa = next(m for m in manifest["per_layer"]
               if m["name"] == "gqa_attention_ms_per_decode_step")
    manifest["per_layer"] += [
        dict(gqa, name=name, workloads=[], unit="%" if name.endswith("_pct") else "ms")
        for name in NEW]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if metric["name"] in REPORTS:
            metric["workloads"].append(TOY)
    with open(path, "w") as fp:
        json.dump(manifest, fp)
    return root


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_dry_run_of_the_toy_reports_the_cell_s_metrics(copy):
    result = result_of(dryrun.run_cell(copy, TOY))
    assert result["attempted"] == 40 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_itl_p95_ms", "serve_goodput_tokens_per_s", "setup_s"}


def test_counters_and_gauges_through_dry(copy):
    proc = dryrun.run_cell(copy, TOY, "--trace", "1")
    metrics = result_of(proc)["metrics"]
    assert metrics["tick_host_ms_p50"]["value"] > 0.0
    assert metrics["decode_overlap_pct"]["value"] > 0.0
    assert metrics["moe_load_max_over_mean"]["value"] >= 1.0
    assert "moe_experts_hit_per_step" not in metrics  # its reader wants a key this family lacks
    notes = json.loads(next(
        line for line in proc.stdout.splitlines() if line.startswith("notes "))[6:])
    snapshot = notes["snapshot"]
    # 3 window layers x 4 slots x 8 positions x (K and V of 2 heads x 16, float32)
    assert snapshot["window_ring_bytes"] == 3 * 4 * 8 * 2 * 2 * 16 * 4
    assert snapshot["state_cache_bytes"] == snapshot["window_ring_bytes"]
    # 2 full layers: K and V of 2 heads x 16, float32, a position
    assert snapshot["pool_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert snapshot["kv_pool_bytes"] == 32 * 4 * snapshot["pool_bytes_per_token"]
    assert snapshot["pool_aliased_bytes"] == (
        snapshot["state_cache_bytes"] + snapshot["kv_pool_bytes"])
    assert snapshot["state_live_row_share_mean"] > 0.0
    assert snapshot["admitted"] >= 40
    # the CPU's trace has no device plane: the device readers report nothing
    for name in NEW + ("decode_step_device_ms", "gqa_attention_ms_per_decode_step"):
        assert name not in metrics


def test_sound_program_is_correct(copy):
    """float32 toy: the served token is the reference's own first choice at
    every position, through prefill, the pool and rings that wrap (prompts up
    to 16 and 8 answers against a window of 8)."""
    proc = subprocess.run(
        [sys.executable, dryrun.HERE + "/drive.py", copy, TOY, "none"],
        capture_output=True, text=True, timeout=900, cwd=copy,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert "check_correct true" in proc.stdout, proc.stdout[-2000:] + proc.stderr[-3000:]


def test_a_program_without_the_scopes_leaves_the_metrics_out():
    """What a parent commit gives these readers: a snapshot and a trace
    with nothing of the new scopes or span fields in them."""
    run = Run(cell={"config_file": load_json(CONFIG)},
              kind="serve", seconds=1.0, chips=1, out_dir="")
    run.serve = {"snapshot": {"tick_host_ms_p50": 5.0}}
    for name in NEW:
        assert load_module("metrics", name).read(run) is None


def test_window_attention_counts_by_hand():
    config = load_json(CONFIG)
    assert window_attention.window_layers(config) == 12   # three of every four of 1-16
    assert window_attention.full_layers(config) == 5      # layers 0, 4, 8, 12, 16
    # a position of a layer: K and V, 8 heads of 128, bfloat16
    assert window_attention.position_bytes(config) == 2 * 8 * 128 * 2 == 4096
    # 16 rows past their window: 16 x 512 kept positions in 12 layers
    assert window_attention.window_bytes_per_step(config, 16 * 512) == 402653184
    # the same rows at a mean length of 3,000 in the 5 full layers
    assert window_attention.full_bytes_per_step(config, 16 * 3000) == 983040000
    # what a server keeps: a token in the pool, a slot's ring in a window layer
    assert window_attention.full_layers(config) * 4096 == 20480
    assert 512 * 4096 == 2 * 1024 * 1024


def hand_made_run(tmp_path, spans):
    d, p = "jit(decode_step)/jit(main)/LagunaLM/", "jit(prefill)/jit(main)/LagunaLM/"
    ops = [
        ["%fusion.1", d + "layer0/attn/gqa_attention/rotary/mul", False, 0.0000, 0.0001],
        ["%paged_decode.1", d + "layer0/attn/gqa_attention/full_attention/paged_decode/pallas_call", True, 0.0001, 0.0008],
        ["%fusion.2", d + "layer0/attn/gqa_attention/head_gate/dot_general", False, 0.0009, 0.0001],
        ["%fusion.3", d + "layer1/attn/gqa_attention/window_attention/scatter", False, 0.0010, 0.0001],
        ["%paged_decode.2", d + "layer1/attn/gqa_attention/window_attention/paged_decode/pallas_call", True, 0.0011, 0.0005],
        ["%fusion.4", p + "layer1/attn/gqa_attention/window_attention/while/body/dot_general", False, 0.0020, 0.0300],
        ["%paged_decode.3", d + "layer0/attn/gqa_attention/full_attention/paged_decode/pallas_call", True, 0.0400, 0.0012],
        ["%paged_decode.4", d + "layer1/attn/gqa_attention/window_attention/paged_decode/pallas_call", True, 0.0412, 0.0006],
    ]
    path = str(tmp_path / "hand.ops.json.gz")
    with gzip.open(path, "wt") as fp:
        json.dump({"ops": {"/device:TPU:0": ops}, "spans": spans}, fp)
    xplane._LOADED.clear()
    host_spans._by_kind.cache_clear()
    run = Run(cell={"config_file": load_json(CONFIG)}, kind="serve", seconds=1.0,
              chips=1, out_dir="")
    run.notes["xplane"] = path
    run.device = {"kind": "TPU v5 lite"}
    run.trace = {"devices": 1, "programs": {
        "jit_decode_step": {"count": 2, "total_s": 0.004, "median_s": 0.002},
        "jit_prefill": {"count": 1, "total_s": 0.03, "median_s": 0.03}}}
    return run


@pytest.mark.parametrize("name", NEW)
def test_readers_over_a_hand_made_trace(tmp_path, name):
    """Two decode steps around a prefill whose banded scores lie under the
    same scope in ANOTHER program and do not count."""
    run = hand_made_run(tmp_path, {"decode_step": [
        {"active": 10, "window_keys": 10 * 512, "full_keys": 10 * 2000,
         "start_s": 0.0, "end_s": 0.002},
        {"active": 14, "window_keys": 14 * 512, "full_keys": 14 * 3000,
         "start_s": 0.04, "end_s": 0.042}]})
    # 12 kept positions x 512 a traced step in 12 layers, 31,000 in 5 layers
    window_least_ms = 12 * 512 * 12 * 4096 / 819e9 * 1e3
    full_least_ms = 31000 * 5 * 4096 / 819e9 * 1e3
    want = {
        "window_attention_ms_per_decode_step": 0.6,   # scatter + both kernels, over 2
        "full_attention_ms_per_decode_step": 1.0,
        "window_attention_roofline_pct": 100 * window_least_ms / 0.6,
        "full_attention_roofline_pct": 100 * full_least_ms / 1.0,
    }
    got = load_module("metrics", name).read(run)
    assert got == pytest.approx(want[name])
    assert "roofline" not in name or got < 100.0
    # the layer's whole scope, as the shared reader has it: rotary and gate too
    assert load_module("metrics", "gqa_attention_ms_per_decode_step").read(run) == (
        pytest.approx(1.7))


@pytest.mark.parametrize("name", NEW[2:])
def test_a_span_without_the_counts_gives_no_roofline(tmp_path, name):
    """Another family's program has the scope ``full_attention`` and no
    ``full_keys`` in its spans: nothing to divide by, nothing reported."""
    run = hand_made_run(tmp_path, {"decode_step": [
        {"active": 10, "start_s": 0.0, "end_s": 0.002}]})
    assert load_module("metrics", name).read(run) is None


def test_the_manifest_lists_the_configuration_its_cell_and_what_it_reports():
    """The configuration, the one cell that runs it, and the cell's name in
    the list of every metric it reports and of no other; the four metrics of
    this PR move the gap between tokens and sit in the attention layer."""
    manifest = load_json(os.path.join(dryrun.REPO, "BENCHMARK.json"))
    config = next(c for c in manifest["configs"] if c["name"] == "laguna-xs2")
    assert config["file"] == "benchmark/configs/laguna-xs2.json"
    assert config["reduced"] == ["num_hidden_layers", "num_experts"]
    cells = [w for w in manifest["workloads"] if w["config"] == "laguna-xs2"]
    assert [(c["name"], c["traffic"], c["chips"]) for c in cells] == [(CELL, "serve.code32", 1)]
    by_name = {m["name"]: m for m in manifest["end_to_end"] + manifest["per_layer"]}
    listed = {name for name, m in by_name.items() if CELL in m.get("workloads", ())}
    assert listed == set(REPORTS)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_itl_p95_ms"
        assert by_name[name]["layer"] == by_name["gqa_attention_ms_per_decode_step"]["layer"]
        assert os.path.exists(os.path.join(dryrun.BENCH, "metrics", name + ".py"))
    # readers that want a key this family's config does not have, and the
    # other families' layers, are not among what it reports
    assert not {name for name in REPORTS if name.startswith(
        ("moe_experts_hit", "moe_gmm", "kda_", "mamba_", "gdn_", "mla_", "mlp_", "latent_"))}


def test_the_mix_is_long_prompts_and_short_answers_a_tail_each():
    """ISSUE 47's mix as the cell's traffic file gives it: prompts with a
    tail of 1.2 (mean about 2,750, a fifth past 4,096, about 8% clipped at
    8,192, every row past the window of 512), answers with one of 1.8 (mean
    about 128), arrivals inside the traced seconds, and the worst footprint of
    32 rows inside the pool."""
    cell = load_cell(CELL)
    mix, config = cell["traffic_file"], cell["config_file"]
    assert (mix["kind"], mix["prompt_tail_alpha"], mix["gen_tail_alpha"]) == (
        "serve_two_tails", 1.2, 1.8)
    assert (mix["prompt_min"], mix["prompt_max"], mix["gen_min"], mix["gen_max"]) == (
        1024, 8192, 64, 512)
    assert "tail_alpha" not in mix and not mix["prefix_groups"] and not mix["flash_crowds"]
    trace = serve_two_tails.make_trace(mix, 30.0)
    window = [a for a in trace if a.counted]
    assert len(window) == round(mix["rate_rps"] * 30)
    long_run = [a for a in serve_two_tails.make_trace(mix, 1000.0) if a.counted]
    prompts = [a.prompt_len for a in long_run]
    answers = [a.gen_len for a in long_run]
    assert 2600 < sum(prompts) / len(prompts) < 2900
    assert 0.17 < sum(p > 4096 for p in prompts) / len(prompts) < 0.23
    assert 0.06 < sum(p == 8192 for p in prompts) / len(prompts) < 0.10
    assert 120 < sum(answers) / len(answers) < 136 and max(answers) <= 512
    # arrivals due inside the traced seconds: their prefills fall among the
    # decode steps the readers read, and every row is past the window of 512
    traced = [a for a in window if a.due_s >= 30.0 - mix["trace_seconds"]]
    assert len(traced) >= 3 and min(a.prompt_len for a in trace) >= 1024 > 512
    # every request's worst footprint at once is what the pool holds
    scheduler = config["serve"]["serving"]["scheduler"]
    assert scheduler["num_blocks"] * scheduler["block_size"] >= 32 * (8192 + 512)
    assert max(a.prompt_len + a.gen_len for a in trace) <= config["reference_pad_to"] == 8704


def test_two_tails_are_the_generator_that_is_there_drawn_twice():
    """A tail moves no arrival, so each length comes from the accepted
    generator under its own tail; equal tails give that generator's trace;
    inside the driver's block ``loadgen.make_trace`` is the two-tailed one
    (``drivers/serve.py`` and ``sweep.py`` reach it there) and afterwards the
    accepted one again."""
    mix = load_cell(CELL)["traffic_file"]
    both = serve_two_tails.make_trace(mix, 200.0)
    by_prompt = loadgen.make_trace(dict(mix, tail_alpha=1.2), 200.0)
    by_answer = loadgen.make_trace(dict(mix, tail_alpha=1.8), 200.0)
    assert [(a.index, a.due_s, a.prompt_len, a.counted) for a in both] == [
        (a.index, a.due_s, a.prompt_len, a.counted) for a in by_prompt]
    assert [a.gen_len for a in both] == [a.gen_len for a in by_answer]
    assert [a.gen_len for a in both] != [a.gen_len for a in by_prompt]
    equal = dict(mix, prompt_tail_alpha=1.5, gen_tail_alpha=1.5)
    assert serve_two_tails.make_trace(equal, 50.0) == loadgen.make_trace(
        dict(equal, tail_alpha=1.5), 50.0)
    accepted = loadgen.make_trace
    with serve_two_tails.generator():
        assert loadgen.make_trace(mix, 50.0) == serve_two_tails.make_trace(mix, 50.0)
    assert loadgen.make_trace is accepted


def test_published_keys_stand_at_their_published_values():
    """Every key of the catalog row's ``config`` but the two that are
    reduced, at the file's top level and in what is run."""
    period = ["full_attention"] + ["sliding_attention"] * 3
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 262144,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": period * 10, "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
    }
    config = load_json(CONFIG)
    for key, value in published.items():
        assert config[key] == value, key
        if key != "vocab_size":
            assert config["serve"]["model"][key] == value, key
    assert config["num_hidden_layers"] == config["serve"]["model"]["num_hidden_layers"] == 17
    assert config["num_experts"] == 32 and config["serve"]["model"]["num_experts"] == 256
    assert config["serve"]["model"]["experts_held"] == [0, 32]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["num_experts"] == 256
    assert config["reduced"] == ["num_hidden_layers", "num_experts"]
    assert config["serve"]["dataset"]["n_classes"] == 100352
    serving = config["serve"]["serving"]
    assert all(bucket % 512 == 0 for bucket in serving["seq_buckets"])
    assert serving["seq_buckets"][0] == 1024 and serving["seq_buckets"][-1] == 8192
    assert serving["scheduler"]["slots"] == 32 and serving["scheduler"]["block_size"] == 16
    assert serving["scheduler"]["prefix_cache"] is False and serving["temperature"] == 0.0
    assert config["control_mode"] == "int8" and config["reference_pad_to"] == 8704
    for item in ("block", "qk_norm", "window", "gating", "rotary_pairing",
                 "attention_factor", "activation", "router_scoring", "shared_expert"):
        assert item in config["assumed"]
    assert "8 chips" in config["deployment"] and "eighth" in config["deployment"]
    assert "12.0 GB" in config["bytes_on_the_chip"]
