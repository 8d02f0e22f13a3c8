"""The nemotron-3-super-120b configuration's files at a size the CPU holds:
its reference's control flow through ``--dry``, what ``correct`` rests on,
the counts of ``kernels/mamba2.py`` and ``kernels/latent_moe_gmm.py`` against
hand arithmetic, the readers of its per-layer metrics over a hand-made
trace, and the manifest's new entries."""
import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark.common import Run, load_json, load_module
from benchmark import host_spans, loadgen, xplane
from benchmark.kernels import latent_moe_gmm, mamba2, moe_gmm
from benchmark.tests import dryrun

CELL = "nemotron-3-super-120b.serve.burst32"
TOY = "nemotron-h-tiny.serve.dry"
NEW = ("mamba_ms_per_decode_step", "mamba_step_roofline_pct",
       "mamba_scan_ms_per_prefill_ktoken", "mamba_scan_roofline_pct",
       "latent_moe_gmm_roofline_pct")
CONFIG = os.path.join(dryrun.BENCH, "configs", "nemotron-3-super-120b.json")
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with the toy of this configuration listed in its
    manifest: the toy cell reports what the real one reports."""
    root = dryrun.make_copy(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    manifest = load_json(path)
    manifest["configs"].append({
        "name": "nemotron-h-tiny", "source": "test only",
        "file": "benchmark/configs/nemotron-h-tiny.json",
        "reduced": [], "why": "toy sizes for the CPU tests"})
    manifest["workloads"].append({
        "name": TOY, "config": "nemotron-h-tiny", "traffic": "serve.dry",
        "chips": 1, "why": "control flow of the state-space / latent-expert cell on the CPU"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
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
    assert metrics["moe_load_max_over_mean"]["value"] > 0.0
    notes = json.loads(next(
        line for line in proc.stdout.splitlines() if line.startswith("notes "))[6:])
    snapshot = notes["snapshot"]
    # 4 slots x 6 of 16 experts a step, 4 held: at most 4 hit a layer, 5 layers
    assert 0.0 < snapshot["moe_experts_hit_mean"] <= 20.0
    # 5 Mamba layers x 4 slots x (16 x 8 x 16 float32 + 3 x 256 float32 rows)
    assert snapshot["state_cache_bytes"] == 5 * 4 * (16 * 8 * 16 * 4 + 3 * 256 * 4)
    # one attention layer: K and V of 32 blocks x 4 rows x 2 heads x 16, float32
    assert snapshot["kv_pool_bytes"] == 2 * 32 * 4 * 2 * 16 * 4
    assert snapshot["pool_aliased_bytes"] == (
        snapshot["state_cache_bytes"] + snapshot["kv_pool_bytes"])
    # the CPU's trace has no device plane: the device readers report nothing
    for name in NEW + ("decode_step_device_ms",):
        assert name not in metrics


def test_sound_program_is_correct(copy):
    """float32 toy: the served token is the reference's own first choice at
    every position, through batched prefills, the pool and the state."""
    proc = subprocess.run(
        [sys.executable, dryrun.HERE + "/drive.py", copy, TOY, "none"],
        capture_output=True, text=True, timeout=900, cwd=copy,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert "check_correct true" in proc.stdout, proc.stdout[-2000:] + proc.stderr[-3000:]


def test_a_program_without_the_scopes_leaves_the_metrics_out():
    """What a parent commit gives these readers: a snapshot and a trace
    with nothing of the new layers in them."""
    run = Run(cell={"config_file": load_json(CONFIG)},
              kind="serve", seconds=1.0, chips=1, out_dir="")
    run.serve = {"snapshot": {"tick_host_ms_p50": 5.0}}
    for name in NEW:
        assert load_module("metrics", name).read(run) is None


def test_mamba_counts_by_hand():
    config = load_json(CONFIG)
    assert mamba2.mamba_layers(config) == 5      # MEMEMEM*EME
    # a state: 128 heads x 64 x 128 float32 = 4,194,304 bytes; the
    # convolution rows 3 x 10,240 bf16 = 61,440; read + written
    assert mamba2.state_bytes_per_step(config, 1) == 5 * 2 * (4194304 + 61440)
    assert mamba2.state_bytes_per_step(config, 32) == 1361838080
    # a token a head: 5 x 64 x 128 operations; 128 heads, 5 layers
    assert mamba2.scan_flops_per_token(config) == 5 * 128 * 5 * 64 * 128 == 26214400
    # a head's x, y (64 each) and dt; a group's B and C (128 each), 8 groups; bf16
    assert mamba2.scan_bytes_per_token(config) == 5 * (128 * 129 + 8 * 256) * 2 == 185600


def test_latent_expert_counts_by_hand():
    config = load_json(CONFIG)
    assert latent_moe_gmm.expert_layers(config) == 5
    # an expert hit: two matrices of 1024 x 2688 bf16 = 11,010,048 bytes; a
    # pair's rows: 1024 in, 2688 out and in again, 1024 out
    assert latent_moe_gmm.bytes_per_step(config, 1, 0) == 2 * 1024 * 2688 * 2 == 11010048
    assert latent_moe_gmm.bytes_per_step(config, 0, 1) == (2 * 1024 + 2 * 2688) * 2
    assert latent_moe_gmm.flops_per_step(config, 1) == 2 * 1024 * 2688 * 2
    # what the accepted reader would count for the same expert: three
    # matrices of hidden_size x width, six times as much, hence several
    # hundred percent: the cell does not list it
    gated = moe_gmm.bytes_per_step(dict(config, first_k_dense_replace=0), 1, 0)
    assert gated == 6 * 11010048


def test_readers_over_a_hand_made_trace(tmp_path):
    """Two decode steps, one whole prefill of 8 x 256 (three arrivals of a
    burst in one call) and one cut by the trace's edge (no span: neither its
    time nor its tokens count)."""
    d, p = "jit(decode_step)/jit(main)/NemotronHLM/", "jit(prefill)/jit(main)/NemotronHLM/"
    ops = [
        ["%fusion.1", d + "layer7/attn/gqa_attention/dot_general", False, 0.000, 0.001],
        ["%fusion.2", d + "layer0/mamba/mamba/mamba_in/dot_general", False, 0.001, 0.002],
        ["%fusion.3", d + "layer0/mamba/mamba/mamba_step/reduce", False, 0.003, 0.004],
        ["%gmm.1", d + "layer1/moe/moe/moe_gmm/jit(gmm)/pallas_call", True, 0.007, 0.006],
        ["%fusion.4", d + "layer1/moe/moe/moe_latent_up/dot_general", False, 0.013, 0.001],
        ["%fusion.5", p + "layer0/mamba/mamba/mamba_scan/dot_general", False, 0.020, 0.010],
        ["%fusion.6", p + "layer0/mamba/mamba/mamba_conv/fusion", False, 0.030, 0.005],
        ["%fusion.7", d + "layer0/mamba/mamba/mamba_step/reduce", False, 0.060, 0.004],
        ["%gmm.2", d + "layer1/moe/moe/moe_gmm/jit(gmm)/pallas_call", True, 0.064, 0.006],
        ["%fusion.8", p + "layer0/mamba/mamba/mamba_scan/dot_general", False, 0.090, 0.008],
    ]
    path = str(tmp_path / "hand.ops.json.gz")
    # the program's spans of the traced seconds: 12 and 20 rows live in the
    # two decode steps, 150 and 250 held experts hit over the 5 layers
    with gzip.open(path, "wt") as fp:
        json.dump({"ops": {"/device:TPU:0": ops}, "spans": {
            "prefill": [{"rows": 3, "bucket": 256, "tokens": 500,
                         "start_s": 0.019, "end_s": 0.040}],
            "decode_step": [{"active": 12, "start_s": 0.0, "end_s": 0.015},
                            {"active": 20, "start_s": 0.059, "end_s": 0.071}],
            "moe_counts": [{"hit": 150, "rows": 12, "start_s": 0.016, "end_s": 0.016},
                           {"hit": 250, "rows": 20, "start_s": 0.072, "end_s": 0.072}],
        }}, fp)
    xplane._LOADED.clear()
    host_spans._by_kind.cache_clear()
    run = Run(cell={"config_file": load_json(CONFIG)}, kind="serve", seconds=1.0,
              chips=1, out_dir="")
    run.notes["xplane"] = path
    run.device = {"kind": "TPU v5 lite"}
    run.trace = {"devices": 1, "programs": {
        "jit_decode_step": {"count": 2, "total_s": 0.03, "median_s": 0.015},
        "jit_prefill": {"count": 2, "total_s": 0.03, "median_s": 0.015}}}
    # the whole run's means are NOT what the roofline shares read
    run.serve = {"snapshot": {"slot_occupancy_mean": 0.9, "moe_experts_hit_mean": 999.0}}
    read = lambda name: load_module("metrics", name).read(run)  # noqa: E731
    assert read("gqa_attention_ms_per_decode_step") == pytest.approx(0.5)
    assert read("moe_ms_per_decode_step") == pytest.approx(6.5)  # gmm + latent_up
    assert read("mamba_ms_per_decode_step") == pytest.approx(5.0)  # in + both steps
    # 16 rows live a traced step: 680,919,040 bytes at 819 GB/s over 4 ms a step
    least_ms = 16 * 5 * 2 * (4194304 + 61440) / 819e9 * 1e3
    assert read("mamba_step_roofline_pct") == pytest.approx(100 * least_ms / 4.0)
    # 200 held experts hit a traced step, 2 x 1024 x 2688 bf16 weights each,
    # and at least one pair each (2 x 1024 + 2 x 2688 values); 6 ms a step
    least_ms = (200 * 2 * 1024 * 2688 + 200 * (2 * 1024 + 2 * 2688)) * 2 / 819e9 * 1e3
    assert read("latent_moe_gmm_roofline_pct") == pytest.approx(100 * least_ms / 6.0)
    assert 0.0 < read("latent_moe_gmm_roofline_pct") < 100.0
    # the whole prefill alone: 3 rows take the batch bucket of 8, 8 x 256
    # bucket tokens, 10 ms under mamba_scan
    assert read("mamba_scan_ms_per_prefill_ktoken") == pytest.approx(10.0 / 2.048)
    least_s = 2048 * max(26214400 / 197e12, 185600 / 819e9)
    assert read("mamba_scan_roofline_pct") == pytest.approx(100 * least_s / 0.010)
    assert 0.0 < read("mamba_scan_roofline_pct") < 100.0


def test_manifest_entries_of_the_configuration():
    manifest = load_json(os.path.join(dryrun.REPO, "BENCHMARK.json"))
    config_entry = next(c for c in manifest["configs"] if c["name"] == "nemotron-3-super-120b")
    assert config_entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config_entry["source"] == load_json(CONFIG)["source"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve.burst32"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_itl_p95_ms"
        assert by_name[name]["source"] == "device_trace"
    assert by_name["latent_moe_gmm_roofline_pct"]["layer"] == by_name["moe_gmm_roofline_pct"]["layer"]
    assert {by_name[n]["layer"] for n in NEW[:4]} == {"state-space layer (Mamba-2)"}
    reported = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"setup_s", "serve_itl_p95_ms", "serve_goodput_tokens_per_s", "moe_ms_per_decode_step",
            "decode_step_device_ms", "device_idle_pct.serve", "tick_host_ms_p50",
            "gqa_attention_ms_per_decode_step", "moe_load_max_over_mean"} <= reported
    # their counts are of another expert (three matrices of the full width)
    # or another layer: several hundred percent, or nothing to read; and
    # moe_experts_hit_per_step divides by num_hidden_layers less
    # first_k_dense_replace, a key this family has not (PERF.md section 7)
    assert not {"moe_gmm_roofline_pct", "moe_gmm_held_roofline_pct", "kda_ms_per_decode_step",
                "kda_step_roofline_pct", "mla_attention_ms_per_decode_step",
                "moe_experts_hit_per_step"} & reported


def test_the_traffic_is_the_issue_s_and_its_bursts_fall_where_the_readme_says():
    traffic = load_json(os.path.join(dryrun.BENCH, "traffic", "serve.burst32.json"))
    assert isinstance(traffic["rate_rps"], float) and traffic["drain_s"] == 60.0
    assert traffic["prefix_groups"] == 0 and traffic["mix_seed"] == 0
    assert (traffic["prompt_min"], traffic["prompt_max"]) == (64, 1024)
    assert (traffic["gen_min"], traffic["gen_max"], traffic["tail_alpha"]) == (64, 512, 1.8)
    assert (traffic["lead_in_s"], traffic["sample_requests"], traffic["trace_seconds"]) == (
        20.0, 6, 8.0)
    assert traffic["flash_crowds"] == 2 and traffic["flash_duration_s"] == 3.0
    assert traffic["flash_multiplier"] in (2.0, 3.0)
    trace = loadgen.make_trace(traffic, 30.0)
    due = [a.due_s for a in trace if a.counted]
    assert len(due) == round(traffic["rate_rps"] * 30)
    # the densest 3 s of the window hold well over the mean's share of arrivals
    densest = max(sum(t <= d < t + 3.0 for d in due) for t in due)
    assert densest > 1.5 * len(due) * 3.0 / 30.0
    # the traced seconds (the last 8) hold prefills for the scan's readers
    assert sum(d >= 22.0 for d in due) >= 5


def test_published_keys_stand_at_their_published_values():
    """Every key of the catalog row's ``config`` but the three in
    ``reduced``, at the file's top level and in what is run."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
        "head_dim": 128, "hidden_size": 4096, "hybrid_override_pattern": PATTERN,
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 22, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True,
    }
    config = load_json(CONFIG)
    for key, value in published.items():
        assert config[key] == value, key
        assert config["serve"]["model"][key] == value, key
    assert len(PATTERN) == 88 and PATTERN[:11] == "MEMEMEM*EME"
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (40, 40, 8)
    assert config["num_hidden_layers"] == 11 and config["n_routed_experts"] == 128
    assert config["vocab_size"] == 32768
    assert config["published"] == dict(
        config["published"], num_hidden_layers=88, n_routed_experts=512, vocab_size=131072)
    model, serving = config["serve"]["model"], config["serve"]["serving"]
    assert model["n_routed_experts"] == 512 and model["experts_held"] == [0, 128]
    assert model["num_hidden_layers"] == 11 and "vocab_size" not in model
    assert serving["scheduler"]["prefix_cache"] is False
    # the cell as ISSUE 34 fixed it before any code
    assert serving["batch_buckets"] == [1, 8, 32] and serving["seq_buckets"] == [256, 1024]
    assert serving["scheduler"]["slots"] == 32 and serving["scheduler"]["block_size"] == 16
    assert serving["scheduler"]["num_blocks"] * 16 >= 32 * (1024 + 512)
    assert config["reference_pad_to"] == 1024 + 512
    for key in ("position_term", "mamba_state", "dt_limit", "router", "correction_bias",
                "weights"):
        assert config["assumed"][key]
    assert "multi-token-prediction" in config["not_built"]
    assert "8 pipeline stages" in config["deployment"] and "4 chips" in config["deployment"]
    assert config["parameters"] == 4648163712
