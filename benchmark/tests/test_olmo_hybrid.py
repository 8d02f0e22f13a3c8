"""The olmo-hybrid-7b configuration's files at a size the CPU holds: its
reference's control flow through ``--dry``, what ``correct`` rests on, the
counts of ``kernels/gated_delta.py`` against hand arithmetic, and the readers
of its per-layer metrics over a hand-made trace."""
import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark import host_spans, loadgen, xplane
from benchmark.common import Run, load_json, load_module
from benchmark.kernels import gated_delta
from benchmark.tests import dryrun

CELL = "olmo-hybrid-7b.serve.reason32"
TOY = "olmo-hybrid-tiny.serve.dry"
NEW = ("gdn_ms_per_decode_step", "gdn_step_roofline_pct",
       "gdn_scan_ms_per_prefill_ktoken", "gdn_scan_roofline_pct",
       "mlp_ms_per_decode_step")
CONFIG = os.path.join(dryrun.BENCH, "configs", "olmo-hybrid-7b.json")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with the toy of this configuration listed in its
    manifest: the toy cell reports what the real one reports."""
    root = dryrun.make_copy(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    manifest = load_json(path)
    manifest["configs"].append({
        "name": "olmo-hybrid-tiny", "source": "test only",
        "file": "benchmark/configs/olmo-hybrid-tiny.json",
        "reduced": [], "why": "toy sizes for the CPU tests"})
    manifest["workloads"].append({
        "name": TOY, "config": "olmo-hybrid-tiny", "traffic": "serve.dry",
        "chips": 1, "why": "control flow of the dense hybrid cell on the CPU"})
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
    assert metrics["decode_overlap_pct"]["value"] > 0.0
    assert "moe_experts_hit_per_step" not in metrics  # a dense model
    notes = json.loads(next(
        line for line in proc.stdout.splitlines() if line.startswith("notes "))[6:])
    snapshot = notes["snapshot"]
    # 3 linear layers x 4 slots x (3 x 12 x 20 float32 + 3 x 132 float32 rows)
    assert snapshot["state_cache_bytes"] == 3 * 4 * (3 * 12 * 20 * 4 + 3 * 132 * 4)
    # one full layer: K and V of 32 blocks x 4 rows x 3 heads x 20, float32
    assert snapshot["kv_pool_bytes"] == 2 * 32 * 4 * 3 * 20 * 4
    assert snapshot["pool_aliased_bytes"] == (
        snapshot["state_cache_bytes"] + snapshot["kv_pool_bytes"])
    assert snapshot["state_live_row_share_mean"] > 0.0
    assert snapshot["admitted"] >= 40
    # the CPU's trace has no device plane: the device readers report nothing
    for name in NEW + ("decode_step_device_ms", "gqa_attention_ms_per_decode_step"):
        assert name not in metrics


def test_sound_program_is_correct(copy):
    """float32 toy: the served token is the reference's own first choice at
    every position, through prefill, the pool and the state."""
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


def test_gated_delta_counts_by_hand():
    config = load_json(CONFIG)
    assert gated_delta.gdn_layers(config) == 12     # three of every four of 16
    # a state: 30 heads x 96 x 192 float32 = 2,211,840 bytes; read + written
    assert gated_delta.state_bytes_per_step(config, 1) == 12 * 2 * 2211840
    assert gated_delta.state_bytes_per_step(config, 32) == 1698693120
    # a token a head: 6 x 96 x 192 operations; 30 heads, 12 layers
    assert gated_delta.scan_flops_per_token(config) == 12 * 30 * 6 * 96 * 192 == 39813120
    # rows of q, k (96 each), v, output (192 each) and ONE decay: bf16 values
    assert gated_delta.scan_bytes_per_token(config) == 12 * 30 * 577 * 2 == 415440


@pytest.mark.parametrize("name", NEW)
def test_readers_over_a_hand_made_trace(tmp_path, name):
    """Two decode steps, one whole prefill of 1 x 1,024 and one cut by the
    trace's edge (no span: neither its time nor its tokens count)."""
    d, p = "jit(decode_step)/jit(main)/OlmoHybridLM/", "jit(prefill)/jit(main)/OlmoHybridLM/"
    ops = [
        ["%fusion.1", d + "layer3/attn/gqa_attention/paged_attention/paged_decode/pallas_call", True, 0.000, 0.003],
        ["%fusion.2", d + "layer0/gdn/gdn/gdn_conv/dot_general", False, 0.003, 0.001],
        ["%fusion.3", d + "layer0/gdn/gdn/gdn_step/while/body/dot_general", False, 0.004, 0.002],
        ["%fusion.4", d + "layer0/mlp/mlp/dot_general", False, 0.006, 0.004],
        ["%fusion.5", p + "layer0/gdn/gdn/gdn_scan/while/body/dot_general", False, 0.010, 0.030],
        ["%fusion.6", p + "layer0/gdn/gdn/gdn_conv/dot_general", False, 0.040, 0.010],
        ["%fusion.7", p + "layer0/mlp/mlp/dot_general", False, 0.050, 0.004],
        ["%fusion.8", d + "layer0/gdn/gdn/gdn_step/while/body/dot_general", False, 0.060, 0.002],
        ["%fusion.9", p + "layer0/gdn/gdn/gdn_scan/while/body/dot_general", False, 0.090, 0.008],
    ]
    path = str(tmp_path / "hand.ops.json.gz")
    with gzip.open(path, "wt") as fp:
        json.dump({"ops": {"/device:TPU:0": ops}, "spans": {
            "prefill": [{"rows": 1, "bucket": 1024, "tokens": 700,
                         "start_s": 0.009, "end_s": 0.056}],
            "decode_step": [{"active": 20, "start_s": 0.0, "end_s": 0.007},
                            {"active": 28, "start_s": 0.059, "end_s": 0.063}],
        }}, fp)
    xplane._LOADED.clear()
    host_spans._by_kind.cache_clear()
    run = Run(cell={"config_file": load_json(CONFIG)}, kind="serve", seconds=1.0,
              chips=1, out_dir="")
    run.notes["xplane"] = path
    run.device = {"kind": "TPU v5 lite"}
    run.trace = {"devices": 1, "programs": {
        "jit_decode_step": {"count": 2, "total_s": 0.012, "median_s": 0.006},
        "jit_prefill": {"count": 2, "total_s": 0.05, "median_s": 0.025}}}
    # the whole run's occupancy is NOT what the roofline share reads
    run.serve = {"snapshot": {"slot_occupancy_mean": 0.1}}
    # 24 rows live a traced step: 1,274,019,840 bytes at 819 GB/s, 2 ms a step
    step_least_ms = 24 * 12 * 2 * 2211840 / 819e9 * 1e3
    # the whole prefill alone: 30 ms under gdn_scan for 1,024 bucket tokens
    scan_least_s = 1024 * max(39813120 / 197e12, 415440 / 819e9)
    want = {
        "gdn_ms_per_decode_step": 2.5,            # conv + both steps, over 2
        "mlp_ms_per_decode_step": 2.0,            # the decode program's alone
        "gdn_step_roofline_pct": 100 * step_least_ms / 2.0,
        "gdn_scan_ms_per_prefill_ktoken": 30.0 / 1.024,
        "gdn_scan_roofline_pct": 100 * scan_least_s / 0.030,
    }
    got = load_module("metrics", name).read(run)
    assert got == pytest.approx(want[name])
    assert "roofline" not in name or got < 100.0
    assert load_module("metrics", "gqa_attention_ms_per_decode_step").read(run) == (
        pytest.approx(1.5))


def test_manifest_entries_of_the_configuration():
    manifest = load_json(os.path.join(dryrun.REPO, "BENCHMARK.json"))
    entry = next(c for c in manifest["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == load_json(CONFIG)["source"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b", "serve.reason32", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["moves"] == "serve_itl_p95_ms"
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
    reported = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"setup_s", "serve_itl_p95_ms", "serve_goodput_tokens_per_s",
            "decode_step_device_ms", "device_idle_pct.serve", "tick_host_ms_p50",
            "gqa_attention_ms_per_decode_step", "decode_overlap_pct"} <= reported
    assert not {name for name in reported
                if name.startswith(("moe_", "kda_", "mamba_", "mla_", "latent_"))}
    # every serving cell before it reports what it reported
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert metric["workloads"][-1] == CELL


def test_the_traffic_is_the_issue_s_and_its_traced_seconds_hold_prefills():
    traffic = load_json(os.path.join(dryrun.BENCH, "traffic", "serve.reason32.json"))
    assert (traffic["prompt_min"], traffic["prompt_max"]) == (128, 1024)
    assert (traffic["gen_min"], traffic["gen_max"]) == (512, 2048)
    assert traffic["tail_alpha"] == 1.8 and traffic["mix_seed"] == 0
    assert traffic["prefix_groups"] == 0 and traffic["flash_crowds"] == 0
    assert traffic["lead_in_s"] == 20.0 and traffic["drain_s"] == 60.0
    assert traffic["sample_requests"] == 6 and traffic["trace_seconds"] == 4.0
    assert traffic["serving"] == {"max_new_tokens": 2048, "temperature": 0.0, "eos_id": None}
    assert isinstance(traffic["rate_rps"], float)
    trace = loadgen.make_trace(traffic, 30.0)
    window = [a for a in trace if a.counted]
    assert len(window) == round(traffic["rate_rps"] * 30)
    # arrivals due inside the traced last 8 s: their prefills are what the
    # gdn_scan readers read
    assert sum(a.due_s >= 30.0 - traffic["trace_seconds"] for a in window) >= 5
    # the pool binds: every request's footprint at once is more than it holds,
    # the mean footprint of 32 is less than half of it
    config = load_json(CONFIG)
    blocks = config["serve"]["serving"]["scheduler"]["num_blocks"]
    footprints = [-(-(a.prompt_len + a.gen_len) // 16) for a in trace]
    assert 32 * max(footprints) > blocks > 32 * 2 * sum(footprints) / len(footprints) * 0.9
    assert max(a.prompt_len + a.gen_len for a in trace) <= config["reference_pad_to"]


def test_published_keys_stand_at_their_published_values():
    """Every key of the catalog row's ``config`` but ``num_hidden_layers``,
    at the file's top level and in what is run."""
    period = ["linear_attention"] * 3 + ["full_attention"]
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "layer_types": period * 8,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }
    config = load_json(CONFIG)
    for key, value in published.items():
        assert config[key] == value, key
        if key != "vocab_size":
            assert config["serve"]["model"][key] == value, key
    assert config["num_hidden_layers"] == config["serve"]["model"]["num_hidden_layers"] == 16
    assert config["published"]["num_hidden_layers"] == 32
    assert config["serve"]["dataset"]["n_classes"] == 100352
    serving = config["serve"]["serving"]
    assert serving["batch_buckets"] == [1, 8] and serving["seq_buckets"] == [256, 1024]
    assert serving["scheduler"]["slots"] == 32 and serving["scheduler"]["block_size"] == 16
    assert serving["scheduler"]["prefix_cache"] is False and serving["temperature"] == 0.0
    assert config["control_mode"] == "int8" and config["reference_pad_to"] == 3072
    for item in ("block", "qk_norm", "rotary", "gdn_gate", "gdn_decay"):
        assert item in config["assumed"]
    assert "two pipeline stages" in config["deployment"]
