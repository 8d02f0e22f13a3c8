"""The solar-open2-250b configuration's files at a size the CPU holds: its
reference's control flow through ``--dry``, what ``correct`` rests on, the
counts of ``kernels/kda.py`` against hand arithmetic, and the readers of its
per-layer metrics over a hand-made trace."""
import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark.common import Run, load_json, load_module
from benchmark import host_spans, xplane
from benchmark.kernels import kda
from benchmark.tests import dryrun

CELL = "solar-open2-250b.serve.long32"
TOY = "solar-open2-tiny.serve.dry"
NEW = ("kda_ms_per_decode_step", "gqa_attention_ms_per_decode_step",
       "kda_step_roofline_pct", "kda_scan_ms_per_prefill_ktoken",
       "kda_scan_roofline_pct", "moe_gmm_held_roofline_pct")
CONFIG = os.path.join(dryrun.BENCH, "configs", "solar-open2-250b.json")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with the toy of this configuration listed in its
    manifest: the toy cell reports what the real one reports."""
    root = dryrun.make_copy(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    manifest = load_json(path)
    manifest["configs"].append({
        "name": "solar-open2-tiny", "source": "test only",
        "file": "benchmark/configs/solar-open2-tiny.json",
        "reduced": [], "why": "toy sizes for the CPU tests"})
    manifest["workloads"].append({
        "name": TOY, "config": "solar-open2-tiny", "traffic": "serve.dry",
        "chips": 1, "why": "control flow of the hybrid linear-attention cell on the CPU"})
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
    # 4 slots x 4 of 16 experts a step, 8 held: at most 8 hit a layer
    assert 0.0 < metrics["moe_experts_hit_per_step"]["value"] <= 8.0
    assert metrics["tick_host_ms_p50"]["value"] > 0.0
    notes = json.loads(next(
        line for line in proc.stdout.splitlines() if line.startswith("notes "))[6:])
    snapshot = notes["snapshot"]
    # 3 KDA layers x 4 slots x (4 x 16 x 16 float32 + 3 x 192 float32 rows)
    assert snapshot["state_cache_bytes"] == 3 * 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    # one GQA layer: K and V of 32 blocks x 4 rows x 2 heads x 16, float32
    assert snapshot["kv_pool_bytes"] == 2 * 32 * 4 * 2 * 16 * 4
    assert snapshot["pool_aliased_bytes"] == (
        snapshot["state_cache_bytes"] + snapshot["kv_pool_bytes"])
    # the CPU's trace has no device plane: the device readers report nothing
    for name in NEW + ("decode_step_device_ms",):
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


def test_kda_counts_by_hand():
    config = load_json(CONFIG)
    assert kda.kda_layers(config) == 3          # layers 1-3 of the four
    # a state: 64 heads x 128 x 128 float32 = 4,194,304 bytes; read + written
    assert kda.state_bytes_per_step(config, 1) == 3 * 2 * 4194304
    assert kda.state_bytes_per_step(config, 32) == 805306368
    # a token a head: 6 x 128 x 128 operations; 64 heads, 3 layers
    assert kda.scan_flops_per_token(config) == 3 * 64 * 6 * 128 * 128 == 18874368
    # rows of q, k, decay, v, output: 5 x 128 bf16 values a head
    assert kda.scan_bytes_per_token(config) == 3 * 64 * 5 * 128 * 2 == 245760


def test_readers_over_a_hand_made_trace(tmp_path):
    """Two decode steps, one whole prefill of 1 x 1,024 and one cut by the
    trace's edge (no span: neither its time nor its tokens count)."""
    d, p = "jit(decode_step)/jit(main)/SolarOpen2LM/", "jit(prefill)/jit(main)/SolarOpen2LM/"
    ops = [
        ["%fusion.1", d + "layer0/attn/gqa_attention/gather", False, 0.000, 0.003],
        ["%fusion.2", d + "layer1/kda/kda/kda_conv/dot_general", False, 0.003, 0.001],
        ["%fusion.3", d + "layer1/kda/kda/kda_step/dot_general", False, 0.004, 0.002],
        ["%gmm.1", d + "layer1/moe/moe/moe_gmm/pallas_call", True, 0.006, 0.001],
        ["%fusion.4", p + "layer1/kda/kda/kda_scan/while/body/dot_general", False, 0.010, 0.030],
        ["%fusion.5", p + "layer1/kda/kda/kda_conv/dot_general", False, 0.040, 0.010],
        ["%fusion.6", d + "layer1/kda/kda/kda_step/dot_general", False, 0.060, 0.002],
        ["%fusion.7", p + "layer1/kda/kda/kda_scan/while/body/dot_general", False, 0.090, 0.008],
    ]
    path = str(tmp_path / "hand.ops.json.gz")

    def write(prefill_rows):
        # the program's spans of the traced seconds: 12 and 20 rows live in
        # the two decode steps, 9 and 11 held experts hit over the 4 layers
        with gzip.open(path, "wt") as fp:
            json.dump({"ops": {"/device:TPU:0": ops}, "spans": {
                "prefill": [{"rows": prefill_rows, "bucket": 1024, "tokens": 700,
                             "start_s": 0.009, "end_s": 0.055}],
                "decode_step": [{"active": 12, "start_s": 0.0, "end_s": 0.007},
                                {"active": 20, "start_s": 0.059, "end_s": 0.063}],
                "moe_counts": [{"hit": 9, "rows": 12, "start_s": 0.008, "end_s": 0.008},
                               {"hit": 11, "rows": 20, "start_s": 0.064, "end_s": 0.064}],
            }}, fp)
        xplane._LOADED.clear()
        host_spans._by_kind.cache_clear()

    write(1)
    run = Run(cell={"config_file": load_json(CONFIG)}, kind="serve", seconds=1.0,
              chips=1, out_dir="")
    run.notes["xplane"] = path
    run.device = {"kind": "TPU v5 lite"}
    run.trace = {"devices": 1, "programs": {
        "jit_decode_step": {"count": 2, "total_s": 0.012, "median_s": 0.006},
        "jit_prefill": {"count": 2, "total_s": 0.05, "median_s": 0.025}}}
    # the whole run's means are NOT what the roofline shares read
    run.serve = {"snapshot": {"slot_occupancy_mean": 0.9, "moe_experts_hit_mean": 99.0}}
    read = lambda name: load_module("metrics", name).read(run)  # noqa: E731
    assert read("gqa_attention_ms_per_decode_step") == pytest.approx(1.5)
    assert read("kda_ms_per_decode_step") == pytest.approx(2.5)   # conv + both steps
    # 16 rows live a traced step: 402,653,184 bytes at 819 GB/s over 2 ms a step
    least_ms = 402653184 / 819e9 * 1e3
    assert read("kda_step_roofline_pct") == pytest.approx(100 * least_ms / 2.0)
    # 10 held experts hit a traced step, 3 x 4096 x 1280 bf16 weights each, and
    # at least one pair each (2 x 4096 + 3 x 1280 values a pair); 1 ms under
    # moe_gmm over the two steps
    least_ms = (10 * 3 * 4096 * 1280 + 10 * (2 * 4096 + 3 * 1280)) * 2 / 819e9 * 1e3
    assert read("moe_gmm_held_roofline_pct") == pytest.approx(100 * least_ms / 0.5)
    # the whole prefill alone: 30 ms under kda_scan for 1,024 bucket tokens
    assert read("kda_scan_ms_per_prefill_ktoken") == pytest.approx(30.0 / 1.024)
    least_s = 1024 * max(18874368 / 197e12, 245760 / 819e9)
    assert read("kda_scan_roofline_pct") == pytest.approx(100 * least_s / 0.030)
    assert read("kda_scan_roofline_pct") < 100.0
    # under a configuration with wider batch buckets, three rows in one call
    # take the bucket of 4: 4 x 1,024 tokens
    write(3)
    run.cell["config_file"]["serve"]["serving"]["batch_buckets"] = [1, 4, 32]
    assert read("kda_scan_ms_per_prefill_ktoken") == pytest.approx(30.0 / 4.096)


def test_manifest_entries_of_the_configuration():
    manifest = load_json(os.path.join(dryrun.REPO, "BENCHMARK.json"))
    # by name and cell, wherever an entry stands: later configurations add
    # theirs behind these, and their cells to the readers they share
    entry = next(c for c in manifest["configs"] if c["name"] == "solar-open2-250b")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b", "serve.long32", 1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["moves"] == "serve_itl_p95_ms"
        shared = name == "gqa_attention_ms_per_decode_step"  # since PR 34
        assert by_name[name]["workloads"][0] == CELL
        assert shared or by_name[name]["workloads"] == [CELL]
    reported = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"setup_s", "serve_itl_p95_ms", "serve_goodput_tokens_per_s", "moe_ms_per_decode_step",
            "decode_step_device_ms", "device_idle_pct.serve"} <= reported
    # its reader takes its counts from the whole run and read 101-115% here;
    # the cell reports the share that counts the traced seconds' held experts
    assert "moe_gmm_roofline_pct" not in reported
    assert "moe_gmm_held_roofline_pct" in reported
    traffic = load_json(os.path.join(dryrun.BENCH, "traffic", "serve.long32.json"))
    assert isinstance(traffic["rate_rps"], float) and traffic["drain_s"] == 60.0
    assert traffic["prefix_groups"] == 0 and traffic["mix_seed"] == 0
    config = load_json(CONFIG)
    assert config["num_hidden_layers"] == 4 and config["n_routed_experts"] == 40
    assert config["vocab_size"] == 24576 and config["published"]["n_routed_experts"] == 320
    model, serving = config["serve"]["model"], config["serve"]["serving"]
    assert model["n_routed_experts"] == 320 and model["experts_held"] == [0, 40]
    assert serving["scheduler"]["prefix_cache"] is False
    # the cell as ISSUE 32 fixed it before any code
    assert serving["batch_buckets"] == [1, 8, 32]
    assert serving["seq_buckets"] == [1024, 4096] and serving["scheduler"]["slots"] == 32
    assert serving["scheduler"]["num_blocks"] * 16 == 32 * (4096 + 512)


def test_published_keys_stand_at_their_published_values():
    """Every key of the catalog row's ``config`` but the three in
    ``reduced``, at the file's top level and in what is run."""
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8,
    }
    config = load_json(CONFIG)
    for key, value in published.items():
        assert config[key] == value, key
        assert config["serve"]["model"][key] == value, key
