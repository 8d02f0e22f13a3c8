"""The deepseek-v2-lite configuration's files at a size the CPU holds: its
reference's control flow through ``--dry``, what ``correct`` rests on, the
counts of ``kernels/moe_gmm.py`` against hand arithmetic, and the readers of
its per-layer metrics over a hand-made trace."""
import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark.common import Run, load_json, load_module
from benchmark.kernels import moe_gmm
from benchmark.tests import dryrun

CELL = "deepseek-v2-lite.serve.steady32"
TOY = "deepseek-v2-lite-tiny.serve.dry"
NEW = ("moe_ms_per_decode_step", "mla_attention_ms_per_decode_step",
       "moe_experts_hit_per_step", "moe_load_max_over_mean", "moe_gmm_roofline_pct")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with the toy of this configuration listed in its
    manifest: the toy cell reports what the real one reports."""
    root = dryrun.make_copy(str(tmp_path_factory.mktemp("bench")))
    path = os.path.join(root, "BENCHMARK.json")
    manifest = load_json(path)
    manifest["configs"].append({
        "name": "deepseek-v2-lite-tiny", "source": "test only",
        "file": "benchmark/configs/deepseek-v2-lite-tiny.json",
        "reduced": [], "why": "toy sizes for the CPU tests"})
    manifest["workloads"].append({
        "name": TOY, "config": "deepseek-v2-lite-tiny", "traffic": "serve.dry",
        "chips": 1, "why": "control flow of the expert-layer serving cell on the CPU"})
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


def test_counters_of_the_expert_layer_through_dry(copy):
    metrics = result_of(dryrun.run_cell(copy, TOY, "--trace", "1"))["metrics"]
    # 4 slots x 3 of 8 experts a step: between 3 and 8 experts hit a layer
    assert 3.0 <= metrics["moe_experts_hit_per_step"]["value"] <= 8.0
    assert metrics["moe_load_max_over_mean"]["value"] >= 1.0
    assert metrics["tick_host_ms_p50"]["value"] > 0.0
    # the CPU's trace has no device plane: the device readers report nothing
    for name in ("moe_ms_per_decode_step", "mla_attention_ms_per_decode_step",
                 "moe_gmm_roofline_pct", "decode_step_device_ms"):
        assert name not in metrics


def test_sound_program_is_correct_and_the_control_is_not(copy):
    """float32 toy: the served token is the reference's own first choice;
    the bfloat16 control puts another token first often enough to miss."""
    proc = subprocess.run(
        [sys.executable, dryrun.HERE + "/drive.py", copy, TOY, "none"],
        capture_output=True, text=True, timeout=900, cwd=copy,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert "check_correct true" in proc.stdout, proc.stdout[-2000:] + proc.stderr[-3000:]


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """What the parent commit gives these readers: a snapshot and a trace
    with nothing of the expert layer in them."""
    run = Run(cell={"config_file": load_json(os.path.join(
        dryrun.BENCH, "configs", "deepseek-v2-lite.json"))},
        kind="serve", seconds=1.0, chips=1, out_dir="")
    run.serve = {"snapshot": {"tick_host_ms_p50": 5.0}}
    for name in NEW:
        assert load_module("metrics", name).read(run) is None


def test_moe_gmm_counts_by_hand():
    config = {"hidden_size": 2048, "moe_intermediate_size": 1408, "dtype": "bfloat16",
              "num_hidden_layers": 7, "first_k_dense_replace": 1}
    assert moe_gmm.expert_layers(config) == 6
    # an expert: 3 x 2048 x 1408 weights = 8,650,752; bf16 -> 17,301,504 bytes
    assert moe_gmm.bytes_per_step(config, 1, 0) == 17301504
    # 61 of 64 experts hit in each of 6 layers, 32 rows x 6 picks a layer:
    # weights 366 x 17,301,504 = 6,332,350,464 bytes; rows 192 x 6 layers x
    # (2048 + 2816 + 1408 + 2048) values x 2 bytes = 19,169,280
    assert moe_gmm.bytes_per_step(config, 6 * 61, 192) == 6332350464 + 19169280
    # a pair: 2 x 8,650,752 operations; 192 pairs x 6 layers
    assert moe_gmm.flops_per_step(config, 192) == 192 * 6 * 2 * 8650752


def test_readers_take_the_decode_program_s_scopes_only(tmp_path):
    """A hand-made trace: two decode steps and a prefill; the prefill's
    expert layer is not counted, and the roofline share reads the counters."""
    d, p = "jit(decode_step)/jit(main)/DeepseekV2LM/", "jit(prefill)/jit(main)/DeepseekV2LM/"
    ops = [
        ["%fusion.1", d + "layer1/mla_attention/attn/dot_general", False, 0.0, 0.001],
        ["%gmm.1", d + "layer1/moe/moe/moe_gmm/pallas_call", True, 0.001, 0.004],
        ["%fusion.2", d + "layer1/moe/moe/moe_shared/dot_general", False, 0.005, 0.001],
        ["%gmm.2", p + "layer1/moe/moe/moe_gmm/pallas_call", True, 0.006, 0.050],
        ["%gmm.3", d + "layer1/moe/moe/moe_gmm/pallas_call", True, 0.060, 0.004],
        ["%fusion.3", d + "loss_head/dot_general", False, 0.064, 0.002],
    ]
    path = str(tmp_path / "hand.ops.json.gz")
    with gzip.open(path, "wt") as fp:
        json.dump({"ops": {"/device:TPU:0": ops}}, fp)
    config = load_json(os.path.join(dryrun.BENCH, "configs", "deepseek-v2-lite.json"))
    run = Run(cell={"config_file": config}, kind="serve", seconds=1.0, chips=1, out_dir="")
    run.notes["xplane"] = path
    run.device = {"kind": "TPU v5 lite"}
    run.trace = {"devices": 1, "programs": {
        "jit_decode_step": {"count": 2, "total_s": 0.012, "median_s": 0.006},
        "jit_prefill": {"count": 1, "total_s": 0.05, "median_s": 0.05}}}
    run.serve = {"snapshot": {"moe_experts_hit_mean": 366.0, "slot_occupancy_mean": 1.0,
                              "moe_load_max_over_mean_p50": 2.5}}
    read = lambda name: load_module("metrics", name).read(run)  # noqa: E731
    assert read("moe_ms_per_decode_step") == pytest.approx(4.5)
    assert read("mla_attention_ms_per_decode_step") == pytest.approx(0.5)
    assert read("moe_experts_hit_per_step") == pytest.approx(61.0)
    assert read("moe_load_max_over_mean") == 2.5
    least_ms = (6332350464 + 19169280) / 819e9 * 1e3
    assert read("moe_gmm_roofline_pct") == pytest.approx(100 * least_ms / 4.0)
    # counted from fewer experts, the same time is a smaller share: the bytes
    # follow the counter, never the layer's 64
    run.serve["snapshot"]["moe_experts_hit_mean"] = 183.0
    assert read("moe_gmm_roofline_pct") < 0.51 * 100 * least_ms / 4.0


def test_manifest_entries_of_the_configuration():
    manifest = load_json(os.path.join(dryrun.REPO, "BENCHMARK.json"))
    # by name and cell, wherever an entry stands: later configurations add
    # theirs behind these, and their cells to the readers they share
    assert "deepseek-v2-lite" in [c["name"] for c in manifest["configs"]]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2-lite", "serve.steady32", 1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "serve_itl_p95_ms"
    for name in ("mla_attention_ms_per_decode_step", "moe_gmm_roofline_pct"):
        assert by_name[name]["workloads"] == [CELL]
    reported = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"setup_s", "serve_itl_p95_ms", "serve_goodput_tokens_per_s",
            "decode_step_device_ms", "device_idle_pct.serve"} <= reported
    traffic = load_json(os.path.join(dryrun.BENCH, "traffic", "serve.steady32.json"))
    assert isinstance(traffic["rate_rps"], float) and traffic["drain_s"] == 60.0
    config = load_json(os.path.join(dryrun.BENCH, "configs", "deepseek-v2-lite.json"))
    assert config["reduced"] == ["num_hidden_layers"] and config["num_hidden_layers"] == 7
    assert config["serve"]["model"]["n_routed_experts"] == 64
