"""The harness is driven by data, refuses to measure without the chip, and
prints the contract's one line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.tests import dryrun


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy to which a configuration, two cells and a per-layer metric
    were added AS FILES; only the manifest lists them."""
    return dryrun.make_copy(str(tmp_path_factory.mktemp("bench")))


def result_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_added_configuration_cell_and_metric_run_without_an_edit(copy):
    # nothing but the manifest differs from the repository's files
    for sub, _, files in os.walk(os.path.join(dryrun.BENCH)):
        for name in files:
            if "__pycache__" in sub:
                continue
            mine = os.path.join(sub, name)
            theirs = os.path.join(copy, os.path.relpath(mine, dryrun.REPO))
            with open(mine, "rb") as a, open(theirs, "rb") as b:
                assert a.read() == b.read(), mine
    result = result_of(dryrun.run_cell(copy, "lmtiny.train.dry", "--trace", "1"))
    assert result["correct"] is False  # a dry run never claims correctness
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["steps_in_window.test"]["value"] > 0
    assert "data_wait_ms_per_step" in result["metrics"]
    # device metrics have nothing to read on the CPU and are left out
    assert "device_step_ms" not in result["metrics"]
    assert "mfu_pct" not in result["metrics"]


def test_result_line_of_a_serving_cell(copy):
    result = result_of(dryrun.run_cell(copy, "lmtiny.serve.dry"))
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["attempted"] == 40 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "serve_itl_p95_ms", "serve_goodput_tokens_per_s", "setup_s",
    }


def test_refuses_to_measure_without_a_tpu(copy):
    proc = dryrun.run_cell(copy, "lm271m.train.b8s2048", dry=False)
    assert proc.returncode != 0
    assert "refused" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_fails_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's own
    files there is no system under test: no result, not exit 0."""
    shutil.copytree(dryrun.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(dryrun.REPO, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload",
         "lm271m.train.b8s2048", "--seed", "1", "--seconds", "1", "--dry"],
        capture_output=True, text=True, cwd=tmp_path, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_manifest_names_only_files_that_exist():
    with open(os.path.join(dryrun.REPO, "BENCHMARK.json")) as fp:
        manifest = json.load(fp)
    for config in manifest["configs"]:
        assert os.path.isfile(os.path.join(dryrun.REPO, config["file"]))
    for cell in manifest["workloads"]:
        assert os.path.isfile(
            os.path.join(dryrun.BENCH, "traffic", cell["traffic"] + ".json"))
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert os.path.isfile(
            os.path.join(dryrun.BENCH, "metrics", metric["name"] + ".py"))
    layers = {m["layer"] for m in manifest["per_layer"]}
    with open(os.path.join(dryrun.REPO, "PERF.md")) as fp:
        perf = fp.read()
    assert all(layer in perf for layer in layers)


def test_manifest_keeps_to_the_contract():
    import re

    with open(os.path.join(dryrun.REPO, "BENCHMARK.json")) as fp:
        raw = fp.read()
    manifest = json.loads(raw)
    assert len(raw) <= 64 * 1024
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

    def line(text):
        return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text

    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
    cells = {w["name"] for w in manifest["workloads"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4) and (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        # each cell that reports the metric reports the metric it moves
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:  # every cell: setup_s, one more end-to-end, one per-layer
        mine = [m for m in manifest["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in manifest["per_layer"])
    assert 1 <= manifest["run_seconds"] <= 51
