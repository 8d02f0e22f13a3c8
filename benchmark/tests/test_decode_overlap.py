"""``decode_overlap_pct`` (PR 39): the reader over hand-made snapshots, and
its entry in the manifest."""
import os

import pytest

from benchmark.common import ROOT, Run, load_json, load_module

SERVING = ["lm271m.serve.steady", "deepseek-v2-lite.serve.steady32",
           "solar-open2-250b.serve.long32", "nemotron-3-super-120b.serve.burst32"]


def run_with(snapshot):
    serve = None if snapshot is None else {"snapshot": snapshot, "records": []}
    return Run(cell={}, kind="serve", seconds=1.0, chips=1, out_dir="", serve=serve)


@pytest.mark.parametrize("snapshot, want", [
    ({"decode_overlap_share": 0.9875, "decode_steps_dispatched": 80}, 98.75),
    ({"decode_overlap_share": 0.0}, 0.0),          # the sync body: a value, 0
    ({"tick_host_ms_p50": 5.0}, None),             # a parent commit's snapshot
    (None, None),                                  # a training run
], ids=["ring", "sync", "parent", "no_snapshot"])
def test_the_reader_gives_the_share_in_percent_or_nothing(snapshot, want):
    got = load_module("metrics", "decode_overlap_pct").read(run_with(snapshot))
    assert got == (None if want is None else pytest.approx(want))


def test_the_manifest_lists_it_for_the_four_serving_cells():
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": "decode_overlap_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_itl_p95_ms", "workloads": SERVING,
    }
    moved = next(m for m in manifest["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
