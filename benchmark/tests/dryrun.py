"""A temporary copy of the benchmark with the tests' toy cells added AS
FILES ONLY (no file of the copy is edited except the manifest, which a
later PR extends the same way), beside links to the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
PROGRAM = ("pytorch_distributed_training_tpu", "train_distributed.py", "config", "native")


def make_copy(tmp: str) -> str:
    """Copy ``benchmark/`` and ``BENCHMARK.json`` to ``tmp``, link the
    program beside them, drop the toy files in, and list them in the
    manifest.  Returns the copy's root."""
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in PROGRAM:
        os.symlink(os.path.join(REPO, name), os.path.join(tmp, name))
    dry = os.path.join(HERE, "data", "dry")
    for sub in ("configs", "traffic", "metrics"):
        src = os.path.join(dry, sub)
        for name in (os.listdir(src) if os.path.isdir(src) else ()):
            shutil.copy(os.path.join(src, name), os.path.join(tmp, "benchmark", sub, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fp:
        manifest = json.load(fp)
    with open(os.path.join(dry, "manifest_additions.json")) as fp:
        extra = json.load(fp)
    for key in ("configs", "workloads", "per_layer"):
        manifest[key] += extra.get(key, [])
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        for cell in extra["workloads"]:
            if "workloads" in metric and cell["like"] in metric["workloads"]:
                metric["workloads"].append(cell["name"])
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fp:
        json.dump(manifest, fp)
    return tmp


def run_cell(root: str, workload: str, *more, dry=True, env=None, timeout=600):
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"),
           "--workload", workload, "--seed", "7", "--seconds", "1", *more]
    if dry:
        cmd.append("--dry")
    full = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(cmd, capture_output=True, text=True, env=full,
                          cwd=root, timeout=timeout)


if __name__ == "__main__":
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        proc = run_cell(make_copy(tmp), *sys.argv[1:])
        print(proc.stdout[-6000:], proc.stderr[-6000:])
