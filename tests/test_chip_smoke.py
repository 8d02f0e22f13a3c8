"""``chip_smoke.py`` off the chip: it refuses, quickly, and builds nothing.

What it does ON the chip is proved by running it there (README "Running on
the chip"); here only the two ways it must fail are pinned.
"""
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_a_cpu_within_seconds(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py"),
         "--out", str(tmp_path / "out")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode not in (0, None), proc.stdout + proc.stderr
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    # refused before anything was built: no phase line, no output directory
    assert len(lines) == 1 and not (tmp_path / "out").exists()
    assert elapsed < 60, f"refusal took {elapsed:.0f}s"


def test_a_phase_that_raises_fails_the_run(capsys):
    import chip_smoke

    def fine(ctx):
        return {"phase": "fine", "problems": []}

    def broken(ctx):
        raise RuntimeError("boom")

    assert chip_smoke.run_phases(None, [fine]) is True
    assert chip_smoke.run_phases(None, [broken, fine]) is False
    records = [
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    assert [r["ok"] for r in records] == [True, False, True]
    assert "boom" in records[1]["problems"][0]
