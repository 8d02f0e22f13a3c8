"""How ``correct`` is decided, at a size a test run can hold (CPU, toy
cells, float32 configuration so the control is bfloat16).

Tolerances of the toy cells (tests/data/dry/configs/*.json) and why: the
float32 program agrees with the float32 reference to rounding (loss 1e-7,
first gradient 2e-7 for the LM), so the limits sit at 1e-5 (loss), 1e-4
(first gradient), which bfloat16 operands miss by a factor of ten or more;
the parameters' change is held against a step that changes nothing (gap 1).
"""
import json
import subprocess
import sys

import pytest

from benchmark.tests import dryrun


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return dryrun.make_copy(str(tmp_path_factory.mktemp("bench")))


def lines(proc, prefix):
    return [
        json.loads(line[len(prefix):]) for line in proc.stdout.splitlines()
        if line.startswith(prefix)
    ]


def drive(copy, workload, mode):
    proc = subprocess.run(
        [sys.executable, dryrun.HERE + "/drive.py", copy, workload, mode],
        capture_output=True, text=True, timeout=900, cwd=copy,
        env=dict(dryrun.os.environ, JAX_PLATFORMS="cpu"),
    )
    assert "check_correct" in proc.stdout, proc.stderr[-3000:]
    return lines(proc, "check_correct ")[0], lines(proc, "compared ")


@pytest.mark.parametrize("workload", [
    "lmtiny.train.dry", "lmtiny.serve.dry", "resnet50tiny.train.imgdry",
])
def test_sound_program_agrees_with_the_plain_reference(copy, workload):
    correct, rows = drive(copy, workload, "none")
    assert correct, [r for r in rows if not r["ok"]]


@pytest.mark.parametrize("workload,mode,caught_by", [
    ("lmtiny.train.dry", "unchanged_state", "param_change_norm_worst_leaf_gap"),
    ("lmtiny.train.dry", "half_batch", "loss_step0_rel_gap"),
    ("resnet50tiny.train.imgdry", "unchanged_state", "param_change_norm_worst_leaf_gap"),
    ("lmtiny.serve.dry", "altered_token", "served_token_logit_gap_widest"),
])
def test_broken_timed_path_is_not_correct(copy, workload, mode, caught_by):
    correct, rows = drive(copy, workload, mode)
    assert not correct
    assert not {r["compared"]: r["ok"] for r in rows}[caught_by]


@pytest.mark.parametrize("workload", ["lmtiny.train.dry", "lmtiny.serve.dry"])
def test_lower_precision_control_fails_a_limit(copy, workload):
    """The reference in the nearest precision below the configuration's,
    held to the same comparison, must miss at least one of the limits the
    sound program meets."""
    proc = dryrun.run_cell(copy, workload, "--control")
    assert proc.returncode == 0, proc.stderr[-3000:]
    limits = {r["compared"]: r["limit"] for r in lines(proc, "compared ")}
    assert all(r["ok"] for r in lines(proc, "compared "))
    control = lines(proc, "control ")
    assert control and any(r["value"] > limits[r["compared"]] for r in control)
