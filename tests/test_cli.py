"""CLI-level behavior that Runner-level tests cannot reach.

The crash path (reference train_distributed.py:77-86): a failure inside
the runner must log CRITICAL, delete ONLY the TensorBoard event directory
(the reference's rmtree bug deleted the whole log dir — we implement the
intent), keep the text log and stop the listener cleanly.  The reference
then exits 0; here the crash propagates and the CLI exits 1, so a launcher
cannot take a crashed run for a finished one.
"""
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BAD_CFG = """\
dataset: {name: synthetic, root: /tmp/none, n_classes: 8, image_size: 32, n_samples: 64}
training:
    optimizer: {name: SGD, lr: 0.01, weight_decay: 1.0e-4, momentum: 0.9}
    lr_schedule: {name: multi_step, milestones: [6], gamma: 0.1}
    train_iters: 4
    print_interval: 2
    val_interval: 4
    batch_size: 16
    num_workers: 2
    sync_bn: True
validation: {batch_size: 16, num_workers: 2}
model: {name: NoSuchModel}
"""


def test_cli_crash_path_cleans_tb_only(tmp_path):
    cfg = tmp_path / "bad.yml"
    cfg.write_text(_BAD_CFG)
    log_dir = tmp_path / "run"
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=_ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    )
    proc = subprocess.run(
        [
            sys.executable, os.path.join(_ROOT, "train_distributed.py"),
            "--num-nodes", "1", "--rank", "0",
            "--dist-backend", "tpu", "--dist-url", "tcp://127.0.0.1:9981",
            "--log-dir", str(log_dir), "--file-name-cfg", "bad",
            "--cfg-filepath", str(cfg), "--seed", "1",
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    # logged and cleaned up as the reference does, then a failing status
    assert proc.returncode == 1, proc.stdout + proc.stderr
    log_file = log_dir / "bad.log"
    assert log_file.exists()
    content = log_file.read_text()
    assert "CRITICAL" in content and "NoSuchModel" in content
    # only the TB event dir is removed; the text log survives
    assert not (log_dir / "tf-board-logs").exists()

