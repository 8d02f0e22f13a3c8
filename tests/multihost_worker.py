"""Subprocess worker for the multi-host execution test.

Runs the REAL multi-host path end to end in one OS process per "host":
``jax.distributed.initialize`` over the coordination service (the
reference's ``dist.init_process_group`` rendezvous,
/root/reference/train_distributed.py:149-154), a global mesh spanning both
processes' virtual CPU devices, per-host ``DistributedShardSampler`` shards,
and ``jax.make_array_from_process_local_data`` batch assembly — the code
paths that single-process tests cannot reach.

Driven by tests/test_multihost.py via environment variables:
  MH_RANK           process id (0-based)
  MH_NUM_NODES      number of processes ("hosts")
  MH_PORT           coordinator port on 127.0.0.1 — or a comma-separated
                    candidate list; rank 0 probes them in order (bounded,
                    one attempt per candidate) and publishes the winner
                    through MH_PORT_FILE, so a bind collision with another
                    test run retries on the next candidate instead of dying
  MH_PORT_FILE      rendezvous file for the chosen port (required when
                    MH_PORT lists more than one candidate)
  MH_OUT            output JSON path (plus <MH_OUT>.npz for final params)
  MH_LOCAL_DEVICES  virtual CPU devices per process
  MH_BATCH_DIVISION training.batch_division value ("local" or "world")
  MH_ELASTIC        "1" arms training.elastic (heartbeat peer-loss layer)
  MH_HB_INTERVAL    elastic heartbeat interval seconds (default 0.1)
  MH_HB_TIMEOUT     elastic peer timeout seconds (default 0.75)

A diagnosed peer loss (engine.elastic.PeerLostError) is NOT a worker
failure: the survivor writes its JSON with the diagnosis + recovery
counters and exits 0 — the driving test asserts on that record.

The platform is pinned to CPU *before* JAX is imported: the parent may hold
a chip (``__graft_entry__.py``, ``chip_smoke.py``), and a chip belongs
to one process — a worker that reached for it would fail or hang.
"""
import json
import os
import sys
import time

rank = int(os.environ["MH_RANK"])
num_nodes = int(os.environ["MH_NUM_NODES"])
out_path = os.environ["MH_OUT"]
local_devices = int(os.environ.get("MH_LOCAL_DEVICES", "4"))


def _choose_port(spec: str, rank: int) -> str:
    """Resolve the coordinator port from a candidate list (see MH_PORT)."""
    candidates = [c.strip() for c in spec.split(",") if c.strip()]
    port_file = os.environ.get("MH_PORT_FILE")
    if len(candidates) == 1 and not port_file:
        return candidates[0]  # legacy single-port path, no rendezvous file
    if not port_file:
        raise RuntimeError(
            "MH_PORT lists multiple candidates; set MH_PORT_FILE so "
            "non-zero ranks can learn which one rank 0 bound"
        )
    if rank == 0:
        import socket

        last_err = None
        for cand in candidates:  # bounded: one probe per candidate
            try:
                s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                try:
                    s.bind(("127.0.0.1", int(cand)))
                finally:
                    s.close()
            except OSError as e:
                last_err = e
                continue
            tmp = port_file + ".tmp"
            with open(tmp, "w") as fp:
                fp.write(cand)
            os.replace(tmp, port_file)  # atomic publish
            return cand
        raise RuntimeError(
            f"no free coordinator port among {candidates}: {last_err}"
        )
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        try:
            with open(port_file) as fp:
                text = fp.read().strip()
        except OSError:
            text = ""
        if text:
            return text
        time.sleep(0.05)
    raise RuntimeError(
        f"rank {rank}: rank 0 never published a coordinator port to "
        f"{port_file} within 30s"
    )


port = _choose_port(os.environ["MH_PORT"], rank)

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={local_devices}"
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from pytorch_distributed_training_tpu.engine import (  # noqa: E402
    PeerLostError,
    Runner,
    fault,
)


class _RecordingTB:
    """Minimal SummaryWriter stand-in capturing every scalar write."""

    def __init__(self):
        self.scalars = []

    def add_scalar(self, tag, value, step):
        self.scalars.append((tag, float(value), int(step)))


class _RecordingRunner(Runner):
    """Runner that additionally records the per-iteration loss scalar, and
    can deliver a SIGTERM to ITSELF at a configured iteration (simulating a
    spot eviction landing on exactly one host — the multi-process
    preemption-agreement path, runner._globally_preempted)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.losses = []
        self._self_preempt_at = int(os.environ.get("MH_SELF_PREEMPT_AT", "-1"))
        self._self_preempt_rank = int(
            os.environ.get("MH_SELF_PREEMPT_RANK", "-1")
        )

    def train_iter(self, g_img, g_label):
        self.state, loss = self.train_step(self.state, g_img, g_label)
        self.losses.append(float(loss))
        self.scheduler.step()  # per-iteration, reference :299
        if (
            self.iter == self._self_preempt_at
            and self.current_rank == self._self_preempt_rank
        ):
            import signal

            os.kill(os.getpid(), signal.SIGTERM)


def main():
    task = os.environ.get("MH_TASK", "image")
    if task == "lm":
        # multi-process long-context path: token dataset + TransformerLM,
        # tokens sharded over the (data, sequence) axes across processes
        dataset = {
            "name": "synthetic_text",
            "root": "/unused",
            "n_classes": 64,
            "seq_len": 32,
            "n_samples": 128,
        }
        model = {"name": "TransformerLM", "embed_dim": 32, "depth": 2,
                 "num_heads": 4}
        extra = {"sequence_parallelism": int(os.environ.get("MH_SEQ_PAR", "1"))}
    else:
        dataset = {
            "name": "synthetic",
            "root": "/unused",
            "n_classes": 8,
            "image_size": 32,
            "n_samples": 128,
        }
        model = {"name": "ResNet18"}
        extra = {}
    ckpt_dir = os.environ.get("MH_CKPT_DIR")
    ckpt = (
        {
            "checkpoint": {
                "dir": ckpt_dir,
                # huge regular interval: only the preemption path (or the
                # final iteration) writes, so the test can attribute saves
                "interval": int(os.environ.get("MH_CKPT_INTERVAL", "100000")),
                "preemption_sync_interval": int(
                    os.environ.get("MH_PREEMPT_SYNC", "2")
                ),
            }
        }
        if ckpt_dir
        else {}
    )
    if os.environ.get("MH_ELASTIC") == "1":
        ckpt["elastic"] = {
            "enabled": True,
            "heartbeat_interval": float(os.environ.get("MH_HB_INTERVAL", "0.1")),
            "timeout": float(os.environ.get("MH_HB_TIMEOUT", "0.75")),
        }
    cfg = {
        "dataset": dataset,
        "training": {
            **ckpt,
            "optimizer": {
                "name": "SGD",
                # small lr: keeps the 4-step trajectory out of the chaotic
                # large-step regime so cross-topology float32 reduction-order
                # noise stays at tolerance scale instead of amplifying
                "lr": 0.001,
                "weight_decay": 1.0e-4,
                "momentum": 0.9,
            },
            "lr_schedule": {"name": "multi_step", "milestones": [100], "gamma": 0.1},
            "train_iters": int(os.environ.get("MH_TRAIN_ITERS", "4")),
            "print_interval": 1,
            "val_interval": 100,  # is_val still fires on the last iter (p3)
            "batch_size": 16,
            "num_workers": 2,
            "sync_bn": task != "lm",
            "batch_division": os.environ.get("MH_BATCH_DIVISION", "world"),
            **extra,
        },
        "validation": {"batch_size": 16, "num_workers": 2},
        "model": model,
    }
    tb = _RecordingTB()
    runner = _RecordingRunner(
        num_nodes=num_nodes,
        rank=rank,
        seed=1029,
        dist_url=f"tcp://127.0.0.1:{port}",
        dist_backend="tpu",
        multiprocessing=False,
        logger_queue=None,
        global_cfg=cfg,
        tb_writer_constructor=lambda: tb,
    )
    try:
        runner()
    except PeerLostError as e:
        # the DIAGNOSED dead-peer outcome the elastic layer promises: record
        # it (plus the recovery counters — the emergency save already ran in
        # runner._on_peer_lost) and exit 0.  os._exit skips interpreter
        # teardown: jax.distributed shutdown barriers would hang against the
        # very peer whose death was just diagnosed.
        with open(out_path, "w") as fp:
            json.dump(
                {
                    "rank": rank,
                    "peer_lost": str(e),
                    "dead_ranks": list(getattr(e, "dead_ranks", ())),
                    "mid_step": bool(getattr(e, "mid_step", False)),
                    "losses": runner.losses,
                    "final_iter": runner.iter,
                    "counters": fault.counters(),
                },
                fp,
            )
            fp.flush()
            os.fsync(fp.fileno())
        os._exit(0)

    params = jax.tree.leaves(jax.tree.map(np.asarray, runner.state.params))
    np.savez(out_path + ".npz", **{f"p{i}": p for i, p in enumerate(params)})
    with open(out_path, "w") as fp:
        json.dump(
            {
                "rank": rank,
                "process_count": jax.process_count(),
                "world_size": runner.world_size,
                "global_batch": runner.global_batch,
                "losses": runner.losses,
                "final_iter": runner.iter,
                "eval": {t: v for t, v, _ in tb.scalars if t.startswith("eval/")},
                "counters": fault.counters(),
                "param_bytes_digest": __import__("hashlib").sha256(
                    b"".join(p.tobytes() for p in params)
                ).hexdigest(),
            },
            fp,
        )


if __name__ == "__main__":
    main()
