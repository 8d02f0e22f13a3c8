"""Engine: compiled SPMD train/eval steps on the 8-device mesh + full Runner.

This is the "minimum end-to-end slice" oracle (SURVEY.md §7 stage 3): the
test-sync config semantics with a synthetic dataset, real pjit/shard_map
collectives on fake devices.
"""
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.engine import (
    Runner,
    build_eval_step,
    build_train_step,
    init_train_state,
)
from pytorch_distributed_training_tpu.models import get_model
from pytorch_distributed_training_tpu.optimizers import SGD
from pytorch_distributed_training_tpu.parallel import (
    DATA_AXIS,
    batch_sharding,
    make_mesh,
    replicated_sharding,
)
from pytorch_distributed_training_tpu.schedulers import multi_step_lr


def _tiny_setup(sync_bn: bool, n_classes: int = 8):
    mesh = make_mesh()
    model = get_model(
        "ResNet18", num_classes=n_classes, axis_name=DATA_AXIS if sync_bn else None
    )
    opt = SGD(lr=0.001, momentum=0.9, weight_decay=1e-4)
    lr_fn = multi_step_lr(0.001, [1000], 0.1)
    state = init_train_state(
        model, opt, jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))
    )
    state = jax.device_put(state, replicated_sharding(mesh))
    train_step = build_train_step(model, opt, lr_fn, mesh, sync_bn=sync_bn)
    eval_step = build_eval_step(model, mesh)
    return mesh, state, train_step, eval_step


def _batch(mesh, rng, batch=64, n_classes=8):
    img = rng.standard_normal((batch, 32, 32, 3)).astype(np.float32)
    label = (rng.integers(0, n_classes, (batch,))).astype(np.int32)
    # class-dependent signal so a few steps of training measurably help
    img += 0.5 * label[:, None, None, None] / n_classes
    g_img = jax.device_put(img, batch_sharding(mesh, 4))
    g_label = jax.device_put(label, batch_sharding(mesh, 1))
    return g_img, g_label


@pytest.mark.parametrize("sync_bn", [True, False])
def test_train_step_decreases_loss(sync_bn):
    mesh, state, train_step, _ = _tiny_setup(sync_bn)
    rng = np.random.default_rng(0)
    img, label = _batch(mesh, rng)
    losses = []
    for _ in range(12):
        state, loss = train_step(state, img, label)
        losses.append(float(loss))
    assert int(state.step) == 12
    assert min(losses[-3:]) < losses[0], losses
    assert np.isfinite(losses).all()


@pytest.mark.slow
def test_train_state_stays_replicated():
    mesh, state, train_step, _ = _tiny_setup(sync_bn=True)
    rng = np.random.default_rng(1)
    img, label = _batch(mesh, rng)
    state, _ = train_step(state, img, label)
    # params remain fully-replicated across the mesh after the update
    leaf = jax.tree.leaves(state.params)[0]
    assert leaf.sharding.is_fully_replicated
    bs_leaf = jax.tree.leaves(state.batch_stats)[0]
    assert bs_leaf.sharding.is_fully_replicated


@pytest.mark.quick
def test_sync_bn_stats_update_in_train_step():
    mesh, state, train_step, _ = _tiny_setup(sync_bn=True)
    before = jax.tree.map(np.asarray, state.batch_stats)
    rng = np.random.default_rng(2)
    img, label = _batch(mesh, rng)
    state, _ = train_step(state, img, label)
    after = jax.tree.map(np.asarray, state.batch_stats)
    changed = jax.tree.map(lambda a, b: not np.allclose(a, b), before, after)
    assert any(jax.tree.leaves(changed))


@pytest.mark.quick
@pytest.mark.parametrize("sync_bn", [True, False], ids=["syncbn", "localbn"])
@pytest.mark.parametrize("grad_accum", [1, 2], ids=["accum1", "accum2"])
def test_dp_step_matches_single_device(grad_accum, sync_bn):
    """8-device DP step == single-device full-batch step.

    The DDP-parity oracle: gradient averaging, SyncBN statistics, and the
    SGD update must all compose to exactly the single-device result.  In
    particular this pins the gradient scale — shard_map's AD transpose
    already psums the replicated params' cotangent, so an extra post-grad
    pmean/psum would make grads world_size x too large (caught here).

    ``sync_bn`` on: BN normalizes with GLOBAL batch statistics, so the
    single device sees the same normalization (with accumulation: the same
    per-micro-batch rows, see ``_micro_major``).  ``sync_bn`` off: each
    replica normalizes its own two rows, which no single-device batch
    reproduces — the reference is a BN-free model there (ViT), where the
    only cross-replica term left is the gradient reduction itself.
    """
    opt = SGD(lr=0.01, momentum=0.9, weight_decay=1e-4)
    lr_fn = multi_step_lr(0.01, [1000], 0.1)
    if sync_bn:
        model = get_model("ResNet18", num_classes=8, axis_name=DATA_AXIS)
    else:
        model = get_model("ViT-Ti16", num_classes=8)
    state0 = init_train_state(
        model, opt, jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))
    )
    rng = np.random.default_rng(7)
    img = rng.standard_normal((16, 32, 32, 3)).astype(np.float32)
    label = rng.integers(0, 8, (16,)).astype(np.int32)

    def run(mesh, img, label):
        step = build_train_step(
            model, opt, lr_fn, mesh, sync_bn=sync_bn, donate=False,
            grad_accum=grad_accum,
        )
        return step(
            jax.device_put(state0, replicated_sharding(mesh)),
            jax.device_put(img, batch_sharding(mesh, 4)),
            jax.device_put(label, batch_sharding(mesh, 1)),
        )

    s8, loss8 = run(make_mesh(), img, label)
    # micro-batch m on 8 devices is row m of every device's shard; one
    # device must see those same rows as ITS micro-batch m
    order = _micro_major(16, 8, grad_accum)
    s1, loss1 = run(
        make_mesh(devices=jax.devices()[:1]), img[order], label[order]
    )

    assert np.isclose(float(loss8), float(loss1), atol=1e-5)
    for a, b in zip(jax.tree.leaves(s8.params), jax.tree.leaves(s1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    for a, b in zip(jax.tree.leaves(s8.batch_stats), jax.tree.leaves(s1.batch_stats)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _micro_major(batch, n_dev, grad_accum):
    """Row order in which ONE device's micro-batch ``m`` holds the rows that
    micro-batch ``m`` holds across ``n_dev`` devices (identity at 1)."""
    per_dev = batch // n_dev
    micro = per_dev // grad_accum
    rows = np.arange(batch).reshape(n_dev, grad_accum, micro)
    return rows.transpose(1, 0, 2).reshape(-1)


def test_eval_step_metrics_sane():
    mesh, state, train_step, eval_step = _tiny_setup(sync_bn=True)
    rng = np.random.default_rng(3)
    img, label = _batch(mesh, rng)
    loss, acc1, acc5 = eval_step(state, img, label)
    assert np.isfinite(float(loss))
    assert 0.0 <= float(acc1) <= 100.0
    assert float(acc5) >= float(acc1)


def _tiny_cfg(tmp_path):
    return {
        "dataset": {
            "name": "synthetic",
            "root": str(tmp_path),
            "n_classes": 8,
            "image_size": 32,
            "n_samples": 128,
        },
        "training": {
            "optimizer": {"name": "SGD", "lr": 0.05, "weight_decay": 1.0e-4, "momentum": 0.9},
            "lr_schedule": {"name": "multi_step", "milestones": [4], "gamma": 0.1},
            "train_iters": 6,
            "print_interval": 2,
            "val_interval": 3,
            "batch_size": 16,
            "num_workers": 2,
            "sync_bn": True,
        },
        "validation": {"batch_size": 16, "num_workers": 2},
        "model": {"name": "ResNet18"},
    }


def test_runner_end_to_end(tmp_path):
    """The reference flow end-to-end: Runner -> worker -> train loop -> val.

    Mirrors cold-start call stack SURVEY.md §3.1 on the 8-device CPU mesh.
    """

    class _FakeTB:
        def __init__(self):
            self.scalars = []

        def add_scalar(self, tag, value, step):
            self.scalars.append((tag, value, step))

    tb = _FakeTB()
    runner = Runner(
        num_nodes=1,
        rank=0,
        seed=1029,
        dist_url="tcp://127.0.0.1:9901",
        dist_backend="tpu",
        multiprocessing=True,
        logger_queue=None,
        global_cfg=_tiny_cfg(tmp_path),
        tb_writer_constructor=lambda: tb,
    )
    runner()

    assert runner.iter == 6
    tags = {t for t, _, _ in tb.scalars}
    # the reference's exact five tag families (train_distributed.py:295-297, :329-331)
    assert {"loss/train", "lr_group/0", "eval/Acc@1", "eval/Acc@5", "eval/loss"} <= tags
    # val ran at iters 2 and 5 (is_val semantics :255-259)
    val_iters = sorted(s for t, _, s in tb.scalars if t == "eval/Acc@1")
    assert val_iters == [2, 5]
    train_losses = [v for t, v, _ in tb.scalars if t == "loss/train"]
    assert all(np.isfinite(v) for v in train_losses)
    # world: all 8 fake devices participate
    assert runner.world_size == 8
    assert runner.global_batch == 16


def test_exact_eval_matches_unsharded():
    """validation.exact (round 5): the masked-sum eval over wrap-padded,
    ragged batches equals the unsharded full-set metrics EXACTLY on a
    deliberately non-divisible val set (N=37, 2 emulated hosts, batch 16;
    the parity eval double-counts the tail — reference
    train_distributed.py:219-222)."""
    from pytorch_distributed_training_tpu.data import DistributedShardSampler
    from pytorch_distributed_training_tpu.engine import build_eval_step_exact

    mesh, state, _, _ = _tiny_setup(sync_bn=False)
    model = get_model("ResNet18", num_classes=8)
    rng = np.random.default_rng(11)
    n_val, host_batch, n_hosts = 37, 16, 2
    imgs = rng.standard_normal((n_val, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 8, (n_val,)).astype(np.int32)

    # ---- unsharded reference over exactly the 37 samples ------------------
    params = jax.device_get(state.params)
    out = model.apply(
        {"params": params, "batch_stats": jax.device_get(state.batch_stats)},
        jnp.asarray(imgs), train=False,
    )
    logp = jax.nn.log_softmax(np.asarray(out, np.float32), axis=-1)
    ce_ref = float(np.mean([-logp[i, labels[i]] for i in range(n_val)]))
    top5 = np.asarray(jax.lax.top_k(out, 5)[1])
    acc1_ref = 100.0 * np.mean(top5[:, 0] == labels)
    acc5_ref = 100.0 * np.mean((top5 == labels[:, None]).any(axis=1))

    # ---- exact eval: 2 emulated hosts, wrap-padded sampler, ragged batches
    step = build_eval_step_exact(model, mesh)
    totals = np.zeros(4, np.float64)
    for rank in range(n_hosts):
        sampler = DistributedShardSampler(
            n_val, num_replicas=n_hosts, rank=rank, shuffle=False
        )
        local = sampler.local_indices()
        assert len(local) == 19  # ceil(37/2): rank 1 carries a wrap dup
        n_real = -(-(n_val - rank) // n_hosts)
        for lo in range(0, len(local), host_batch):
            idx = local[lo:lo + host_batch]
            b = len(idx)
            img = imgs[idx]
            lab = labels[idx]
            mask = (np.arange(lo, lo + b) < n_real).astype(np.int32)
            if b < host_batch:
                pad = host_batch - b
                img = np.concatenate([img, np.repeat(img[-1:], pad, axis=0)])
                lab = np.concatenate([lab, np.zeros(pad, lab.dtype)])
                mask = np.concatenate([mask, np.zeros(pad, np.int32)])
            sums = step(state, jnp.asarray(img), jnp.asarray(lab), jnp.asarray(mask))
            totals += np.asarray([float(x) for x in sums])
    assert totals[3] == n_val  # every real sample counted exactly once
    np.testing.assert_allclose(totals[0] / n_val, ce_ref, rtol=1e-5)
    np.testing.assert_allclose(100 * totals[1] / n_val, acc1_ref, rtol=1e-6)
    np.testing.assert_allclose(100 * totals[2] / n_val, acc5_ref, rtol=1e-6)


def test_runner_exact_eval_smoke(tmp_path):
    """validation.exact drives through the full Runner on a ragged synthetic
    val set (250 % 16 != 0, so the loader wrap-pads the final batch) — the
    exact path must execute end to end and log finite metrics."""

    class _FakeTB:
        def __init__(self):
            self.scalars = []

        def add_scalar(self, tag, value, step):
            self.scalars.append((tag, value, step))

    cfg = _tiny_cfg(tmp_path)
    cfg["dataset"]["n_samples"] = 250
    cfg["validation"]["exact"] = True
    cfg["training"]["train_iters"] = 3
    cfg["training"]["val_interval"] = 3
    tb = _FakeTB()
    runner = Runner(
        num_nodes=1,
        rank=0,
        seed=7,
        dist_url="tcp://127.0.0.1:9902",
        dist_backend="tpu",
        multiprocessing=True,
        logger_queue=None,
        global_cfg=cfg,
        tb_writer_constructor=lambda: tb,
    )
    runner()
    accs = [v for t, v, _ in tb.scalars if t == "eval/Acc@1"]
    assert accs and all(np.isfinite(v) and 0.0 <= v <= 100.0 for v in accs)
