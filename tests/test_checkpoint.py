"""Checkpoint/resume: config-gated orbax save/restore of the full TrainState."""
import pytest
import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_tpu.engine import Runner
from pytorch_distributed_training_tpu.engine.checkpoint import Checkpointer


def _cfg(tmp_path, ckpt=True, train_iters=4):
    cfg = {
        "dataset": {
            "name": "synthetic",
            "root": str(tmp_path),
            "n_classes": 4,
            "image_size": 16,
            "n_samples": 64,
        },
        "training": {
            "optimizer": {"name": "SGD", "lr": 0.01, "weight_decay": 1.0e-4, "momentum": 0.9},
            "lr_schedule": {"name": "multi_step", "milestones": [100], "gamma": 0.1},
            "train_iters": train_iters,
            "print_interval": 10,
            "val_interval": 100,
            "batch_size": 16,
            "num_workers": 0,
            "sync_bn": True,
        },
        "validation": {"batch_size": 16, "num_workers": 0},
        "model": {"name": "ResNet18"},
    }
    if ckpt:
        cfg["training"]["checkpoint"] = {
            "dir": str(tmp_path / "ckpt"),
            "interval": 2,
            "resume": True,
        }
    return cfg


def _run(cfg):
    runner = Runner(
        num_nodes=1, rank=0, seed=3, dist_url="tcp://127.0.0.1:9901",
        dist_backend="tpu", multiprocessing=False, logger_queue=None,
        global_cfg=cfg, tb_writer_constructor=lambda: None,
    )
    runner()
    return runner


def test_from_config_gating(tmp_path):
    assert Checkpointer.from_config({}) is None
    assert Checkpointer.from_config({"checkpoint": {}}) is None
    ck = Checkpointer.from_config({"checkpoint": {"dir": str(tmp_path), "interval": 5}})
    assert ck is not None and ck.interval == 5
    ck.close()


def test_save_and_resume(tmp_path):
    cfg = _cfg(tmp_path, train_iters=4)
    r1 = _run(cfg)
    params_after_4 = jax.tree.map(np.asarray, r1.state.params)
    assert int(r1.state.step) == 4

    # Second run with train_iters extended: must resume from iter 4 (saved at
    # iters 1 and 3 via interval=2 -> latest step 3, resume at 4), not restart.
    cfg2 = _cfg(tmp_path, train_iters=6)
    r2 = _run(cfg2)
    assert int(r2.state.step) == 6
    # resumed state continued from the first run's params (not re-initialized)
    leaf1 = jax.tree.leaves(params_after_4)[0]
    leaf2 = jax.tree.leaves(jax.tree.map(np.asarray, r2.state.params))[0]
    assert not np.allclose(leaf1, leaf2)  # moved past iter-4 params

    # Third run with same train_iters=6: nothing left to do, state preserved
    cfg3 = _cfg(tmp_path, train_iters=6)
    r3 = _run(cfg3)
    assert int(r3.state.step) == 6
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(r3.state.params)[0]), leaf2, rtol=0, atol=0
    )


def test_resume_false_populated_dir_rejected(tmp_path):
    """orbax never overwrites a step; fresh-run-into-populated-dir must fail fast."""
    import pytest

    _run(_cfg(tmp_path, train_iters=2))  # populates ckpt dir (step 1)
    cfg = _cfg(tmp_path, train_iters=2)
    cfg["training"]["checkpoint"]["resume"] = False
    with pytest.raises(Exception) as exc_info:
        _run(cfg)
    assert "resume is False" in str(exc_info.value)


def test_resume_bit_exact_vs_straight_run(tmp_path):
    """4 iters straight == 2 iters + checkpoint + resume 2 more (bit-exact)."""
    straight = _run(_cfg(tmp_path / "a", ckpt=False, train_iters=4))

    cfg_b = _cfg(tmp_path / "b", train_iters=2)
    cfg_b["training"]["checkpoint"]["interval"] = 2
    _run(cfg_b)
    cfg_b2 = _cfg(tmp_path / "b", train_iters=4)
    cfg_b2["training"]["checkpoint"]["interval"] = 2
    resumed = _run(cfg_b2)

    a = jax.tree.map(np.asarray, straight.state.params)
    b = jax.tree.map(np.asarray, resumed.state.params)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)


@pytest.fixture
def one_device_graft(monkeypatch):
    """Pin the Runner to a ONE-device mesh: the resume logic under test is
    device-count independent, and one device keeps the run quick."""
    from pytorch_distributed_training_tpu.engine import paths
    from pytorch_distributed_training_tpu.parallel import make_mesh

    mesh = make_mesh(jax.devices()[:1])
    monkeypatch.setattr(paths, "make_mesh", lambda *a, **kw: mesh)
    return mesh


class _BatchHashingRunner(Runner):
    """Records a digest of every training batch the step consumes — the
    observable the mid-epoch-resume contract is stated in."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.batch_hashes = []

    def train_iter(self, g_img, g_label):
        import hashlib

        h = hashlib.sha256()
        h.update(np.asarray(g_img).tobytes())
        h.update(np.asarray(g_label).tobytes())
        self.batch_hashes.append(h.hexdigest())
        super().train_iter(g_img, g_label)


def _run_hashing(cfg):
    runner = _BatchHashingRunner(
        num_nodes=1, rank=0, seed=3, dist_url="tcp://127.0.0.1:9903",
        dist_backend="tpu", multiprocessing=False, logger_queue=None,
        global_cfg=cfg, tb_writer_constructor=lambda: None,
    )
    runner()
    return runner


@pytest.mark.slow
def test_mid_epoch_resume_batch_sequence_bit_exact(tmp_path, one_device_graft):
    """Interrupt at iteration 2 of a 4-batch epoch and resume: the resumed
    run must consume EXACTLY the batches (bitwise) the uninterrupted run
    would have — pinned on the batch digests, not just the final params —
    and the checkpoint must carry the (epoch, batch_in_epoch) sidecar the
    resume used."""
    import json as _json
    import os

    straight = _run_hashing(_cfg(tmp_path / "a", ckpt=False, train_iters=6))
    assert len(straight.batch_hashes) == 6  # 64 samples/16 = 4 per epoch

    cfg_b = _cfg(tmp_path / "b", train_iters=2)
    first = _run_hashing(cfg_b)
    assert first.batch_hashes == straight.batch_hashes[:2]

    # the interval-2 save at step 1 wrote the pipeline sidecar: 2 batches
    # of epoch 0 consumed — a MID-epoch position
    sidecar = os.path.join(str(tmp_path / "b" / "ckpt"), "pipeline_1.json")
    assert os.path.exists(sidecar), "pipeline sidecar missing"
    with open(sidecar) as fp:
        extras = _json.load(fp)
    assert extras["epoch"] == 0 and extras["batch_in_epoch"] == 2
    assert extras["batches_per_epoch"] == 4

    resumed = _run_hashing(_cfg(tmp_path / "b", train_iters=6))
    assert resumed.iter == 6
    # the resumed stream picked up at epoch 0, batch 2 — bit-identical
    assert resumed.batch_hashes == straight.batch_hashes[2:]


def test_emergency_checkpoint_roundtrip_and_precedence(tmp_path):
    """save_emergency/restore_latest: a survivor's local dump of fully-
    replicated state restores exactly (values + extras), is preferred over
    OLDER orbax steps, and yields to NEWER ones; non-replicated state is
    rejected with a diagnosis instead of silently saving one shard."""
    from pytorch_distributed_training_tpu.engine import TrainState, fault
    from pytorch_distributed_training_tpu.optimizers import SGD
    from pytorch_distributed_training_tpu.parallel import replicated_sharding
    from pytorch_distributed_training_tpu.parallel.mesh import make_mesh

    opt = SGD(lr=0.1, momentum=0.9)
    mesh = make_mesh()

    def make_state(fill):
        params = {"w": jnp.full((8, 4), float(fill)), "b": jnp.full((4,), float(fill))}
        state = TrainState(
            params=params, batch_stats={}, opt_state=opt.init(params)
        )
        return jax.device_put(state, replicated_sharding(mesh))

    fault.reset_counters()
    ck = Checkpointer(str(tmp_path / "c"), interval=1)
    ck.save(3, make_state(3.0))
    ck.wait()

    extras = {"epoch": 1, "batch_in_epoch": 2, "batches_per_epoch": 4}
    ck.save_emergency(4, make_state(4.0), extras=extras)
    assert ck.latest_emergency() == 4
    assert ck.read_extras(4)["batch_in_epoch"] == 2

    # newer than orbax step 3: the emergency dump wins
    restored, next_iter = ck.restore_latest(make_state(0.0))
    assert next_iter == 5
    np.testing.assert_array_equal(
        np.asarray(restored.params["w"]), np.full((8, 4), 4.0)
    )
    assert fault.counters().get("elastic_restores") == 1

    # an orbax step NEWER than the emergency takes precedence again
    ck.save(9, make_state(9.0))
    ck.wait()
    restored2, next_iter2 = ck.restore_latest(make_state(0.0))
    assert next_iter2 == 10
    np.testing.assert_array_equal(
        np.asarray(restored2.params["w"]), np.full((8, 4), 9.0)
    )

    # sharded (non-replicated) state: a lone survivor holds one shard only
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharded = jax.device_put(
        jnp.arange(32.0).reshape(8, 4), NamedSharding(mesh, P("data"))
    )
    bad = TrainState(
        params={"w": sharded}, batch_stats={}, opt_state=opt.init({"w": sharded})
    )
    with pytest.raises(ValueError, match="survivor"):
        ck.save_emergency(11, bad)
    ck.close()


def test_preemption_guard_restores_handlers():
    import signal

    from pytorch_distributed_training_tpu.engine.preemption import PreemptionGuard

    before = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard() as g:
        assert signal.getsignal(signal.SIGTERM) is not before
        assert not g.triggered
    assert signal.getsignal(signal.SIGTERM) is before


def test_preemption_checkpoints_current_iter_and_resumes(tmp_path, monkeypatch):
    """SIGTERM mid-run (engine/preemption.py): the loop must save a
    checkpoint at the CURRENT iteration — not an interval boundary — exit
    cleanly, and a relaunch must resume past it to completion.

    The signal is raised from inside the third train_iter (so the guard is
    installed and the timing is deterministic — a wall-clock timer can fire
    during setup, before the guard exists, and kill the process)."""
    import os
    import signal

    cfg = _cfg(tmp_path, train_iters=400)
    # a huge interval isolates the preemption save from the periodic one
    cfg["training"]["checkpoint"]["interval"] = 10_000

    orig = Runner.train_iter
    calls = {"n": 0}

    def train_then_preempt(self, *args):
        orig(self, *args)
        calls["n"] += 1
        if calls["n"] == 3:
            os.kill(os.getpid(), signal.SIGTERM)

    monkeypatch.setattr(Runner, "train_iter", train_then_preempt)
    runner = _run(cfg)
    monkeypatch.setattr(Runner, "train_iter", orig)
    stopped_at = runner.iter
    assert stopped_at == 2  # preempted during the 3rd iteration (0-indexed)
    ck = Checkpointer(str(tmp_path / "ckpt"))
    assert ck.latest() == stopped_at
    ck.close()

    # relaunch with a few more iters: resumes from the preemption save
    cfg2 = _cfg(tmp_path, train_iters=stopped_at + 3)
    cfg2["training"]["checkpoint"]["interval"] = 10_000
    runner2 = _run(cfg2)
    assert runner2.iter == stopped_at + 3


def test_preemption_opt_out(tmp_path):
    """checkpoint.preemption: False keeps the reference's fail-fast
    behavior — no guard is installed."""
    cfg = _cfg(tmp_path, train_iters=2)
    cfg["training"]["checkpoint"]["preemption"] = False
    runner = _run(cfg)
    assert runner._preempt is None
    assert runner.iter == 2


def test_restore_converts_pp_layout_both_ways(tmp_path):
    """A checkpoint written under pipeline_parallelism (stacked
    {blocks, shared} params + mirrored optimizer moments) restores into a
    non-PP run's per-layer state — and vice versa — via the automatic
    layout conversion (round-2 ADVICE item; engine/checkpoint.py).  Values
    must round-trip exactly; the optimizer step counter and moment trees
    convert with the params."""
    from pytorch_distributed_training_tpu.engine import TrainState
    from pytorch_distributed_training_tpu.models.transformer_lm import (
        TransformerLM,
    )
    from pytorch_distributed_training_tpu.optimizers import SGD
    from pytorch_distributed_training_tpu.parallel import (
        make_pp_mesh,
        pp_stack_params,
        pp_state_shardings,
        replicated_sharding,
    )
    from pytorch_distributed_training_tpu.parallel.mesh import make_mesh

    depth = 4
    model = TransformerLM(
        vocab_size=32, max_len=8, embed_dim=16, depth=depth, num_heads=2,
        seq_axis=None,
    )
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    opt = SGD(lr=0.1, momentum=0.9)

    # --- flat checkpoint -> PP state -----------------------------------
    mesh = make_mesh()
    flat_state = TrainState(
        params=params, batch_stats={}, opt_state=opt.init(params)
    )
    flat_state = jax.device_put(flat_state, replicated_sharding(mesh))
    ck1 = Checkpointer(str(tmp_path / "flat"), interval=1)
    ck1.save(5, flat_state)
    ck1.wait()

    pp_mesh = make_pp_mesh(4)
    pp_params = pp_stack_params(params, depth)
    pp_state = TrainState(
        params=jax.tree.map(jnp.zeros_like, pp_params),
        batch_stats={},
        opt_state=opt.init(jax.tree.map(jnp.zeros_like, pp_params)),
    )
    pp_state = jax.device_put(pp_state, pp_state_shardings(pp_state, pp_mesh))
    restored, next_iter = ck1.restore_latest(pp_state)
    ck1.close()
    assert next_iter == 6
    for a, b in zip(jax.tree.leaves(restored.params), jax.tree.leaves(pp_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # stage shardings of the target were applied
    assert restored.params["blocks"]["attn"]["qkv"]["kernel"].sharding.spec[0] == "stage"

    # --- PP checkpoint -> flat state -----------------------------------
    pp_src = TrainState(
        params=pp_params, batch_stats={}, opt_state=opt.init(pp_params)
    )
    pp_src = jax.device_put(pp_src, pp_state_shardings(pp_src, pp_mesh))
    ck2 = Checkpointer(str(tmp_path / "pp"), interval=1)
    ck2.save(9, pp_src)
    ck2.wait()

    flat_target = TrainState(
        params=jax.tree.map(jnp.zeros_like, params),
        batch_stats={},
        opt_state=opt.init(jax.tree.map(jnp.zeros_like, params)),
    )
    flat_target = jax.device_put(flat_target, replicated_sharding(mesh))
    restored2, next_iter2 = ck2.restore_latest(flat_target)
    ck2.close()
    assert next_iter2 == 10
    for a, b in zip(jax.tree.leaves(restored2.params), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_nonstructural_error_not_misdiagnosed(tmp_path):
    """A corrupt checkpoint (array data destroyed, structure unchanged)
    must raise the ORIGINAL IO/orbax error — not the layout-mismatch
    RuntimeError, whose pp_stack/unstack advice would send the operator
    debugging pipeline settings instead of the disk.  Structural-vs-IO is
    decided from the checkpoint's stored tree metadata
    (Checkpointer._structure_differs), not error-string keywords."""
    import os
    import shutil

    from pytorch_distributed_training_tpu.engine import TrainState
    from pytorch_distributed_training_tpu.optimizers import SGD
    from pytorch_distributed_training_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_training_tpu.parallel import replicated_sharding

    params = {"w": jnp.ones((4, 4))}
    opt = SGD(lr=0.1)
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    state = jax.device_put(state, replicated_sharding(make_mesh()))
    ck = Checkpointer(str(tmp_path / "c"), interval=1)
    ck.save(3, state)
    ck.wait()
    # same structure, destroyed payload: gut every array store's contents
    # under the step dir (keep the directory skeleton so metadata-based
    # structure detection still sees a matching tree where possible)
    step_dir = os.path.join(ck.directory, "3")
    removed = 0
    for root, dirs, files in os.walk(step_dir):
        for f in files:
            if f not in ("_METADATA", "metadata", "manifest.ocdbt"):
                os.remove(os.path.join(root, f))
                removed += 1
    assert removed > 0, "corruption setup removed nothing"
    with pytest.raises(Exception) as exc_info:
        ck.restore_latest(state)
    ck.close()
    # it must NOT be the layout-mismatch wrapper
    assert "pp_stack_params" not in str(exc_info.value), (
        "corruption misdiagnosed as a params-layout mismatch:\n"
        f"{exc_info.value}"
    )


def test_corrupt_newest_checkpoint_falls_back_to_previous_valid(tmp_path, caplog):
    """Fault-tolerance satellite: a truncated/corrupt NEWEST checkpoint is
    skipped with a warning and restore_latest falls back to the newest
    EARLIER valid step — a partial write during eviction must not brick the
    relaunch.  (A single corrupt step with nothing to fall back to still
    raises the raw error: test_restore_nonstructural_error_not_misdiagnosed.)"""
    import logging
    import os

    from pytorch_distributed_training_tpu.engine import TrainState, fault
    from pytorch_distributed_training_tpu.optimizers import SGD
    from pytorch_distributed_training_tpu.parallel import replicated_sharding
    from pytorch_distributed_training_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_training_tpu.utils.retry import Retry

    opt = SGD(lr=0.1)

    def make_state(fill):
        params = {"w": jnp.full((4, 4), float(fill))}
        state = TrainState(
            params=params, batch_stats={}, opt_state=opt.init(params)
        )
        return jax.device_put(state, replicated_sharding(make_mesh()))

    # attempts=1: the corrupt step must fail over to the previous step, not
    # burn retry backoff on a permanently damaged directory
    ck = Checkpointer(str(tmp_path / "c"), interval=1, retry=Retry(attempts=1))
    ck.save(1, make_state(1.0))
    ck.save(3, make_state(3.0))
    ck.wait()
    step_dir = os.path.join(ck.directory, "3")
    removed = 0
    for root, dirs, files in os.walk(step_dir):
        for f in files:
            if f not in ("_METADATA", "metadata", "manifest.ocdbt"):
                os.remove(os.path.join(root, f))
                removed += 1
    assert removed > 0, "corruption setup removed nothing"

    fault.reset_counters()
    logger = logging.getLogger("ckpt-fallback-test")
    with caplog.at_level(logging.WARNING, logger=logger.name):
        restored, next_iter = ck.restore_latest(make_state(0.0), logger)
    ck.close()
    assert next_iter == 2  # step 1 restored, not the corrupt step 3
    np.testing.assert_array_equal(
        np.asarray(restored.params["w"]), np.full((4, 4), 1.0)
    )
    assert fault.counters().get("ckpt_fallbacks") == 1
    assert any("falling back" in r.getMessage() for r in caplog.records)


def test_orbax_metadata_contract_version_guard(monkeypatch):
    """The layout-vs-corruption discriminator leans on orbax's (undocumented)
    item_metadata tree-structure convention.  The installed orbax must be
    inside the verified range, and outside it the discriminator must decline
    to classify (return False -> raw restore errors re-raise) rather than
    risk misreading a changed metadata layout as a checkpoint-layout
    mismatch (round-4 VERDICT #8 / round-3 ADVICE #3)."""
    import orbax.checkpoint as ocp

    from pytorch_distributed_training_tpu.engine import checkpoint as ckpt_mod

    # (a) the baked-in orbax is inside the verified range
    assert ckpt_mod._orbax_metadata_contract_ok(), (
        f"installed orbax {ocp.__version__} is outside "
        f"{ckpt_mod._ORBAX_METADATA_CONTRACT_RANGE}; re-verify the "
        "item_metadata contract (wrong-layout restore tests above) and "
        "extend the range"
    )

    # (b) outside the range, _structure_differs declines without touching
    # the manager (guard short-circuits before any metadata read)
    monkeypatch.setattr(ocp, "__version__", "99.0.0")
    assert not ckpt_mod._orbax_metadata_contract_ok()
    differs = Checkpointer._structure_differs(
        object.__new__(Checkpointer), 0, {"w": jnp.ones(2)}
    )
    assert differs is False


# ----------------------------------------------------------------------
# Cross-topology restore (round-3 VERDICT #6): a checkpoint written under
# one parallelism layout must restore into another whenever the LOGICAL
# state tree matches — orbax reshards to the target's shardings.  Layouts
# that genuinely differ (stacked PP params) stay descriptive errors
# (covered above).
# ----------------------------------------------------------------------
def _lm_cfg(tmp_path, train_iters=2, **train_extra):
    return {
        "dataset": {
            "name": "synthetic_text",
            "root": "/unused",
            "n_classes": 64,
            "seq_len": 32,
            "n_samples": 64,
        },
        "training": {
            "optimizer": {
                "name": "SGD", "lr": 0.01, "weight_decay": 1.0e-4, "momentum": 0.9,
            },
            "lr_schedule": {"name": "multi_step", "milestones": [100], "gamma": 0.1},
            "train_iters": train_iters,
            "print_interval": 10,
            "val_interval": 100,
            "batch_size": 16,
            "num_workers": 1,
            "sync_bn": False,
            "checkpoint": {"dir": str(tmp_path / "ckpt"), "interval": 2},
            **train_extra,
        },
        "validation": {"batch_size": 16, "num_workers": 1},
        "model": {"name": "TransformerLM", "embed_dim": 32, "depth": 2,
                  "num_heads": 4},
    }


class _SetupOnlyRunner(Runner):
    """Runs worker setup (incl. restore); skips the training loop."""

    def _train_loop(self, iter_generator, train_cfg):
        self.captured_iter = self.iter


def _setup_only(cfg):
    runner = _SetupOnlyRunner(
        num_nodes=1, rank=0, seed=3, dist_url="tcp://127.0.0.1:9902",
        dist_backend="tpu", multiprocessing=False, logger_queue=None,
        global_cfg=cfg, tb_writer_constructor=lambda: None,
    )
    runner()
    return runner


from tree_utils import flat_tree as _flat  # single source of the key format


@pytest.mark.parametrize(
    "target_extra",
    [{"tensor_parallelism": 2}, {"zero": 1}, {"zero": 2}, {"zero": 3}],
    ids=["tp2", "zero1", "zero2", "zero3"],
)
def test_dp_checkpoint_restores_into_resharded_run(tmp_path, target_extra):
    """A plain-DP LM checkpoint restores into TP=2 / ZeRO-1 / ZeRO-2 runs:
    identical values, target-topology shardings (orbax resharding)."""
    writer = _run(_lm_cfg(tmp_path, train_iters=2))
    want_params = _flat(writer.state.params)
    want_mu = _flat(writer.state.opt_state.momentum)

    reader = _setup_only(_lm_cfg(tmp_path, train_iters=2, **target_extra))
    assert reader.captured_iter == 2  # resumed past the saved step
    got_params = _flat(reader.state.params)
    got_mu = _flat(reader.state.opt_state.momentum)
    assert set(got_params) == set(want_params)
    for name in want_params:
        np.testing.assert_array_equal(got_params[name], want_params[name], err_msg=name)
    for name in want_mu:
        np.testing.assert_array_equal(got_mu[name], want_mu[name], err_msg=name)

    # the restored state is in the TARGET topology's layout, not the writer's
    from conftest import uses_mesh_axis

    flat_live = _flat(reader.state.params, materialize=False)
    if "tensor_parallelism" in target_extra:
        assert uses_mesh_axis(
            flat_live["block0/attn/qkv/kernel"].sharding, "model"
        )
    else:
        flat_mu_live = _flat(reader.state.opt_state.momentum, materialize=False)
        assert uses_mesh_axis(
            flat_mu_live["block0/attn/qkv/kernel"].sharding, "data"
        )
    # and the compiled step accepts it (one extra iteration runs cleanly)
    cont = _run(_lm_cfg(tmp_path, train_iters=3, **target_extra))
    assert int(cont.state.step) == 3


# ----------------------------------------------------------------------
# Async overlapped checkpointing (ISSUE 5): the save step blocks only for
# the host snapshot; the write happens on a background thread with errors
# deferred to the next synchronization point, the sidecar strictly after
# the commit, and a crash mid-write indistinguishable from the existing
# truncated-checkpoint fallback case.
# ----------------------------------------------------------------------
def _tiny_state(fill):
    from pytorch_distributed_training_tpu.engine import TrainState
    from pytorch_distributed_training_tpu.optimizers import SGD
    from pytorch_distributed_training_tpu.parallel import replicated_sharding
    from pytorch_distributed_training_tpu.parallel.mesh import make_mesh

    opt = SGD(lr=0.1, momentum=0.9)
    params = {"w": jnp.full((8, 4), float(fill)), "b": jnp.full((4,), float(fill))}
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    return jax.device_put(state, replicated_sharding(make_mesh()))


def test_async_save_commits_and_roundtrips(tmp_path):
    """Async saves commit durably (values round-trip exactly), write the
    sidecar only after the commit, and prune sidecars exactly on the
    garbage-collection events that evict their steps."""
    import os

    ck = Checkpointer(str(tmp_path / "c"), interval=1, max_to_keep=2,
                      async_save=True, max_inflight=1)
    assert ck.async_save and ck.max_inflight == 1
    for it in range(4):
        ck.save(it, _tiny_state(it), extras={"epoch": it})
    ck.wait()  # commit barrier: every enqueued write is durable past here
    assert ck.all_steps() == [2, 3]  # max_to_keep=2 evicted steps 0 and 1
    # evicted steps lost their sidecars on the GC event; kept steps didn't
    sidecars = sorted(
        f for f in os.listdir(str(tmp_path / "c")) if f.startswith("pipeline_")
    )
    assert sidecars == ["pipeline_2.json", "pipeline_3.json"]
    assert ck.read_extras(3) == {"epoch": 3}

    restored, next_iter = ck.restore_latest(_tiny_state(0.0))
    ck.close()
    assert next_iter == 4
    np.testing.assert_array_equal(
        np.asarray(restored.params["w"]), np.full((8, 4), 3.0)
    )


def test_async_config_surface(tmp_path):
    """training.checkpoint.async / max_inflight parse additively; a
    nonsensical inflight bound is rejected at construction."""
    ck = Checkpointer.from_config({
        "checkpoint": {"dir": str(tmp_path / "a"), "async": True,
                       "max_inflight": 2},
    })
    assert ck.async_save and ck.max_inflight == 2
    ck.close()
    ck2 = Checkpointer.from_config({"checkpoint": {"dir": str(tmp_path / "b")}})
    assert not ck2.async_save  # default off: sync semantics unchanged
    ck2.close()
    with pytest.raises(ValueError, match="max_inflight"):
        Checkpointer(str(tmp_path / "x"), max_inflight=0)


def test_async_write_failure_surfaces_at_next_sync_point(tmp_path):
    """A background write that exhausts its retry budget must not vanish:
    the NEXT save (a synchronization point) raises AsyncCheckpointError
    chaining the storage error, and the failed step is never visible to
    restore."""
    from pytorch_distributed_training_tpu.engine import fault
    from pytorch_distributed_training_tpu.engine.checkpoint import (
        AsyncCheckpointError,
    )
    from pytorch_distributed_training_tpu.engine.fault import FaultInjectionError
    from pytorch_distributed_training_tpu.utils.retry import Retry

    fault.reset_counters()
    ck = Checkpointer(str(tmp_path / "c"), interval=1, async_save=True,
                      retry=Retry(attempts=1))
    try:
        ck.save(0, _tiny_state(0.0))
        ck.wait()  # step 0 durably committed before the fault window opens
        fault.install("ckpt_async_fail@0:99")
        ck.save(1, _tiny_state(1.0))  # background write fails, no budget left
        with pytest.raises(AsyncCheckpointError, match="step 1") as exc_info:
            ck.save(2, _tiny_state(2.0))
        assert isinstance(exc_info.value.__cause__, FaultInjectionError)
        assert fault.counters().get("injected_ckpt_async_write_failures") == 1
        # recovery flavor: drain without raising drops the failure (logged)
        ck.drain(raise_errors=False)
        assert ck.all_steps() == [0]  # the failed write never committed
        restored, next_iter = ck.restore_latest(_tiny_state(9.0))
        assert next_iter == 1  # previous committed step restores
        np.testing.assert_array_equal(
            np.asarray(restored.params["w"]), np.full((8, 4), 0.0)
        )
    finally:
        ck.close()
        fault.install(None)
        fault.reset_counters()


def test_crash_during_async_write_falls_back_like_truncated_step(tmp_path):
    """Kill-during-async-write (extends the corrupt-fallback battery): the
    interrupted write leaves only an UNCOMMITTED tmp step dir — orbax's
    atomic-rename commit never ran — so restore_latest must treat it like
    the truncated-checkpoint case and hand back the previous committed
    step, without even burning a fallback."""
    import os

    from pytorch_distributed_training_tpu.engine import fault
    from pytorch_distributed_training_tpu.utils.retry import Retry

    fault.reset_counters()
    ck = Checkpointer(str(tmp_path / "c"), interval=1, async_save=True,
                      retry=Retry(attempts=1))
    try:
        ck.save(1, _tiny_state(1.0))
        ck.wait()
        fault.install("ckpt_async_fail@0:99")
        ck.save(3, _tiny_state(3.0))  # dies on the writer thread
        ck.drain(raise_errors=False)
        # the crash artifact a mid-write kill leaves on disk: a partial,
        # uncommitted tmp directory for the step
        tmp_dir = os.path.join(ck.directory, "3.orbax-checkpoint-tmp-123456")
        os.makedirs(tmp_dir)
        with open(os.path.join(tmp_dir, "partial"), "w") as fp:
            fp.write("truncated")

        assert ck.all_steps() == [1]  # the tmp dir is invisible
        restored, next_iter = ck.restore_latest(_tiny_state(0.0))
        assert next_iter == 2
        np.testing.assert_array_equal(
            np.asarray(restored.params["w"]), np.full((8, 4), 1.0)
        )
        # no fallback was needed: the uncommitted step was never a candidate
        assert "ckpt_fallbacks" not in fault.counters()
    finally:
        ck.close()
        fault.install(None)
        fault.reset_counters()


@pytest.mark.slow
def test_sidecar_missing_for_committed_step_tolerated(tmp_path, one_device_graft):
    """Satellite regression (sidecar/commit ordering): a checkpoint whose
    sidecar is gone — the old ordering could crash between manager.save and
    the sidecar write; GC pruning can also race a crash — must still
    resume, deriving the pipeline position from the step counter."""
    import os

    _run(_cfg(tmp_path, train_iters=2))  # interval=2 -> save at step 1
    sidecar = os.path.join(str(tmp_path / "ckpt"), "pipeline_1.json")
    assert os.path.exists(sidecar)
    os.remove(sidecar)  # the crash-at-the-boundary artifact

    ck = Checkpointer(str(tmp_path / "ckpt"))
    assert ck.read_extras(1) is None  # absence-tolerant, no raise
    ck.close()

    resumed = _run(_cfg(tmp_path, train_iters=4))
    assert resumed.iter == 4  # resumed from step 1 without the sidecar


@pytest.mark.slow
def test_resume_bit_exact_async_vs_straight_run(tmp_path, one_device_graft):
    """The async-save pipeline end to end through the Runner: 4 iters
    straight == 2 iters + async checkpoint + resume 2 more, bit-exact —
    the snapshot/overlapped write must save exactly the state the sync
    path would have."""
    straight = _run(_cfg(tmp_path / "a", ckpt=False, train_iters=4))

    cfg_b = _cfg(tmp_path / "b", train_iters=2)
    cfg_b["training"]["checkpoint"]["async"] = True
    _run(cfg_b)
    cfg_b2 = _cfg(tmp_path / "b", train_iters=4)
    cfg_b2["training"]["checkpoint"]["async"] = True
    resumed = _run(cfg_b2)

    a = jax.tree.map(np.asarray, straight.state.params)
    b = jax.tree.map(np.asarray, resumed.state.params)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.slow
def test_restore_at_different_device_count(tmp_path):
    """batch_division: world — a checkpoint written on the 8-device mesh
    restores in a 4-device process (orbax resharding across world sizes),
    bit-identical params."""
    import subprocess
    import sys
    import json as _json
    import os

    cfg = _lm_cfg(tmp_path, train_iters=2, batch_division="world")
    writer = _run(cfg)
    want = _flat(writer.state.params)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(_json.dumps(cfg))
    out_path = tmp_path / "restored.npz"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env.update(
        RW_DEVICES="4", RW_CFG=str(cfg_path), RW_OUT=str(out_path),
        PYTHONPATH=root + os.pathsep + env.get("PYTHONPATH", ""),
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tests", "restore_worker.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(str(out_path) + ".json") as fp:
        meta = _json.load(fp)
    assert meta["device_count"] == 4
    assert meta["restored_iter"] == 2
    got = dict(np.load(str(out_path)))
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
