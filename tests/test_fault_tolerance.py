"""Fault-tolerance layer: every recovery path proven by deterministic injection.

Strategy (ISSUE: robustness tentpole): nothing here waits for production to
reproduce a failure — each path is driven by the engine/fault.py injection
registry (or a direct kill/stall) and the test asserts the RECOVERY, not
just the detection:

  - anomaly-step guard: a NaN batch leaves params bitwise unchanged; a
    grad-norm spike is gated by the trailing-median threshold; N
    consecutive anomalies roll the Runner back to the last checkpoint and
    the run still completes;
  - retrying checkpoint I/O: injected save failures are absorbed by the
    Retry policy and the final params bit-match an uninjected run;
  - worker respawn: a SIGKILLed pool worker is replaced and the epoch's
    batch sequence is bit-identical to an unkilled run;
  - serving degradation: submit-after-close fails fast, over-deadline
    requests resolve with TimeoutError while in-deadline ones complete,
    and the backlog bound sheds with OverloadedError;
  - watchdog: a stalled step fires exactly once, and never during warmup;
  - preemption: the latched signal set parses from YAML values, and the
    guard degrades to an inert flag off the main thread.
"""
import logging
import os
import signal
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_training_tpu.engine import Runner, fault
from pytorch_distributed_training_tpu.engine.fault import (
    FaultInjectionError,
    FaultInjector,
)
from pytorch_distributed_training_tpu.utils.retry import Retry


@pytest.fixture(autouse=True)
def _fault_hygiene():
    """Process-global injector/counters must not leak between tests."""
    fault.install(None)
    fault.reset_counters()
    yield
    fault.install(None)
    fault.reset_counters()


@pytest.fixture
def one_device_mesh(monkeypatch):
    """A ONE-device mesh for the step/runner tests: the guard/rollback/retry
    logic under test is device-count independent, so these tests pin it on
    one device and stay quick."""
    from pytorch_distributed_training_tpu.engine import paths
    from pytorch_distributed_training_tpu.parallel import make_mesh

    mesh = make_mesh(jax.devices()[:1])
    monkeypatch.setattr(paths, "make_mesh", lambda *a, **kw: mesh)
    return mesh


# ======================================================================
# utils/retry.py
# ======================================================================
def test_retry_backoff_sequence():
    slept = []
    policy = Retry(
        attempts=4, backoff=0.1, max_backoff=0.3, jitter=0.0,
        sleep=slept.append,
    )
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise OSError("transient")
        return "ok"

    retries = []
    assert policy.call(flaky, on_retry=lambda a, e, d: retries.append(a)) == "ok"
    assert calls["n"] == 4
    # exponential 0.1, 0.2 then capped at max_backoff (jitter 0 -> exact)
    assert slept == pytest.approx([0.1, 0.2, 0.3])
    assert retries == [0, 1, 2]


def test_retry_allowlist_and_exhaustion():
    policy = Retry(attempts=3, backoff=0.0, jitter=0.0, sleep=lambda d: None)

    # non-allowlisted exception: no retry at all
    calls = {"n": 0}

    def bug():
        calls["n"] += 1
        raise ValueError("programming error")

    with pytest.raises(ValueError):
        policy.call(bug)
    assert calls["n"] == 1

    # allowlisted but persistent: bounded attempts, original re-raised
    calls["n"] = 0

    def broken_disk():
        calls["n"] += 1
        raise OSError("still down")

    with pytest.raises(OSError, match="still down"):
        policy.call(broken_disk)
    assert calls["n"] == 3


def test_retry_non_retryable_classification():
    """Programming errors raise immediately even when the allowlist would
    catch them: ValueError/TypeError are deterministic — retrying burns
    the attempt budget and delays the traceback."""
    policy = Retry(
        attempts=3, backoff=0.0, jitter=0.0, sleep=lambda d: None,
        retry_on=(Exception,),  # broad allowlist that COVERS ValueError
    )
    calls = {"n": 0}

    def bad_argument():
        calls["n"] += 1
        raise ValueError("bad argument")

    with pytest.raises(ValueError, match="bad argument"):
        policy.call(bad_argument)
    assert calls["n"] == 1  # no retry: classified non-retryable

    calls["n"] = 0

    def wrong_type():
        calls["n"] += 1
        raise TypeError("wrong type")

    with pytest.raises(TypeError):
        policy.call(wrong_type)
    assert calls["n"] == 1

    # the denylist is a parameter: opting out restores plain allowlisting
    permissive = Retry(
        attempts=3, backoff=0.0, jitter=0.0, sleep=lambda d: None,
        retry_on=(ValueError,), non_retryable=(),
    )
    calls["n"] = 0
    with pytest.raises(ValueError):
        permissive.call(bad_argument)
    assert calls["n"] == 3  # retried to exhaustion


def test_retry_total_timeout_bounds_stacked_backoff():
    """total_timeout_s: stacked backoff must not outlive an external grace
    window (spot SIGTERM->SIGKILL gap, elastic emergency save).  A retry
    whose NEXT backoff sleep would cross the deadline re-raises the last
    failure immediately instead of sleeping past the budget — fake clock
    and sleep pin the arithmetic without wall time."""
    from pytorch_distributed_training_tpu.telemetry import (
        get_registry,
        reset_registry,
    )

    now = {"t": 0.0}
    slept = []

    def fake_sleep(d):
        slept.append(d)
        now["t"] += d

    reset_registry()
    policy = Retry(
        attempts=5, backoff=1.0, max_backoff=8.0, jitter=0.0,
        total_timeout_s=2.0, sleep=fake_sleep, clock=lambda: now["t"],
    )
    calls = {"n": 0}

    def broken_disk():
        calls["n"] += 1
        raise OSError("still down")

    with pytest.raises(OSError, match="still down"):
        policy.call(broken_disk)
    # attempt 0 fails -> backoff 1.0 fits (t=1.0); attempt 1 fails ->
    # backoff 2.0 would land at t=3.0 > 2.0 -> abandon, re-raise
    assert calls["n"] == 2
    assert slept == [1.0]
    reg = get_registry()
    assert reg.counter("retry_deadline_exceeded").value == 1
    assert reg.counter("retry_attempts").value == 1

    with pytest.raises(ValueError, match="total_timeout_s"):
        Retry(total_timeout_s=0.0)


# ======================================================================
# engine/fault.py — spec grammar and injector semantics
# ======================================================================
def test_fault_spec_parsing_and_one_shot():
    inj = FaultInjector(
        "nan_batch@2; kill_worker@4:1; stall_step@8:0.5; ckpt_fail@1:2"
    )
    assert inj.active
    assert inj.take("nan_batch", 1) is None
    assert inj.take("nan_batch", 2) == 1.0
    assert inj.take("nan_batch", 2) is None  # one-shot: consumed
    assert inj.take("kill_worker", 4) == 1.0
    assert inj.take("stall_step", 8) == 0.5
    # ckpt_fail@1:2 -> attempt ordinals 1 and 2 fail, 0 and 3 succeed
    inj.check_fail_point("ckpt_save")  # ordinal 0
    with pytest.raises(FaultInjectionError):
        inj.check_fail_point("ckpt_save")  # ordinal 1
    with pytest.raises(FaultInjectionError):
        inj.check_fail_point("ckpt_save")  # ordinal 2
    inj.check_fail_point("ckpt_save")  # ordinal 3
    # the restore point is independent of the save point
    inj.check_fail_point("ckpt_restore")
    assert not FaultInjector("").active
    # the async-write point (background writer thread) is its own ordinal
    # space too: ckpt_async_fail windows never consume ckpt_save attempts
    inj2 = FaultInjector("ckpt_async_fail@0:1")
    inj2.check_fail_point("ckpt_save")  # untouched by the async window
    with pytest.raises(FaultInjectionError):
        inj2.check_fail_point("ckpt_async_write")
    inj2.check_fail_point("ckpt_async_write")  # window exhausted


@pytest.mark.parametrize(
    "spec",
    [
        "nan_batch",  # missing @step
        "nan_batch@x",  # non-integer step
        "nan_batch@-1",  # negative step
        "nan_batch@3:1",  # nan_batch takes no arg
        "ckpt_fail@0:0",  # failure count must be >= 1
        "bogus@1",  # unknown kind
    ],
)
def test_fault_spec_errors(spec):
    with pytest.raises(ValueError):
        FaultInjector(spec)


def test_unknown_fault_kind_names_the_valid_kinds():
    """A typo'd kind must fail at SPEC-PARSE time with the full menu, not
    deep into the run when the fault would have fired."""
    with pytest.raises(ValueError) as ei:
        FaultInjector("kil_peer@3")
    msg = str(ei.value)
    for kind in ("nan_batch", "kill_worker", "stall_step", "kill_peer",
                 "sdc_flip", "ckpt_corrupt",
                 "serve_nan", "serve_raise", "serve_device_lost", "serve_hang",
                 "replica_down", "replica_hang",
                 "kv_transfer_stall", "kv_transfer_corrupt",
                 "prefill_replica_down",
                 "ckpt_fail", "restore_fail", "ckpt_async_fail"):
        assert kind in msg, f"{kind!r} missing from the error menu: {msg}"


def test_kill_peer_spec_parses_with_optional_rank():
    inj = FaultInjector("kill_peer@5")
    assert inj.take("kill_peer", 5) == -1.0  # default: any rank
    inj = FaultInjector("kill_peer@7:1")
    assert inj.take("kill_peer", 7) == 1.0
    assert inj.take("kill_peer", 7) is None  # one-shot


def test_fault_spec_comma_separator_and_duplicate_rejection():
    """The soak generator joins entries with ';' but hand-written specs
    (env vars, YAML) often use ',' — both parse, mixed freely.  The same
    kind@step twice is a spec bug (one-shot semantics make the second
    entry dead) and must fail at parse time."""
    inj = FaultInjector("nan_batch@2, kill_worker@4:1 ; stall_step@8:0.5")
    assert inj.take("nan_batch", 2) == 1.0
    assert inj.take("kill_worker", 4) == 1.0
    assert inj.take("stall_step", 8) == 0.5
    with pytest.raises(ValueError, match="duplicate"):
        FaultInjector("nan_batch@2;nan_batch@2")
    # same kind at DIFFERENT steps is the normal burst idiom
    assert FaultInjector("nan_batch@2;nan_batch@3").active


def test_injector_fired_and_pending_accounting():
    """fired()/pending() partition the spec exactly — the soak engine's
    accounting oracle (every armed fault fired, none left pending) reads
    these, so their balance is pinned here."""
    inj = FaultInjector("nan_batch@2;stall_step@5:0.1;ckpt_fail@0:2")
    assert inj.fired() == {}
    # fail-point entries account under their POINT name (ckpt_save), by
    # the attempt ordinals still ahead of the process
    assert inj.pending() == {
        "nan_batch": [2], "stall_step": [5], "ckpt_save": [0, 1],
    }
    inj.take("nan_batch", 2)
    with pytest.raises(FaultInjectionError):
        inj.check_fail_point("ckpt_save")  # ordinal 0
    assert inj.fired() == {"nan_batch": 1, "ckpt_save": 1}
    assert inj.pending() == {"stall_step": [5], "ckpt_save": [1]}
    with pytest.raises(FaultInjectionError):
        inj.check_fail_point("ckpt_save")  # ordinal 1
    inj.take("stall_step", 5)
    assert inj.pending() == {}
    assert inj.fired() == {"nan_batch": 1, "stall_step": 1, "ckpt_save": 2}
    # per-kind trigger counters mirror into the process registry
    c = fault.counters()
    assert c.get("fault_fired_nan_batch") == 1
    assert c.get("fault_fired_stall_step") == 1


def test_fault_spec_config_key_validated_at_parse_time():
    """A bad training.fault_tolerance.fault_spec fails when the CONFIG is
    parsed (topology.parse_fault_tolerance constructs an injector eagerly),
    not minutes later when the injector is first consulted."""
    import types

    from pytorch_distributed_training_tpu.engine.topology import (
        parse_fault_tolerance,
    )

    with pytest.raises(ValueError, match="unknown kind"):
        parse_fault_tolerance(
            types.SimpleNamespace(),
            {"fault_tolerance": {"fault_spec": "bogus@1"}},
        )
    r = types.SimpleNamespace()
    parse_fault_tolerance(
        r, {"fault_tolerance": {"fault_spec": "kill_peer@5; nan_batch@2"}}
    )
    assert r.fault_spec == "kill_peer@5; nan_batch@2"


# ======================================================================
# engine/steps.py — the anomaly guard inside the compiled step
# ======================================================================
def _tiny_guarded_step(anomaly_factor, mesh):
    from pytorch_distributed_training_tpu.engine import (
        build_train_step,
        init_train_state,
    )
    from pytorch_distributed_training_tpu.models.vit import ViT
    from pytorch_distributed_training_tpu.optimizers import SGD
    from pytorch_distributed_training_tpu.parallel import (
        batch_sharding,
        replicated_sharding,
    )
    model = ViT(num_classes=8, patch_size=8, embed_dim=32, depth=1, num_heads=2)
    opt = SGD(lr=0.1, momentum=0.9)

    def fresh_state():
        state = init_train_state(
            model, opt, jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3))
        )
        return jax.device_put(state, replicated_sharding(mesh))

    step = build_train_step(
        model, opt, lambda i: 0.1, mesh, sync_bn=False,
        anomaly_factor=anomaly_factor,
    )
    rng = np.random.default_rng(0)
    img = jax.device_put(
        rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
        batch_sharding(mesh, 4),
    )
    label = jax.device_put(
        rng.integers(0, 8, (16,)).astype(np.int32), batch_sharding(mesh, 1)
    )
    return fresh_state, step, img, label


@pytest.mark.slow
def test_nan_step_skipped_params_bitwise_unchanged(one_device_mesh):
    """anomaly_factor=0.0 arms the non-finite-only check: a NaN batch must
    leave params, momentum and the step counter BITWISE unchanged — nothing
    anomalous leaves the compiled step."""
    fresh_state, step, img, label = _tiny_guarded_step(0.0, one_device_mesh)
    state = fresh_state()
    before_params = jax.tree.map(np.asarray, state.params)
    before_mu = jax.tree.map(np.asarray, state.opt_state.momentum)

    nan_img = jnp.full(img.shape, jnp.nan, img.dtype)
    nan_img = jax.device_put(nan_img, img.sharding)
    state2, loss, gnorm, applied = step(state, nan_img, label, 0.0)
    assert float(applied) == 0.0
    assert not np.isfinite(float(loss))
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, state2.params)),
        jax.tree.leaves(before_params),
    ):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, state2.opt_state.momentum)),
        jax.tree.leaves(before_mu),
    ):
        np.testing.assert_array_equal(a, b)
    assert int(state2.step) == 0  # the skipped update didn't count

    # the same compiled step APPLIES a clean batch (donated state: rebuild)
    state3, loss3, gnorm3, applied3 = step(fresh_state(), img, label, 0.0)
    assert float(applied3) == 1.0
    assert np.isfinite(float(loss3)) and np.isfinite(float(gnorm3))
    assert int(state3.step) == 1
    moved = jax.tree.leaves(jax.tree.map(np.asarray, state3.params))[0]
    assert not np.array_equal(moved, jax.tree.leaves(before_params)[0])


@pytest.mark.slow
def test_gnorm_spike_gated_by_trailing_reference(one_device_mesh):
    """grad_norm_factor > 0: the step is skipped iff the gradient norm
    exceeds factor x the host-fed reference; ref <= 0 means unarmed (the
    warmup steps before any history exists must always apply)."""
    fresh_state, step, img, label = _tiny_guarded_step(2.0, one_device_mesh)
    before = jax.tree.leaves(
        jax.tree.map(np.asarray, fresh_state().params)
    )[0]

    # unarmed reference: applied, and we learn the true gnorm
    _, _, gnorm, applied = step(fresh_state(), img, label, 0.0)
    g = float(gnorm)
    assert float(applied) == 1.0 and np.isfinite(g) and g > 0

    # reference far below the actual norm -> spike -> skipped, params frozen
    state2, _, _, applied2 = step(fresh_state(), img, label, g / 1000.0)
    assert float(applied2) == 0.0
    np.testing.assert_array_equal(
        jax.tree.leaves(jax.tree.map(np.asarray, state2.params))[0], before
    )

    # generous reference -> within threshold -> applied
    _, _, _, applied3 = step(fresh_state(), img, label, g * 1000.0)
    assert float(applied3) == 1.0


# ======================================================================
# Runner integration: injected faults end to end
# ======================================================================
def _ft_cfg(tmp_path, train_iters, fault_spec=None, ckpt=False, interval=2,
            anomaly=None, retry=None):
    cfg = {
        "dataset": {
            "name": "synthetic", "root": str(tmp_path), "n_classes": 4,
            "image_size": 16, "n_samples": 64,
        },
        "training": {
            "optimizer": {
                "name": "SGD", "lr": 0.01, "weight_decay": 1.0e-4,
                "momentum": 0.9,
            },
            "lr_schedule": {
                "name": "multi_step", "milestones": [100], "gamma": 0.1,
            },
            "train_iters": train_iters,
            "print_interval": 10,
            "val_interval": 100,
            "batch_size": 16,
            "num_workers": 0,
            "sync_bn": False,
        },
        "validation": {"batch_size": 16, "num_workers": 0},
        "model": {"name": "ResNet18"},
    }
    ft = {}
    if anomaly is not None:
        ft["anomaly"] = anomaly
    if fault_spec is not None:
        ft["fault_spec"] = fault_spec
    if ft:
        cfg["training"]["fault_tolerance"] = ft
    if ckpt:
        cfg["training"]["checkpoint"] = {
            "dir": str(tmp_path / "ckpt"), "interval": interval,
            "resume": True,
        }
        if retry is not None:
            cfg["training"]["checkpoint"]["retry"] = retry
    return cfg


def _run(cfg):
    runner = Runner(
        num_nodes=1, rank=0, seed=3, dist_url="tcp://127.0.0.1:9901",
        dist_backend="tpu", multiprocessing=False, logger_queue=None,
        global_cfg=cfg, tb_writer_constructor=lambda: None,
    )
    runner()
    return runner


@pytest.mark.slow
def test_runner_nan_injection_skips_and_continues(tmp_path, one_device_mesh):
    """One injected NaN batch: the step is skipped (counted), training
    continues to completion, and the final params are finite."""
    cfg = _ft_cfg(
        tmp_path, train_iters=3, fault_spec="nan_batch@1",
        anomaly={"enabled": True},
    )
    runner = _run(cfg)
    assert runner.iter == 3
    c = fault.counters()
    assert c.get("injected_nan_batches") == 1
    assert c.get("skipped_steps") == 1
    assert "rollbacks" not in c
    for leaf in jax.tree.leaves(jax.tree.map(np.asarray, runner.state.params)):
        assert np.isfinite(leaf).all()
    # two applied steps: the skipped one did not advance the optimizer
    assert int(runner.state.step) == 2


@pytest.mark.slow
def test_runner_consecutive_anomalies_rollback_and_resume(tmp_path, one_device_mesh):
    """max_consecutive NaN steps trip the rollback: the Runner restores the
    last checkpoint, rebuilds the input stream, and completes the run."""
    cfg = _ft_cfg(
        tmp_path, train_iters=6, ckpt=True, interval=2,
        fault_spec="nan_batch@2;nan_batch@3;nan_batch@4",
        anomaly={"enabled": True, "max_consecutive": 3},
    )
    runner = _run(cfg)
    assert runner.iter == 6
    c = fault.counters()
    assert c.get("injected_nan_batches") == 3
    assert c.get("skipped_steps") == 3
    assert c.get("rollbacks") == 1
    # applied steps: 0,1 before the burst, then 4,5 after the rollback
    # replay (the one-shot faults are consumed, so the replay runs clean)
    assert int(runner.state.step) == 4
    for leaf in jax.tree.leaves(jax.tree.map(np.asarray, runner.state.params)):
        assert np.isfinite(leaf).all()


@pytest.mark.slow
def test_rollback_flushes_async_writer_before_restore(tmp_path, one_device_mesh,
                                                      monkeypatch):
    """Async checkpointing composes with the anomaly-guard rollback: the
    Runner must flush (drain, errors dropped) the background writer BEFORE
    restore_latest touches the checkpoint dir — two actors must never race
    on it, and a failed periodic save must not abort the recovery.  The
    rollback scenario itself must still complete end to end with async
    saves on."""
    from pytorch_distributed_training_tpu.engine.checkpoint import Checkpointer

    calls = []
    orig_drain = Checkpointer.drain
    orig_restore = Checkpointer.restore_latest

    def spy_drain(self, *a, **kw):
        calls.append(("drain", kw.get("raise_errors", a[0] if a else True)))
        return orig_drain(self, *a, **kw)

    def spy_restore(self, *a, **kw):
        calls.append(("restore", None))
        return orig_restore(self, *a, **kw)

    monkeypatch.setattr(Checkpointer, "drain", spy_drain)
    monkeypatch.setattr(Checkpointer, "restore_latest", spy_restore)

    cfg = _ft_cfg(
        tmp_path, train_iters=6, ckpt=True, interval=2,
        fault_spec="nan_batch@2;nan_batch@3;nan_batch@4",
        anomaly={"enabled": True, "max_consecutive": 3},
    )
    cfg["training"]["checkpoint"]["async"] = True
    runner = _run(cfg)
    assert runner.iter == 6
    assert fault.counters().get("rollbacks") == 1
    assert int(runner.state.step) == 4  # 0,1 + replayed 4,5 (burst skipped)

    # the rollback's restore (the startup resume also calls restore_latest,
    # on the then-empty dir) must be guarded IMMEDIATELY by the
    # error-dropping flush flavor
    assert any(
        calls[i] == ("drain", False) and calls[i + 1] == ("restore", None)
        for i in range(len(calls) - 1)
    ), f"no drain(raise_errors=False) directly before restore_latest: {calls}"


@pytest.mark.slow
def test_runner_rollback_without_checkpoint_is_loud(tmp_path, one_device_mesh):
    """Anomaly burst with no checkpoint configured: a descriptive error,
    not a silent loop."""
    cfg = _ft_cfg(
        tmp_path, train_iters=6, ckpt=False,
        fault_spec="nan_batch@1;nan_batch@2;nan_batch@3",
        anomaly={"enabled": True, "max_consecutive": 3},
    )
    with pytest.raises(RuntimeError, match="no training.checkpoint"):
        _run(cfg)


@pytest.mark.slow
def test_ckpt_save_failures_retried_final_state_matches(tmp_path, one_device_mesh):
    """Injected checkpoint-save failures are absorbed by the retry policy:
    training completes and the final params BIT-match an uninjected run
    (stronger than the 1e-6 loss bound the issue asks for)."""
    clean = _run(_ft_cfg(tmp_path / "a", train_iters=4, ckpt=True))
    want = jax.tree.map(np.asarray, clean.state.params)

    fault.reset_counters()
    cfg = _ft_cfg(
        tmp_path / "b", train_iters=4, ckpt=True,
        fault_spec="ckpt_fail@0:2",
        retry={"attempts": 3, "backoff": 0.0, "jitter": 0.0},
    )
    injected = _run(cfg)
    c = fault.counters()
    assert c.get("injected_ckpt_save_failures") == 2
    assert c.get("ckpt_retries") == 2
    assert injected.checkpointer.retries == 2
    got = jax.tree.map(np.asarray, injected.state.params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    # the retried save is real: a fresh run resumes from it
    fault.install(None)
    resumed = _run(_ft_cfg(tmp_path / "b", train_iters=4, ckpt=True))
    assert resumed.iter == 4


# ======================================================================
# data/worker_pool.py — dead-worker respawn
# ======================================================================
@pytest.mark.chaos
def test_worker_respawn_preserves_batch_sequence(tmp_path):
    """SIGKILL the (only) decode worker mid-epoch: the pool must respawn it
    with the same shard assignment and the epoch's batch stream must be
    bit-identical to an unkilled run — nothing dropped, nothing duplicated."""
    from pytorch_distributed_training_tpu.data import (
        DataLoader,
        RandomSampler,
        get_dataset,
    )

    ds = get_dataset(
        "synthetic", str(tmp_path), "train", n_classes=4, image_size=8,
        n_samples=64,
    )

    def make_dl():
        return DataLoader(
            ds, batch_size=4, sampler=RandomSampler(len(ds), seed=11),
            num_workers=1, drop_last=True, worker_mode="process",
        )

    ref_dl = make_dl()
    ref = list(ref_dl)
    ref_dl.close()
    assert len(ref) == 16

    dl = make_dl()
    try:
        it = iter(dl)
        got = [next(it), next(it)]
        pool = dl._pool
        pool._poll_seconds = 0.05  # fast dead-worker detection for the test
        os.kill(pool._procs[0].pid, signal.SIGKILL)
        got.extend(it)
        assert pool.respawns >= 1
        assert fault.counters().get("worker_respawns", 0) >= 1
        assert len(got) == len(ref)
        for (gi, gl), (ri, rl) in zip(got, ref):
            np.testing.assert_array_equal(gl, rl)
            np.testing.assert_array_equal(gi, ri)
    finally:
        dl.close()


@pytest.mark.chaos
def test_pool_respawn_budget_exhausted_is_loud(tmp_path):
    """A worker crash past max_respawns must raise, not respawn forever."""
    from pytorch_distributed_training_tpu.data import (
        DataLoader,
        RandomSampler,
        get_dataset,
    )

    ds = get_dataset(
        "synthetic", str(tmp_path), "train", n_classes=4, image_size=8,
        n_samples=32,
    )
    dl = DataLoader(
        ds, batch_size=4, sampler=RandomSampler(len(ds), seed=1),
        num_workers=1, drop_last=True, worker_mode="process",
    )
    try:
        it = iter(dl)
        next(it)
        pool = dl._pool
        pool._poll_seconds = 0.05
        pool.max_respawns = 0
        os.kill(pool._procs[0].pid, signal.SIGKILL)
        with pytest.raises(RuntimeError, match="respawn budget"):
            list(it)
    finally:
        dl.close()


@pytest.mark.slow
@pytest.mark.chaos
def test_pool_close_escalates_wedged_worker(tmp_path):
    """close() must not hang on a wedged worker: a SIGSTOPped process never
    drains its sentinel, so the join times out and close escalates to
    terminate/kill (satellite: bounded close)."""
    from pytorch_distributed_training_tpu.data import (
        DataLoader,
        RandomSampler,
        get_dataset,
    )

    ds = get_dataset(
        "synthetic", str(tmp_path), "train", n_classes=4, image_size=8,
        n_samples=32,
    )
    dl = DataLoader(
        ds, batch_size=4, sampler=RandomSampler(len(ds), seed=1),
        num_workers=1, drop_last=True, worker_mode="process",
    )
    it = iter(dl)
    next(it)
    pool = dl._pool
    proc = pool._procs[0]
    os.kill(proc.pid, signal.SIGSTOP)  # wedged: alive but never progressing
    t0 = time.monotonic()
    dl.close()
    elapsed = time.monotonic() - t0
    assert not proc.is_alive()
    assert elapsed < 15.0  # bounded: join(2) + terminate/kill escalation


# ======================================================================
# serving/batcher.py — graceful degradation
# ======================================================================
def _echo_batcher(**kwargs):
    from pytorch_distributed_training_tpu.serving.batcher import DynamicBatcher

    return DynamicBatcher(
        run_batch=lambda reqs: [r.payload for r in reqs],
        max_batch_size=8, max_delay_ms=1.0, **kwargs,
    )


def test_batcher_submit_after_close_raises():
    b = _echo_batcher()
    assert b.submit("x").result(timeout=10) == "x"
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit("y")
    b.close()  # idempotent


@pytest.mark.chaos
def test_batcher_deadline_timeout_while_inflight_completes():
    """A request still queued past its deadline resolves with TimeoutError
    at collection time; requests inside their deadline complete normally."""
    from pytorch_distributed_training_tpu.serving.batcher import DynamicBatcher

    entered = threading.Event()
    release = threading.Event()

    def run_batch(reqs):
        entered.set()
        release.wait(timeout=30)
        return [r.payload for r in reqs]

    b = DynamicBatcher(run_batch=run_batch, max_batch_size=8, max_delay_ms=0.0)
    try:
        f1 = b.submit("first")
        assert entered.wait(timeout=10)  # flush thread is now blocked
        f2 = b.submit("doomed", deadline_ms=20.0)
        f3 = b.submit("patient")  # no deadline: waits forever
        time.sleep(0.08)  # let f2's deadline lapse while it sits queued
        release.set()
        assert f1.result(timeout=10) == "first"
        with pytest.raises(TimeoutError, match="deadline"):
            f2.result(timeout=10)
        assert f3.result(timeout=10) == "patient"
        assert b.timeouts == 1
    finally:
        release.set()
        b.close()


def test_batcher_load_shedding():
    """Beyond max_backlog, submit fails FAST with OverloadedError instead of
    growing an unbounded queue; queued requests still complete."""
    from pytorch_distributed_training_tpu.serving.batcher import (
        DynamicBatcher,
        OverloadedError,
    )

    entered = threading.Event()
    release = threading.Event()
    shed_events = []

    def run_batch(reqs):
        entered.set()
        release.wait(timeout=30)
        return [r.payload for r in reqs]

    b = DynamicBatcher(
        run_batch=run_batch, max_batch_size=8, max_delay_ms=0.0,
        max_backlog=1, on_shed=lambda: shed_events.append(1),
    )
    try:
        f1 = b.submit("a")
        assert entered.wait(timeout=10)  # "a" popped; the backlog is empty
        f2 = b.submit("b")  # fills the single backlog slot
        with pytest.raises(OverloadedError, match="backlog full"):
            b.submit("c")
        assert b.sheds == 1 and shed_events == [1]
        release.set()
        assert f1.result(timeout=10) == "a"
        assert f2.result(timeout=10) == "b"
    finally:
        release.set()
        b.close()


def test_serving_metrics_counters_in_snapshot():
    from pytorch_distributed_training_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.incr("timeouts")
    m.incr("timeouts")
    m.incr("sheds")
    snap = m.snapshot()
    assert snap["timeouts"] == 2
    assert snap["sheds"] == 1


# ======================================================================
# compound-failure hardening (chaos soak regressions — engine/chaos.py)
# ======================================================================
@pytest.mark.chaos
def test_emergency_save_bounded_when_async_write_wedged(tmp_path, monkeypatch):
    """Compound #1: peer loss with an async checkpoint write in flight.
    The emergency save's writer drain is bounded by
    ``emergency_drain_timeout_s`` — a write wedged in a dead filesystem op
    must not stall the peer-death escape hatch past the grace window.  The
    emergency dump still commits (its own subdir, rank-stamped meta) and
    the timeout is counted."""
    from pytorch_distributed_training_tpu.engine.checkpoint import Checkpointer

    ck = Checkpointer(
        str(tmp_path / "ckpt"), interval=1, async_save=True,
        emergency_drain_timeout_s=0.3,
    )
    monkeypatch.setattr(
        Checkpointer, "_write_async",
        lambda self, it, snapshot, extras: time.sleep(2.5),
    )
    state = {"params": np.arange(8, dtype=np.float32), "step": np.int64(4)}
    ck.save(0, state)  # enqueues the (wedged) background write
    t0 = time.monotonic()
    ck.save_emergency(4, state)
    wall = time.monotonic() - t0
    assert wall < 2.0, f"emergency save blocked {wall:.2f}s on the writer"
    assert fault.counters().get("emergency_drain_timeouts") == 1
    assert ck.latest_emergency() == 4
    emdir = tmp_path / "ckpt" / "emergency" / "4"
    assert any(p.name.startswith("meta_rank") for p in emdir.iterdir())
    ck.drain(raise_errors=False, timeout=5.0)  # let the wedge finish
    ck.close()


@pytest.mark.slow
@pytest.mark.chaos
def test_sdc_during_rollback_replay_restores_post_rollback_timeline(
    tmp_path, one_device_mesh
):
    """Compound #2: an SDC flip lands DURING the anomaly-rollback replay.
    The integrity sentinel must recover to the POST-rollback timeline (the
    Runner rebases the retained snapshot after every rollback) — without
    the rebase, the restore would resurrect pre-rollback state and the
    final params/step would diverge from the flip-free run."""
    def cfg_for(sub, spec):
        cfg = _ft_cfg(
            tmp_path / sub, train_iters=6, ckpt=True, interval=2,
            fault_spec=spec,
            anomaly={"enabled": True, "max_consecutive": 3},
        )
        cfg["training"]["integrity"] = {
            "enabled": True, "check_interval": 6, "replicas": 3,
            "max_consecutive": 2,
        }
        return cfg

    burst = "nan_batch@2;nan_batch@3;nan_batch@4"
    clean = _run(cfg_for("clean", burst))
    want = jax.tree.map(np.asarray, clean.state.params)
    assert fault.counters().get("rollbacks") == 1

    fault.reset_counters()
    # the flip fires at iter 5 — inside the replay that follows the
    # rollback at iter 4 — and the step-5 integrity check catches it
    runner = _run(cfg_for("flip", burst + ";sdc_flip@5:0"))
    c = fault.counters()
    assert c.get("rollbacks") == 1
    assert c.get("injected_sdc_flips") == 1
    assert c.get("integrity_transient_flips") == 1, (
        "the sentinel never healed the replay-window flip"
    )
    assert int(runner.state.step) == int(clean.state.step)
    got = jax.tree.map(np.asarray, runner.state.params)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
@pytest.mark.chaos
def test_watchdog_reenters_warmup_after_rollback(tmp_path, one_device_mesh):
    """Compound #4: the hung-step watchdog's trailing median survives a
    rollback ONLY by being discarded — post-restore replay steps run cold
    (recompiles) and judging them by the pre-fault median would turn the
    recovery into another false hang.  The Runner must reset() the
    watchdog on the rollback path; the reset re-enters warmup."""
    cfg = _ft_cfg(
        tmp_path, train_iters=6, ckpt=True, interval=2,
        fault_spec="nan_batch@2;nan_batch@3;nan_batch@4",
        anomaly={"enabled": True, "max_consecutive": 3},
    )
    cfg["training"]["fault_tolerance"]["watchdog"] = {
        "enabled": True, "factor": 4.0, "min_seconds": 0.5,
        "warmup": 3, "poll_seconds": 0.05,
    }
    runner = _run(cfg)
    assert fault.counters().get("rollbacks") == 1
    wd = runner._watchdog
    assert wd is not None
    assert wd.resets >= 1, "rollback did not reset the watchdog"
    assert wd.fires == 0, "replay was misjudged as a hang"


@pytest.mark.chaos
def test_watchdog_reset_reenters_warmup_semantics():
    """StepWatchdog.reset() drops the trailing window and the fired latch:
    the very next steps are warmup samples, unjudged however slow."""
    from pytorch_distributed_training_tpu.engine.watchdog import StepWatchdog

    fired = []
    with StepWatchdog(
        factor=2.0, min_seconds=0.05, window=8, warmup=2, poll_seconds=0.02,
        on_hang=lambda *a: fired.append(a),
    ) as wd:
        for i in range(2):
            wd.step_started(i)
            time.sleep(0.01)
            wd.step_finished()
        assert wd.trailing_median() is not None  # armed
        wd.reset()
        assert wd.resets == 1
        assert wd.trailing_median() is None  # history gone -> warmup
        wd.step_started(2)  # slow post-reset step: must NOT fire
        time.sleep(0.3)
        wd.step_finished()
        assert wd.fires == 0 and not fired


# ======================================================================
# engine/watchdog.py
# ======================================================================
@pytest.mark.chaos
def test_watchdog_fires_once_on_stalled_step():
    from pytorch_distributed_training_tpu.engine.watchdog import StepWatchdog

    fired = []
    with StepWatchdog(
        factor=2.0, min_seconds=0.15, window=8, warmup=2, poll_seconds=0.02,
        on_hang=lambda step, elapsed, limit: fired.append((step, elapsed, limit)),
    ) as wd:
        for i in range(2):  # warmup: two fast completed steps
            wd.step_started(i)
            time.sleep(0.01)
            wd.step_finished()
        assert wd.trailing_median() is not None
        wd.step_started(2)
        time.sleep(0.4)  # past max(min_seconds, factor * median)
        wd.step_finished()
        deadline = time.monotonic() + 5.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.01)
    assert wd.fires == 1  # once per step index, not once per poll
    step, elapsed, limit = fired[0]
    assert step == 2
    assert elapsed > limit >= 0.15


@pytest.mark.chaos
def test_watchdog_unarmed_during_warmup():
    """The first compile takes minutes of legitimate wall time: before
    ``warmup`` completed samples exist the watchdog must never fire."""
    from pytorch_distributed_training_tpu.engine.watchdog import StepWatchdog

    fired = []
    with StepWatchdog(
        factor=2.0, min_seconds=0.05, window=8, warmup=3, poll_seconds=0.02,
        on_hang=lambda *a: fired.append(a),
    ) as wd:
        wd.step_started(0)  # no completed samples yet
        time.sleep(0.3)
        wd.step_finished()
        assert wd.fires == 0 and not fired


# ======================================================================
# engine/preemption.py — configurable signal set + degradation path
# ======================================================================
def test_parse_signals_accepts_names_numbers_and_lists():
    from pytorch_distributed_training_tpu.engine.preemption import PreemptionGuard

    parse = PreemptionGuard.parse_signals
    assert parse("SIGTERM") == (signal.SIGTERM,)
    assert parse("term") == (signal.SIGTERM,)  # SIG prefix + case optional
    assert parse(("SIGTERM",)) == (signal.SIGTERM,)
    assert parse(["SIGUSR1", "sigusr2"]) == (signal.SIGUSR1, signal.SIGUSR2)
    assert parse(int(signal.SIGTERM)) == (signal.SIGTERM,)
    with pytest.raises(ValueError, match="unknown signal name"):
        parse("SIGBOGUS")
    with pytest.raises(ValueError, match="invalid signal number"):
        parse(10_000)
    with pytest.raises(ValueError, match="at least one"):
        parse([])


def test_preemption_guard_inert_off_main_thread():
    """Signal handlers are installable only from the main thread: entered
    anywhere else the guard must degrade to an inert, still-settable flag
    (documented in engine/preemption.py) — not crash the run."""
    from pytorch_distributed_training_tpu.engine.preemption import PreemptionGuard

    before = signal.getsignal(signal.SIGTERM)
    result = {}

    def run():
        guard = PreemptionGuard(logger=logging.getLogger("test"))
        with guard as g:
            result["installed"] = g._installed
            result["triggered_initial"] = g.triggered
            g.triggered = True  # the watchdog's checkpoint_and_exit path
            result["settable"] = g.triggered

    t = threading.Thread(target=run)
    t.start()
    t.join(timeout=10)
    assert result == {
        "installed": False, "triggered_initial": False, "settable": True,
    }
    assert signal.getsignal(signal.SIGTERM) is before  # untouched


@pytest.mark.slow
def test_runner_parses_preemption_signals_from_yaml(tmp_path, one_device_mesh):
    """training.checkpoint.preemption_signals reaches the installed guard."""
    cfg = _ft_cfg(tmp_path, train_iters=2, ckpt=True)
    cfg["training"]["checkpoint"]["preemption_signals"] = ["SIGTERM", "USR1"]
    runner = _run(cfg)
    assert runner._preempt is not None
    assert runner._preempt.signals == (signal.SIGTERM, signal.SIGUSR1)
