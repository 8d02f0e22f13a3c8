"""Serving fault-tolerance oracles (serving/resilience.py + scheduler).

The two load-bearing oracles mirror the ISSUE acceptance criteria:

  - **Replay parity**: a request interrupted mid-decode by an injected
    device loss and resumed via hot-restart produces a token stream
    bitwise identical to an uninterrupted run — greedy AND sampled — on
    CPU.  The per-row per-token-index ``fold_in`` sampling keys plus
    re-feeding the generated tokens through the SAME decode program make
    this exact, not approximate.
  - **Poison isolation**: with ``serve_raise``/``serve_nan`` injected
    into one slot, exactly that request's future fails (with a diagnosed
    ``PoisonedRequestError``) while every other in-flight request
    completes token-identical to a clean run and the pool's free-block
    accounting returns to empty.

Every fault-scenario driver additionally asserts the KV pool's
accounting invariants after EVERY tick (``PagedKVPool.check_invariants``)
— a recovery path that leaks a block or a refcount fails at the tick it
leaks, not as an eventual pool exhaustion.
"""
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.engine import fault
from pytorch_distributed_training_tpu.models.transformer_lm import TransformerLM
from pytorch_distributed_training_tpu.serving.resilience import (
    EngineRestartError,
    PoisonedRequestError,
)
from pytorch_distributed_training_tpu.serving.scheduler import ContinuousScheduler

VOCAB = 61


def small_lm(**kwargs):
    return TransformerLM(
        vocab_size=VOCAB, max_len=32, embed_dim=32, depth=2, num_heads=4, **kwargs
    )


@pytest.fixture(scope="module")
def lm_and_params():
    model = small_lm()
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def small_latent_lm():
    """The second LM family at a toy size: latent (MLA) rows in the pool,
    dropless experts (models/deepseek_v2.py)."""
    from pytorch_distributed_training_tpu.models import get_model

    return get_model(
        "DeepseekV2", num_classes=VOCAB, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2, num_attention_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
        max_position_embeddings=32,
    )


@pytest.fixture(scope="module", params=["kv_pair", "latent"])
def family_and_params(request, lm_and_params):
    """Both layouts of a pool row: the K/V pair of ``TransformerLM`` and the
    one latent leaf of the ``DeepseekV2`` family."""
    if request.param == "kv_pair":
        return lm_and_params
    model = small_latent_lm()
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _prompts(seed=3, lens=(2, 6, 4)):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, VOCAB, ln).astype(np.int32) for ln in lens]


def _make_sched(model, params, **kw):
    # prefix_cache off by default so ``blocks_in_use == 0`` is an exact
    # leak oracle (the cache legitimately retains prompt blocks after
    # retirement); the replay-parity tests turn it back on and compare
    # against a clean run's residual instead
    defaults = dict(
        slots=4, block_size=4, num_blocks=16, batch_buckets=[4],
        seq_buckets=[8], max_new_tokens=6, temperature=0.0, eos_id=None,
        prefix_cache=False, start=False,
    )
    defaults.update(kw)
    return ContinuousScheduler(model, params, **defaults)


def _drive(sched, futures, limit=200, check_pool=True):
    """Manual-tick driver; optionally asserts pool invariants per tick."""
    n = 0
    while any(not f.done() for f in futures):
        sched.tick()
        if check_pool:
            sched._kv.check_invariants()
        n += 1
        assert n < limit, "scheduler failed to drain"
    return n


def _run_under_spec(model, params, spec, prompts=None, **kw):
    fault.install(spec)
    try:
        sched = _make_sched(model, params, **kw)
        futs = [sched.submit(p) for p in prompts or _prompts()]
        _drive(sched, futs)
        return sched, futs
    finally:
        fault.install(None)


# --------------------------------------------------------------------- #
# acceptance oracle: replay parity


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_replay_parity_after_device_loss(lm_and_params, temperature):
    """Interrupted-by-device-loss == uninterrupted, bitwise, per request."""
    model, params = lm_and_params
    clean_sched, clean = _run_under_spec(
        model, params, None, temperature=temperature, prefix_cache=True
    )
    ref = [f.result()["tokens"] for f in clean]

    sched, futs = _run_under_spec(
        model, params, "serve_device_lost@3", temperature=temperature,
        prefix_cache=True,
    )
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result()["tokens"], ref[i])
    assert sched._supervisor.restarts() == 1
    snap = sched.metrics.snapshot()
    assert snap["engine_restarts"] == 1
    assert snap["replayed_tokens"] > 0
    assert snap.get("replay_parity_mismatch", 0) == 0
    # no leak beyond what a clean run's prefix cache legitimately retains
    assert sched._kv.blocks_in_use == clean_sched._kv.blocks_in_use


def test_replay_is_not_redelivered(lm_and_params):
    """on_token must not refire for tokens the client already holds."""
    model, params = lm_and_params
    streamed = []
    fault.install("serve_device_lost@3")
    try:
        sched = _make_sched(model, params)
        fut = sched.submit(_prompts()[1], on_token=streamed.append)
        _drive(sched, [fut])
    finally:
        fault.install(None)
    assert sched._supervisor.restarts() == 1
    # every token exactly once, in order, despite the mid-stream replay
    assert streamed == fut.result()["tokens"].tolist()


# --------------------------------------------------------------------- #
# acceptance oracle: poison isolation


def test_poison_isolation_decode_raise(lm_and_params):
    """serve_raise: exactly one future fails (diagnosed, cause chained),
    the rest are token-identical to a clean run, pool fully recycled."""
    model, params = lm_and_params
    _, clean = _run_under_spec(model, params, None, prefix_cache=False)
    ref = [f.result()["tokens"] for f in clean]

    sched, futs = _run_under_spec(
        model, params, "serve_raise@2:1", prefix_cache=False
    )
    errs = [i for i, f in enumerate(futs) if f.exception() is not None]
    assert errs == [1]
    exc = futs[1].exception()
    assert isinstance(exc, PoisonedRequestError)
    assert "slot 1" in str(exc) and "tick 2" in str(exc)
    assert isinstance(exc.__cause__, fault.FaultInjectionError)
    for i in (0, 2):
        np.testing.assert_array_equal(futs[i].result()["tokens"], ref[i])
    assert sched._supervisor.restarts() == 0  # isolated, never restarted
    snap = sched.metrics.snapshot()
    assert snap["requests_poisoned"] == 1
    assert snap["poison_probes"] >= 2  # reproduce + bisect + confirm
    assert sched._kv.blocks_in_use == 0


def test_poison_isolation_nan_output_guard(family_and_params):
    """serve_nan: the on-device isfinite guard evicts the NaN emitter
    with NO Python exception; other rows stay bit-exact.  Holds for a
    K/V-pair pool and for a latent one: the row poisoned is read only by
    its owner."""
    model, params = family_and_params
    _, clean = _run_under_spec(model, params, None, prefix_cache=False)
    ref = [f.result()["tokens"] for f in clean]

    sched, futs = _run_under_spec(
        model, params, "serve_nan@2:0", prefix_cache=False
    )
    errs = [i for i, f in enumerate(futs) if f.exception() is not None]
    assert errs == [0]
    exc = futs[0].exception()
    assert isinstance(exc, PoisonedRequestError)
    assert "non-finite" in str(exc)
    assert exc.__cause__ is None  # guard path: nothing ever raised
    for i in (1, 2):
        np.testing.assert_array_equal(futs[i].result()["tokens"], ref[i])
    assert sched.metrics.snapshot()["requests_poisoned"] == 1
    assert sched._kv.blocks_in_use == 0


def test_poisoned_blocks_recycle_cleanly(family_and_params):
    """A NaN-poisoned request's freed blocks must be reusable: requests
    admitted AFTER the eviction decode on recycled blocks bit-exactly."""
    model, params = family_and_params
    model_ref, clean = _run_under_spec(model, params, None, prefix_cache=False)
    ref = [f.result()["tokens"] for f in clean]

    fault.install("serve_nan@2:0")
    try:
        # pool of 6 blocks: three 2-block requests fill it, so the late
        # request can only admit on the evicted request's recycled blocks
        sched = _make_sched(
            model, params, num_blocks=6, block_size=4, max_new_tokens=6,
            seq_buckets=[8], prefix_cache=False,
        )
        prompts = _prompts()
        futs = [sched.submit(p) for p in prompts]
        late = sched.submit(prompts[0])  # waits for blocks, then recycles
        _drive(sched, futs + [late])
    finally:
        fault.install(None)
    assert isinstance(futs[0].exception(), PoisonedRequestError)
    # the late request reuses the poisoned request's NaN-stained blocks
    # and still reproduces the clean tokens for the same prompt
    np.testing.assert_array_equal(late.result()["tokens"], ref[0])
    assert sched._kv.blocks_in_use == 0


def test_bisect_disabled_escalates_to_restart(lm_and_params):
    """poison_bisect=false with several suspects: the raise cannot be
    attributed, so each occurrence burns a restart — the documented cost
    of disabling isolation is that a PERSISTENT poison exhausts the
    budget and fails the world with the chained cause."""
    model, params = lm_and_params
    sched, futs = _run_under_spec(
        model, params, "serve_raise@2:1",
        resilience={"poison_bisect": False, "max_restarts": 1},
    )
    assert sched._supervisor.restarts() == 1
    assert sched._supervisor.exhausted()
    for f in futs:
        exc = f.exception()
        assert isinstance(exc, EngineRestartError)
        assert isinstance(exc.__cause__, fault.FaultInjectionError)
    # never probed: bisect was disabled
    assert sched.metrics.snapshot().get("poison_probes", 0) == 0
    assert sched._kv.blocks_in_use == 0


def test_single_suspect_evicted_without_probing(lm_and_params):
    """With exactly one active request there is nothing to bisect: it is
    evicted directly even when poison_bisect is disabled."""
    model, params = lm_and_params
    fault.install("serve_raise@2:0")
    try:
        sched = _make_sched(
            model, params, resilience={"poison_bisect": False}
        )
        fut = sched.submit(_prompts()[0])
        _drive(sched, [fut])
    finally:
        fault.install(None)
    assert isinstance(fut.exception(), PoisonedRequestError)
    assert sched._supervisor.restarts() == 0
    assert sched.metrics.snapshot().get("poison_probes", 0) == 0
    assert sched._kv.blocks_in_use == 0


# --------------------------------------------------------------------- #
# restart budget


def test_restart_budget_exhaustion_chains_cause(lm_and_params):
    model, params = lm_and_params
    sched, futs = _run_under_spec(
        model, params, "serve_device_lost@2;serve_device_lost@4",
        resilience={"max_restarts": 1},
    )
    for f in futs:
        exc = f.exception()
        assert isinstance(exc, EngineRestartError)
        assert isinstance(exc.__cause__, fault.DeviceLostError)
    assert sched._supervisor.exhausted()
    snap = sched.metrics.snapshot()
    assert snap["engine_restarts"] == 1
    assert snap["restart_budget_exhausted"] == 1
    assert snap["failed_inflight"] == 3
    assert sched._kv.blocks_in_use == 0  # _fail_inflight released them
    health = sched.health()
    assert health["live"] is False and health["ready"] is False


def test_resilience_config_rejects_unknown_keys(lm_and_params):
    model, params = lm_and_params
    with pytest.raises(ValueError, match="resilience"):
        _make_sched(model, params, resilience={"max_restart": 1})
    with pytest.raises(ValueError, match="watchdog"):
        _make_sched(model, params, resilience={"watchdog": {"factr": 2.0}})


# --------------------------------------------------------------------- #
# satellite: deadline enforcement for admission-waiting requests


def test_admission_wait_deadline_swept_manual(lm_and_params):
    """A request parked in pool-admission WAIT expires at its deadline."""
    model, params = lm_and_params
    rng = np.random.default_rng(6)
    # each request: 8 + 4 tokens -> 3 blocks of a 4-block pool, so the
    # second stays queued while the first runs
    sched = _make_sched(
        model, params, slots=2, num_blocks=4, max_new_tokens=4,
        batch_buckets=[2], prefix_cache=False,
    )
    f1 = sched.submit(rng.integers(2, VOCAB, 8).astype(np.int32))
    f2 = sched.submit(
        rng.integers(2, VOCAB, 8).astype(np.int32), deadline_ms=30.0
    )
    sched.tick()  # admits f1, parks f2 (admission_waits)
    sched._kv.check_invariants()
    assert sched.metrics.snapshot()["admission_waits"] >= 1
    time.sleep(0.05)  # let f2's deadline lapse while it is still waiting
    _drive(sched, [f1, f2])
    assert f1.result()["gen_len"] == 4
    assert isinstance(f2.exception(), TimeoutError)
    assert sched.metrics.snapshot()["timeouts"] == 1
    assert sched._kv.blocks_in_use == 0


def test_admission_wait_deadline_swept_threaded(lm_and_params):
    """Regression: the background loop must sweep a blocked request AT
    its deadline even though no new submit arrives to trigger a sweep."""
    model, params = lm_and_params
    rng = np.random.default_rng(6)
    sched = ContinuousScheduler(
        model, params, slots=2, block_size=4, num_blocks=4,
        batch_buckets=[2], seq_buckets=[8], max_new_tokens=4,
        temperature=0.0, eos_id=None, prefix_cache=False, start=True,
    )
    with sched:
        f1 = sched.submit(rng.integers(2, VOCAB, 8).astype(np.int32))
        f2 = sched.submit(
            rng.integers(2, VOCAB, 8).astype(np.int32), deadline_ms=1.0
        )
        assert f1.result(timeout=60)["gen_len"] == 4
        with pytest.raises(TimeoutError):
            f2.result(timeout=60)


# --------------------------------------------------------------------- #
# satellite: retry telemetry


def test_retry_attempts_and_exhaustion_counted():
    from pytorch_distributed_training_tpu.telemetry.registry import get_registry
    from pytorch_distributed_training_tpu.utils.retry import Retry

    reg = get_registry()
    a0 = reg.counters().get("retry_attempts", 0)
    e0 = reg.counters().get("retry_exhausted", 0)

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    policy = Retry(attempts=3, backoff=0.0, sleep=lambda d: None)
    assert policy.call(flaky) == "ok"
    assert reg.counters()["retry_attempts"] == a0 + 2
    assert reg.counters().get("retry_exhausted", 0) == e0

    def doomed():
        raise OSError("permanent")

    with pytest.raises(OSError):
        policy.call(doomed)
    assert reg.counters()["retry_exhausted"] == e0 + 1
    assert reg.counters()["retry_attempts"] == a0 + 4  # 2 more before exhaustion


# --------------------------------------------------------------------- #
# satellite: close/drain lifecycle


def test_close_under_concurrent_submit_race(lm_and_params):
    """close() vs late submit: in-flight work drains, late submissions
    get a clean RuntimeError, nothing deadlocks, and a ServingMetrics
    snapshot taken DURING close stays coherent."""
    model, params = lm_and_params
    sched = ContinuousScheduler(
        model, params, slots=2, block_size=4, num_blocks=16,
        batch_buckets=[2], seq_buckets=[8], max_new_tokens=3,
        temperature=0.0, eos_id=None, prefix_cache=False, start=True,
    )
    prompts = _prompts(seed=9, lens=(3, 5))
    futs = [sched.submit(p) for p in prompts]
    snaps, rejected = [], []

    def late_submitter():
        for _ in range(200):
            snaps.append(sched.metrics.snapshot())
            try:
                futs.append(sched.submit(prompts[0]))
            except RuntimeError:
                rejected.append(1)
                return

    t = threading.Thread(target=late_submitter)
    t.start()
    sched.close()
    t.join(timeout=30)
    assert not t.is_alive()
    assert rejected, "submitter never observed the closed scheduler"
    for f in futs:  # everything accepted before close must resolve
        assert f.result(timeout=60)["gen_len"] == 3
    assert sched._kv.blocks_in_use == 0
    assert all(isinstance(s, dict) for s in snaps)


def test_drain_finishes_inflight_then_closes(lm_and_params):
    model, params = lm_and_params
    sched = _make_sched(model, params)
    futs = [sched.submit(p) for p in _prompts()]
    sched.tick()
    ms = sched.drain()
    assert ms >= 0.0
    for f in futs:
        assert f.result()["gen_len"] == 6
    assert sched._kv.blocks_in_use == 0
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit(_prompts()[0])
    assert sched.drain() == 0.0  # idempotent once closed


def test_drain_rejects_submissions_while_draining(lm_and_params):
    model, params = lm_and_params
    sched = _make_sched(model, params)
    with sched._cond:
        sched._draining = True
    with pytest.raises(RuntimeError, match="draining"):
        sched.submit(_prompts()[0])
    with sched._cond:
        sched._draining = False
    sched.close()


def test_drain_deadline_bounds_shutdown(lm_and_params):
    """Past the drain deadline the remainder fails with TimeoutError —
    the drain completes instead of hanging on slow work."""
    model, params = lm_and_params
    sched = _make_sched(model, params)
    futs = [sched.submit(p) for p in _prompts()]
    sched.tick()
    sched._kv.check_invariants()
    ms = sched.drain(deadline_ms=0.001)  # lapses before the next tick
    assert ms >= 0.0
    for f in futs:
        assert isinstance(f.exception(), TimeoutError)
    assert sched.metrics.snapshot()["drain_expired"] == 1
    assert sched._kv.blocks_in_use == 0
    sched._kv.check_invariants()


def test_serve_nan_poison_during_drain(lm_and_params):
    """Compound #3 (chaos soak): a poison fault that fires INSIDE the
    drain(deadline_ms) window.  The drain loop must run the full bisect/
    evict ladder mid-shutdown — exactly one future fails diagnosed, every
    other request still completes, and the pool drains to empty."""
    model, params = lm_and_params
    fault.reset_counters()  # the registry is global; earlier tests leak
    sched = _make_sched(model, params)
    try:
        futs = [sched.submit(p) for p in _prompts()]
        sched.tick()  # admit; everything else happens inside drain()
        fault.install(f"serve_nan@{sched._tick_no + 2}:0")
        ms = sched.drain(deadline_ms=60_000)
        assert ms >= 0.0
        errs = [i for i, f in enumerate(futs) if f.exception() is not None]
        assert errs == [0]
        assert isinstance(futs[0].exception(), PoisonedRequestError)
        for i in (1, 2):
            assert futs[i].result()["gen_len"] == 6
        snap = sched.metrics.snapshot()
        assert snap["requests_poisoned"] == 1
        c = fault.counters()
        assert c.get("injected_serve_nans") == 1
        assert c.get("fault_fired_serve_nan") == 1
        assert sched._kv.blocks_in_use == 0
        sched._kv.check_invariants()
    finally:
        fault.install(None)
        fault.reset_counters()


def test_unfired_serve_fault_reported_at_close(lm_and_params):
    """A fault armed for a tick the engine never reaches (queue empties
    first) must not vanish: close() reports it via ``fault_unfired_*`` so
    the soak accounting oracle sees exactly fired-or-reported-unfired."""
    model, params = lm_and_params
    fault.reset_counters()  # the registry is global; earlier tests leak
    fault.install("serve_nan@999:0")
    try:
        sched = _make_sched(model, params)
        futs = [sched.submit(p) for p in _prompts()]
        _drive(sched, futs)
        for f in futs:
            assert f.result()["gen_len"] == 6  # fault never fired
        assert fault.get_injector().pending() == {"serve_nan": [999]}
        sched.close()
        c = fault.counters()
        assert c.get("fault_unfired_serve_nan") == 1
        assert not c.get("injected_serve_nans")
    finally:
        fault.install(None)
        fault.reset_counters()


def test_threaded_drain_under_load(lm_and_params):
    model, params = lm_and_params
    sched = ContinuousScheduler(
        model, params, slots=2, block_size=4, num_blocks=16,
        batch_buckets=[2], seq_buckets=[8], max_new_tokens=4,
        temperature=0.0, eos_id=None, prefix_cache=False, start=True,
    )
    futs = [sched.submit(p) for p in _prompts(seed=11, lens=(4, 3, 6, 2))]
    ms = sched.drain()
    assert ms >= 0.0
    for f in futs:
        assert f.result(timeout=1)["gen_len"] == 4
    assert sched._kv.blocks_in_use == 0


# --------------------------------------------------------------------- #
# health + SIGTERM


def test_health_snapshot_and_gauge_mirror(lm_and_params):
    model, params = lm_and_params
    sched = _make_sched(model, params, resilience={"max_restarts": 5})
    h = sched.health()
    assert h["ready"] is True and h["live"] is True
    assert h["queue_depth"] == 0 and h["active_slots"] == 0
    assert h["engine_restarts"] == 0 and h["restart_budget"] == 5
    assert h["last_tick_age_s"] is None  # no tick yet

    fut = sched.submit(_prompts()[0])
    sched.tick()
    h = sched.health()
    assert h["active_slots"] == 1
    assert h["last_tick_age_s"] is not None and h["last_tick_age_s"] >= 0.0
    snap = sched.metrics.snapshot()
    assert snap["health_ready"] == 1.0
    assert snap["health_active_slots"] == 1.0
    _drive(sched, [fut])
    sched.close()
    assert sched.health()["ready"] is False


def test_sigterm_handler_triggers_drain(lm_and_params):
    """install_drain_handler routes SIGTERM to drain; invoked directly
    (in-process kill would tear down the test runner)."""
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = {
        "dataset": {"name": "synthetic_text", "n_classes": VOCAB},
        "model": {
            "name": "TransformerLM", "embed_dim": 32, "depth": 2,
            "num_heads": 4, "max_len": 32,
        },
        "serving": {
            "dtype": "float32", "max_batch_size": 2, "max_delay_ms": 5,
            "batch_buckets": [2], "seq_buckets": [8], "max_new_tokens": 3,
            "temperature": 0.0, "eos_id": None, "seed": 0,
            "scheduler": {
                "enabled": True, "slots": 2, "block_size": 4,
                "num_blocks": 16,
            },
            "resilience": {"max_restarts": 2, "drain_deadline_ms": 30000},
        },
    }
    prev = signal.getsignal(signal.SIGTERM)
    try:
        engine = InferenceEngine.from_config(cfg)
        engine.install_drain_handler()
        handler = signal.getsignal(signal.SIGTERM)
        assert callable(handler) and handler is not prev
        fut = engine.submit(np.asarray([5, 9, 13], np.int32))
        handler(signal.SIGTERM, None)  # what the kernel would deliver
        assert fut.result(timeout=60)["gen_len"] == 3
        deadline = time.monotonic() + 30
        while not engine.health()["closed"]:
            assert time.monotonic() < deadline, "drain never closed the engine"
            time.sleep(0.01)
        assert engine.health()["ready"] is False
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_engine_rejects_resilience_on_batcher_path():
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = {
        "dataset": {"name": "synthetic_text", "n_classes": VOCAB},
        "model": {
            "name": "TransformerLM", "embed_dim": 32, "depth": 2,
            "num_heads": 4, "max_len": 32,
        },
        "serving": {
            "dtype": "float32", "max_batch_size": 2, "max_delay_ms": 5,
            "batch_buckets": [2], "seq_buckets": [8], "max_new_tokens": 3,
            "seed": 0,
            "resilience": {"max_restarts": 2},  # without scheduler.enabled
        },
    }
    with pytest.raises(ValueError, match="resilience"):
        InferenceEngine.from_config(cfg)


# --------------------------------------------------------------------- #
# watchdog: hung tick -> diagnosed restart


def test_hung_tick_becomes_diagnosed_restart(lm_and_params):
    """serve_hang stalls one tick past the watchdog limit; the fire is
    converted into a HungTickError -> hot-restart, and the rebuilt
    engine still finishes every request bitwise-identically."""
    model, params = lm_and_params
    _, clean = _run_under_spec(model, params, None)
    ref = [f.result()["tokens"] for f in clean]

    sched, futs = _run_under_spec(
        model, params, "serve_hang@5:0.5",
        resilience={
            "watchdog": {
                "enabled": True, "min_seconds": 0.15, "factor": 4.0,
                "warmup": 3, "poll_seconds": 0.02,
            },
        },
    )
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result()["tokens"], ref[i])
    assert sched._supervisor.restarts() == 1
    snap = sched.metrics.snapshot()
    assert snap["serve_watchdog_fires"] >= 1
    assert snap["engine_restarts"] == 1
    sched.close()


def test_all_four_serving_faults_in_one_run(lm_and_params):
    """Every serving recovery path fires in ONE scheduler run: a raising
    request (bisect evicts it), a NaN emitter (output guard), a device
    loss (hot-restart + replay) and a hung tick (watchdog -> second
    restart).  Exactly the two poisoned futures fail; every other request
    completes bitwise-identical to an unfaulted run, and the pool ends
    empty with its invariants held at every tick."""
    model, params = lm_and_params
    # two waves over four slots
    load = dict(
        prompts=_prompts(seed=11, lens=(2, 6, 4, 5, 3, 7, 2, 6)),
        max_new_tokens=8,
    )
    _, clean = _run_under_spec(model, params, None, **load)
    ref = [f.result()["tokens"] for f in clean]

    fault.reset_counters()
    sched, futs = _run_under_spec(
        model, params,
        "serve_raise@3:1;serve_nan@6:0;serve_device_lost@10;serve_hang@14:0.5",
        **load,
        resilience={
            "max_restarts": 3,
            "poison_bisect": True,
            "watchdog": {
                "enabled": True, "min_seconds": 0.15, "factor": 4.0,
                "warmup": 3, "poll_seconds": 0.02,
            },
        },
    )
    poisoned = [i for i, f in enumerate(futs) if f.exception() is not None]
    assert len(poisoned) == 2, poisoned
    for i in poisoned:
        assert isinstance(futs[i].exception(), PoisonedRequestError)
    for i, f in enumerate(futs):
        if i not in poisoned:
            np.testing.assert_array_equal(f.result()["tokens"], ref[i])
    c = fault.counters()
    for kind in ("raises", "nans", "device_lost", "hangs"):
        assert c.get(f"injected_serve_{kind}") == 1, (kind, c)
    assert sched._supervisor.restarts() == 2
    snap = sched.metrics.snapshot()
    assert snap["requests_poisoned"] == 2
    assert snap["engine_restarts"] == 2
    assert snap["serve_watchdog_fires"] >= 1
    assert snap.get("replay_parity_mismatch", 0) == 0
    assert sched._kv.blocks_in_use == 0
    sched.close()


# --------------------------------------------------------------------- #
# poison isolation under a ring that holds steps (async_depth > 0):
# the finite guard / poison shim fire up to async_depth ticks AFTER the
# faulted dispatch, so eviction happens at DRAIN time — attribution must
# still name exactly the poisoned request, and the lagged retire must
# not leak blocks or disturb neighbours.


@pytest.mark.parametrize("depth", [1, 2])
def test_async_poison_isolation_nan_output_guard(lm_and_params, depth):
    """serve_nan with a full dispatch ring: the non-finite flag is
    observed one-or-more ticks late at drain, evicts ONLY the poisoned
    slot, and the survivors stay bitwise equal to a clean run at depth 0."""
    model, params = lm_and_params
    _, clean = _run_under_spec(model, params, None, prefix_cache=False)
    ref = [f.result()["tokens"] for f in clean]

    sched, futs = _run_under_spec(
        model, params, "serve_nan@2:0", prefix_cache=False,
        async_depth=depth,
    )
    errs = [i for i, f in enumerate(futs) if f.exception() is not None]
    assert errs == [0]
    exc = futs[0].exception()
    assert isinstance(exc, PoisonedRequestError)
    assert "non-finite" in str(exc)
    assert exc.__cause__ is None  # guard path: nothing ever raised
    for i in (1, 2):
        np.testing.assert_array_equal(futs[i].result()["tokens"], ref[i])
    assert sched._supervisor.restarts() == 0
    assert sched.metrics.snapshot()["requests_poisoned"] == 1
    assert sched._kv.blocks_in_use == 0


@pytest.mark.parametrize("depth", [1, 2])
def test_async_poison_isolation_decode_raise(lm_and_params, depth):
    """serve_raise mid-pipeline: the supervisor drains the in-flight
    ring (flush_async) BEFORE bisecting, so the probe sees a
    state-consistent pool and convicts exactly the faulted request."""
    model, params = lm_and_params
    _, clean = _run_under_spec(model, params, None, prefix_cache=False)
    ref = [f.result()["tokens"] for f in clean]

    sched, futs = _run_under_spec(
        model, params, "serve_raise@2:1", prefix_cache=False,
        async_depth=depth,
    )
    errs = [i for i, f in enumerate(futs) if f.exception() is not None]
    assert errs == [1]
    exc = futs[1].exception()
    assert isinstance(exc, PoisonedRequestError)
    assert isinstance(exc.__cause__, fault.FaultInjectionError)
    for i in (0, 2):
        np.testing.assert_array_equal(futs[i].result()["tokens"], ref[i])
    assert sched._supervisor.restarts() == 0  # isolated, never restarted
    snap = sched.metrics.snapshot()
    assert snap["requests_poisoned"] == 1
    assert sched._kv.blocks_in_use == 0


# --------------------------------------------------------------------- #
# the pool is donated: a call that raises AFTER it took the pool leaves
# none to probe with


def _lose_the_pool_once(sched, monkeypatch):
    """The next ``decode_step`` behaves like a donating call that failed
    after its dispatch: the pool it was given is deleted, and it raises an
    error that names no device (so the ladder's first rung is the bisect)."""
    real = sched._fns.decode_step
    calls = []

    def lossy(params, pool, *rest):
        if not calls:
            calls.append(1)
            for leaf in jax.tree_util.tree_leaves(pool):
                leaf.delete()
            raise RuntimeError("the step failed after it consumed the pool")
        return real(params, pool, *rest)

    monkeypatch.setattr(sched._fns, "decode_step", lossy)
    return calls


@pytest.mark.parametrize("lost_by", ["the_step", "a_probe"])
def test_a_lost_pool_ends_in_the_restart_not_in_a_bisect(
    lm_and_params, monkeypatch, lost_by
):
    """No program can run on a deleted pool, so every probe of a bisect over
    one would raise and the last candidate standing would be evicted for it.
    The supervisor restarts instead: new pool, delivered tokens replayed,
    every request that poisoned nothing completes as in a clean run."""
    model, params = lm_and_params
    _, clean = _run_under_spec(model, params, None)
    ref = [f.result()["tokens"] for f in clean]

    # a_probe: slot 1 raises BEFORE the dispatch at tick 2 (pool intact, the
    # bisect starts), then the first probe that reaches the device loses it
    fault.install("serve_raise@2:1" if lost_by == "a_probe" else None)
    try:
        sched = _make_sched(model, params)
        futs = [sched.submit(p) for p in _prompts()]
        sched.tick()  # admit + prefill + the first decode step
        calls = _lose_the_pool_once(sched, monkeypatch)
        _drive(sched, futs)
    finally:
        fault.install(None)
    assert calls and sched._supervisor.restarts() == 1
    assert not sched._pool_lost()
    snap = sched.metrics.snapshot()
    if lost_by == "the_step":
        assert snap.get("poison_probes", 0) == 0  # never probed a dead pool
        assert snap.get("requests_poisoned", 0) == 0
        survivors = (0, 1, 2)
    else:
        # after the restart the injected raise is found and evicted alone
        assert isinstance(futs[1].exception(), PoisonedRequestError)
        assert snap["requests_poisoned"] == 1
        survivors = (0, 2)
    for i in survivors:
        np.testing.assert_array_equal(futs[i].result()["tokens"], ref[i])
    assert snap.get("replay_parity_mismatch", 0) == 0
    assert sched._kv.blocks_in_use == 0
