"""Config-gated jax.profiler trace hooks (SURVEY.md §5.1 rebuild item) and
the round-6 remat/fusion recovery oracles."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.engine import TraceProfiler


def test_from_config_absent_returns_none():
    assert TraceProfiler.from_config({"batch_size": 16}) is None
    assert TraceProfiler.from_config({"profile": None}) is None


def test_trace_window_produces_profile(tmp_path):
    prof_dir = str(tmp_path / "trace")
    prof = TraceProfiler.from_config(
        {"profile": {"dir": prof_dir, "start_iter": 2, "n_iters": 3}}
    )
    assert prof is not None and prof.start_iter == 2 and prof.n_iters == 3

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    for it in range(8):
        jax.block_until_ready(f(x))
        prof.after_step(it)
    prof.stop()  # idempotent: window already closed at iter 4

    # jax.profiler writes plugins/profile/<timestamp>/*.xplane.pb under dir
    found = [
        os.path.join(dp, fn)
        for dp, _, fns in os.walk(prof_dir)
        for fn in fns
    ]
    assert found, f"no trace files written under {prof_dir}"


def test_from_config_bad_values(tmp_path):
    import pytest

    with pytest.raises(ValueError, match="must be a mapping"):
        TraceProfiler.from_config({"profile": True})
    with pytest.raises(ValueError, match="profile.dir is required"):
        TraceProfiler.from_config({"profile": {"start_iter": 3}})


def test_zero_capture_close_rearms(tmp_path):
    """A stop() that caught no iterations (e.g. validation fired the moment
    the window opened) discards the window and retries afterwards."""
    prof = TraceProfiler(str(tmp_path / "t3"), start_iter=2, n_iters=2)
    prof.after_step(2)          # opens
    prof.stop()                 # interruption before any traced iteration
    assert not prof._active and not prof._done  # re-armed
    prof.after_step(3)          # reopens
    assert prof._active
    prof.after_step(4)
    prof.after_step(5)          # 5 >= 3+2 -> closes, 2 iterations captured
    assert prof._done
    prof.finalize()             # idempotent


def test_window_opens_once(tmp_path):
    prof = TraceProfiler(str(tmp_path / "t2"), start_iter=0, n_iters=1)
    prof.after_step(0)  # opens: traces iteration 1
    assert prof._active and not prof._done
    prof.after_step(1)  # closes after the traced iteration completes
    assert prof._done and not prof._active
    prof.after_step(2)  # no reopen
    assert not prof._active


_VOCAB, _SEQ, _BATCH = 128, 32, 2


def _tiny_lm(**kw):
    from pytorch_distributed_training_tpu.models.transformer_lm import (
        TransformerLM,
    )

    return TransformerLM(
        vocab_size=_VOCAB, max_len=_SEQ, embed_dim=32, depth=2, num_heads=4,
        dtype=jnp.float32, **kw,
    )


def _tiny_batch(seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, _VOCAB, (_BATCH, _SEQ + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


# --------------------------------------------------------------------- #
# Round 6: remat policies + fused tails + fused optimizer parity oracles
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("policy", ["nothing", "dots", "dots_saveable"])
def test_remat_loss_parity(policy):
    """Remat changes WHERE activations come from (store vs recompute),
    never their values: >=10 training steps with remat on must track the
    remat-off trajectory to 1e-5."""
    from pytorch_distributed_training_tpu.ops import cross_entropy_loss

    inp, lab = _tiny_batch()

    def run(lm):
        params = lm.init(jax.random.PRNGKey(0), inp)["params"]

        def loss_fn(p):
            logits = lm.apply({"params": p}, inp)
            return cross_entropy_loss(
                logits.reshape(-1, lm.vocab_size), lab.reshape(-1)
            )

        @jax.jit
        def step(p):
            loss, g = jax.value_and_grad(loss_fn)(p)
            return jax.tree_util.tree_map(lambda w, d: w - 0.1 * d, p, g), loss

        losses = []
        for _ in range(10):
            params, loss = step(params)
            losses.append(float(loss))
        return losses

    base = run(_tiny_lm(remat=False))
    remat = run(_tiny_lm(remat=True, remat_policy=policy))
    np.testing.assert_allclose(remat, base, rtol=0, atol=1e-5)


def test_fused_tails_parity():
    """model.fused_tails swaps elementwise tails into Pallas kernels with
    an IDENTICAL parameter tree: same init values, and logits + grads
    match the plain path on the same params."""
    from pytorch_distributed_training_tpu.ops import cross_entropy_loss

    inp, lab = _tiny_batch()
    plain = _tiny_lm(fused_tails=False)
    fused = _tiny_lm(fused_tails=True)
    p_plain = plain.init(jax.random.PRNGKey(0), inp)["params"]
    p_fused = fused.init(jax.random.PRNGKey(0), inp)["params"]
    assert jax.tree_util.tree_structure(p_plain) == jax.tree_util.tree_structure(
        p_fused
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(p_plain), jax.tree_util.tree_leaves(p_fused)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def loss_fn(lm):
        def f(p):
            logits = lm.apply({"params": p}, inp)
            return cross_entropy_loss(
                logits.reshape(-1, lm.vocab_size), lab.reshape(-1)
            )

        return jax.jit(jax.value_and_grad(f))

    l0, g0 = loss_fn(plain)(p_plain)
    l1, g1 = loss_fn(fused)(p_plain)  # SAME params through the fused graph
    assert abs(float(l0) - float(l1)) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=1e-4
        )


def _mixed_tree(seed=0):
    rng = np.random.default_rng(seed)

    def arr(shape, dt):
        return jnp.asarray(rng.standard_normal(shape), dt)

    return {
        "dense": {"kernel": arr((8, 16), jnp.float32), "bias": arr((16,), jnp.float32)},
        "emb": arr((32, 8), jnp.float32),
        "half": arr((5, 5), jnp.bfloat16),
    }


@pytest.mark.parametrize("opt_name", ["SGD", "AdamW"])
def test_fused_optimizer_bitwise(opt_name):
    """training.optimizer.fused concatenates same-dtype leaves into one
    update — pointwise math commutes with concat, so the result must be
    BITWISE identical to the per-leaf path over multiple steps, including
    the folded-EMA variant vs a post-hoc tree-map."""
    import pytorch_distributed_training_tpu.optimizers as O

    kw = dict(lr=0.1, weight_decay=1e-2)
    if opt_name == "SGD":
        kw["momentum"] = 0.9
    make = getattr(O, opt_name)
    ref, fus = make(**kw), make(**kw, fused=True)
    params_r = params_f = _mixed_tree()
    ema_r = ema_f = _mixed_tree(1)
    state_r, state_f = ref.init(params_r), fus.init(params_f)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(
            np.random.default_rng(2).standard_normal(p.shape), p.dtype
        ),
        params_r,
    )
    d = 0.99
    for _ in range(3):
        params_r, state_r = ref.update(grads, state_r, params_r, 0.05)
        ema_r = jax.tree_util.tree_map(
            lambda e, p: d * e + (1.0 - d) * p, ema_r, params_r
        )
        params_f, state_f, ema_f = fus.update_with_ema(
            grads, state_f, params_f, 0.05, ema_f, d
        )
        for a, b in zip(
            jax.tree_util.tree_leaves((params_r, state_r, ema_r)),
            jax.tree_util.tree_leaves((params_f, state_f, ema_f)),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_training_remat_config_key():
    """training.remat parses onto the model (none/block/dots/dots_saveable),
    rejects unknown values, non-LM configs, and conflicts with the
    model-section remat keys."""
    import types

    from pytorch_distributed_training_tpu.engine.topology import parse_topology

    class _DS:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            return np.zeros(_SEQ, np.int32), np.zeros(_SEQ, np.int32)

    def parse(remat=None, model_extra=None, model_name="TransformerLM"):
        model = {
            "name": model_name, "embed_dim": 32, "depth": 2, "num_heads": 4,
            "max_len": _SEQ,
        }
        if model_name != "TransformerLM":
            model = {"name": model_name}
        model.update(model_extra or {})
        cfg = {
            "dataset": {"name": "synthetic_text", "n_classes": _VOCAB,
                        "seq_len": _SEQ},
            "training": {"sync_bn": False, "batch_size": 8},
            "model": model,
        }
        if remat is not None:
            cfg["training"]["remat"] = remat
        r = types.SimpleNamespace(distributed=False, seq_len=_SEQ, world_size=1)
        parse_topology(r, cfg, cfg["training"], _DS())
        return r

    assert parse("none").model.remat is False
    assert parse("block").model.remat is True
    assert parse("block").model.remat_policy == "nothing"
    assert parse("dots").model.remat_policy == "dots"
    assert parse("dots_saveable").model.remat_policy == "dots_saveable"
    assert parse(None).model.remat is False  # absent key: default off
    with pytest.raises(ValueError, match="training.remat must be one of"):
        parse("typo")
    with pytest.raises(ValueError, match="not both"):
        parse("dots", model_extra={"remat": True})
    with pytest.raises(ValueError, match="only wired for the LM task"):
        parse("dots", model_name="ResNet18")
