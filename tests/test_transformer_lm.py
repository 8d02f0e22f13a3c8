"""Long-context LM: sequence-parallel training step vs single-shard reference.

The strongest correctness property of the SP design (engine/sp_steps.py):
one DP x SP step on a (data, sequence) fake-device mesh must produce the
SAME loss and updated parameters as a single-device step of the same model
over the full (unsharded) batch — ring attention, position-embedding
slicing, the partial-loss psum and the gradient reduction that shard_map's
transpose derives from it all have to be exact for this to hold.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.engine import TrainState, build_lm_train_step
from pytorch_distributed_training_tpu.engine.sp_steps import lm_loss_local
from pytorch_distributed_training_tpu.models.transformer_lm import TransformerLM
from pytorch_distributed_training_tpu.optimizers import SGD
from pytorch_distributed_training_tpu.parallel import make_sp_mesh, replicated_sharding
from pytorch_distributed_training_tpu.schedulers import multi_step_lr

VOCAB, SEQ, BATCH = 64, 32, 4


def _data(seed=0, batch=BATCH):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, VOCAB, (batch, SEQ + 1)).astype(np.int32)
    return jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])  # host shift


def _model(seq_axis):
    return TransformerLM(
        vocab_size=VOCAB, max_len=SEQ, embed_dim=32, depth=2, num_heads=4,
        seq_axis=seq_axis,
    )


def _reference_step(tokens, labels, opt, lr, key=0):
    """One plain-jax step of the unsharded model on the whole batch:
    ``(params, loss, grads, updated params)``."""
    ref_model = _model(None)
    params = ref_model.init(jax.random.PRNGKey(key), tokens)["params"]

    def ref_loss(p):
        logits = ref_model.apply({"params": p}, tokens)
        return lm_loss_local(logits, labels, labels.size)

    loss, grads = jax.value_and_grad(ref_loss)(params)
    updated, _ = opt.update(grads, opt.init(params), params, lr)
    return params, loss, grads, updated


def test_single_shard_forward():
    model = _model(None)
    tokens, _ = _data()
    vars_ = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(vars_, tokens)
    assert logits.shape == (BATCH, SEQ, VOCAB)


@pytest.mark.parametrize("grad_accum", [1, 2], ids=["accum1", "accum2"])
@pytest.mark.parametrize("seq_par", [1, 2, 4], ids=["8x1", "4x2", "2x4"])
def test_sp_step_matches_single_device(seq_par, grad_accum):
    """Every (data, sequence) split of the eight devices, with and without
    accumulation, against plain jax on the unsharded batch: the gradient
    scale in particular (an explicit post-grad collective beside the
    transpose's own would be world_size x too large)."""
    tokens, labels = _data(batch=16)
    opt = SGD(lr=0.05, momentum=0.9, weight_decay=1e-4)
    lr_fn = multi_step_lr(0.05, [], 0.1)
    params, loss_ref, _, params_ref = _reference_step(tokens, labels, opt, 0.05)

    # ---- DP x SP sharded step ---------------------------------------------
    mesh = make_sp_mesh(sequence_parallelism=seq_par)
    sp_model = _model("sequence")
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    state = jax.device_put(state, replicated_sharding(mesh))
    step = build_lm_train_step(
        sp_model, opt, lr_fn, mesh, grad_accum=grad_accum
    )
    state2, loss_sp = step(state, tokens, labels)

    assert np.isclose(float(loss_sp), float(loss_ref), atol=1e-5), (loss_sp, loss_ref)
    flat_ref = jax.tree_util.tree_leaves(params_ref)
    flat_sp = jax.tree_util.tree_leaves(state2.params)
    for a, b in zip(flat_ref, flat_sp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_sp_step_ulysses_matches_single_device():
    tokens, labels = _data(seed=3)
    opt = SGD(lr=0.05, momentum=0.9)
    lr_fn = multi_step_lr(0.05, [], 0.1)
    # param-level oracle too (ADVICE.md r1: loss-only would miss a wrong
    # all_to_all transpose in the ulysses backward)
    params, loss_ref, _, params_ref = _reference_step(
        tokens, labels, opt, 0.05, key=1
    )

    mesh = make_sp_mesh(sequence_parallelism=4)
    sp_model = TransformerLM(
        vocab_size=VOCAB, max_len=SEQ, embed_dim=32, depth=2, num_heads=4,
        seq_axis="sequence", seq_impl="ulysses",
    )
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    state = jax.device_put(state, replicated_sharding(mesh))
    step = build_lm_train_step(sp_model, opt, lr_fn, mesh)
    state2, loss_sp = step(state, tokens, labels)
    assert np.isclose(float(loss_sp), float(loss_ref), atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(params_ref),
        jax.tree_util.tree_leaves(state2.params),
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_lm_step_carries_the_trace_scopes():
    """The step's operations carry ``forward``, ``loss_head`` (final norm,
    logits matmul, CE: model and step builder both) and ``optimizer`` in
    their op_name, forward and transposed: a profiler trace groups device
    time by them (benchmark/metrics/loss_head_ms_per_step.py and
    optimizer_ms_per_step.py read them).  The kernels' own names are held
    in tests/test_chip_compile.py, on the program compiled for the chip."""
    import re

    tokens, labels = _data()
    opt = SGD(lr=0.05, momentum=0.9)
    mesh = make_sp_mesh(sequence_parallelism=4)  # DP(2) x SP(4)
    params = _model(None).init(jax.random.PRNGKey(0), tokens)["params"]
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    step = build_lm_train_step(
        _model("sequence"), opt, multi_step_lr(0.05, [], 0.1), mesh,
        donate=False,
    )
    text = step.lower(state, tokens, labels).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))

    def under(*parts):
        return [n for n in names if all(p in n for p in parts)]

    assert under("jvp(forward)", "block0"), "forward pass not under `forward`"
    assert under("transpose(jvp(forward))", "block0")
    # the final norm and the head inside the model, the CE in the builder
    assert under("jvp(forward)", "loss_head/ln")
    assert under("jvp(forward)", "loss_head/head")
    assert under("jvp(loss_head)") and under("transpose(jvp(loss_head))")
    assert [n for n in names if n.startswith("optimizer/")]
    # nothing of a decoder block is under the head's scope
    assert not under("loss_head", "block")


@pytest.mark.parametrize("seq_par", [1, 2, 4], ids=["8x1", "4x2", "2x4"])
def test_sp_guarded_step_sees_the_global_gradient(seq_par):
    """The anomaly guard takes the gradient's norm with no collective of its
    own: it may, because what ``shard_map``'s transpose hands every shard is
    already the global gradient.  So the norm the step reports must be the
    unsharded gradient's, an applied step must land where the plain one
    does, and a step refused on that norm must leave the state untouched."""
    tokens, labels = _data(batch=16)
    opt = SGD(lr=0.05, momentum=0.9, weight_decay=1e-4)
    lr_fn = multi_step_lr(0.05, [], 0.1)
    params, _, grads_ref, params_ref = _reference_step(tokens, labels, opt, 0.05)
    gnorm_ref = float(
        jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads_ref)))
    )

    mesh = make_sp_mesh(sequence_parallelism=seq_par)
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    state = jax.device_put(state, replicated_sharding(mesh))
    step = build_lm_train_step(
        _model("sequence"), opt, lr_fn, mesh, donate=False, anomaly_factor=10.0
    )
    # a trailing median of the true norm: the step is applied
    applied_state, _, gnorm, applied = step(state, tokens, labels, gnorm_ref)
    assert float(applied) == 1.0
    np.testing.assert_allclose(float(gnorm), gnorm_ref, rtol=1e-4)
    for a, b in zip(
        jax.tree.leaves(params_ref), jax.tree.leaves(applied_state.params)
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)
    # a trailing median a hundred times smaller: the same step is a spike
    kept, _, _, applied = step(state, tokens, labels, gnorm_ref / 100.0)
    assert float(applied) == 0.0
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(kept.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
