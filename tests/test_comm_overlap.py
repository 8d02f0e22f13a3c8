"""Bucketed, backward-overlapped gradient reduction (engine/comm.py).

Parity strategy:

- The overlap path differentiates the LOCAL loss — the backward carries no
  collective — and the reduction then happens as FORWARD-only collectives.
  8-device overlap/zero1 runs are compared against an UNSHARDED plain-jax
  reference.
- The legacy (implicit) path differentiates through an in-body collective;
  baseline-vs-overlap comparisons run on 1-device meshes, where collectives
  are identity and the parity is BITWISE.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_training_tpu.engine.comm import (
    Bucket,
    CommConfig,
    plan_buckets,
    reduce_gradients,
    zero1_init,
    zero1_slot_count,
)

DATA = "data"
SEQ_AXIS = "sequence"


# --------------------------------------------------------------------- #
# Bucket planner (pure host-side: no devices, no fixture)
# --------------------------------------------------------------------- #


def _leaves(*specs):
    return [jnp.zeros(shape, dtype) for shape, dtype in specs]


def test_plan_reverse_order_and_cap():
    # 4 leaves of 64 f32 (256 B) with a 512 B cap -> two buckets of two,
    # walked back-to-front
    leaves = _leaves(*[((64,), jnp.float32)] * 4)
    plan = plan_buckets(leaves, 512 / 2**20)
    assert [b.indices for b in plan] == [(3, 2), (1, 0)]
    assert all(b.size == 128 and b.dtype == jnp.float32 for b in plan)


def test_plan_dtype_change_closes_bucket():
    leaves = _leaves(
        ((8,), jnp.float32), ((8,), jnp.bfloat16), ((8,), jnp.bfloat16)
    )
    plan = plan_buckets(leaves, 1.0)
    assert [(b.indices, b.dtype) for b in plan] == [
        ((2, 1), jnp.dtype(jnp.bfloat16)),
        ((0,), jnp.dtype(jnp.float32)),
    ]


def test_plan_oversized_leaf_becomes_singleton():
    # middle leaf alone exceeds the cap: it must get its own bucket without
    # dragging neighbors in, and the walk stays strictly reverse-ordered
    leaves = _leaves(((4,), jnp.float32), ((10_000,), jnp.float32), ((4,), jnp.float32))
    plan = plan_buckets(leaves, 64 / 2**20)
    assert [b.indices for b in plan] == [(2,), (1,), (0,)]
    assert plan[1].size == 10_000


def test_plan_empty_tree():
    assert plan_buckets([], 25.0) == []


def test_plan_accepts_shape_structs():
    # init-time planning runs on ShapeDtypeStruct, not concrete arrays
    structs = [
        jax.ShapeDtypeStruct((16, 4), jnp.float32),
        jax.ShapeDtypeStruct((3,), jnp.float32),
    ]
    plan = plan_buckets(structs, 25.0)
    assert plan == [Bucket((1, 0), jnp.dtype(jnp.float32), 67)]


def test_reduce_gradients_validates_op_and_passes_empty():
    with pytest.raises(ValueError, match="psum or pmean"):
        reduce_gradients({"g": jnp.ones(3)}, CommConfig(overlap=True), DATA, op="pmax")
    empty = {}
    assert reduce_gradients(empty, CommConfig(overlap=True), DATA) is empty


# --------------------------------------------------------------------- #
# training.comm config parsing (engine/topology.parse_comm)
# --------------------------------------------------------------------- #


class _R:
    pass


def _parse(train_cfg):
    from pytorch_distributed_training_tpu.engine.topology import parse_comm

    r = _R()
    parse_comm(r, train_cfg)
    return r.comm


def test_parse_comm_default_off():
    assert _parse({}) == CommConfig(overlap=False, bucket_mb=25.0, reduce_dtype=None)
    assert _parse({"comm": {}}).overlap is False


def test_parse_comm_full_block():
    cfg = _parse({"comm": {"overlap": True, "bucket_mb": 4, "reduce_dtype": "bfloat16"}})
    assert cfg == CommConfig(overlap=True, bucket_mb=4.0, reduce_dtype="bfloat16")


def test_parse_comm_rejects_bad_keys_and_values():
    with pytest.raises(ValueError, match="unknown key"):
        _parse({"comm": {"overlap": True, "bucket_size_mb": 4}})
    with pytest.raises(ValueError, match="bucket_mb"):
        _parse({"comm": {"bucket_mb": 0}})
    with pytest.raises(ValueError, match="reduce_dtype"):
        _parse({"comm": {"reduce_dtype": "float16"}})


# --------------------------------------------------------------------- #
# zero1 builder validation (raises before any shard_map is traced)
# --------------------------------------------------------------------- #


def test_zero1_validation_errors():
    from pytorch_distributed_training_tpu.engine.sp_steps import build_lm_train_step
    from pytorch_distributed_training_tpu.models.transformer_lm import TransformerLM
    from pytorch_distributed_training_tpu.optimizers import LAMB, LARS, SGD, AdamW
    from pytorch_distributed_training_tpu.parallel import make_sp_mesh
    from pytorch_distributed_training_tpu.schedulers import multi_step_lr

    lm = TransformerLM(vocab_size=32, max_len=16, embed_dim=16, depth=1, num_heads=2)
    opt = SGD(lr=0.1, momentum=0.9)
    lr_fn = multi_step_lr(0.1, [], 0.1)
    on = CommConfig(overlap=True)

    with pytest.raises(ValueError, match="comm.overlap"):
        build_lm_train_step(lm, opt, lr_fn, make_sp_mesh(1), zero1=True)
    with pytest.raises(ValueError, match="anomaly"):
        build_lm_train_step(
            lm, opt, lr_fn, make_sp_mesh(1), comm=on, zero1=True, anomaly_factor=10.0
        )
    with pytest.raises(ValueError, match="sequence_parallelism"):
        build_lm_train_step(lm, opt, lr_fn, make_sp_mesh(4), comm=on, zero1=True)

    # the optimizer gate: elementwise kernels only
    assert zero1_slot_count(SGD(lr=0.1)) == 1
    assert zero1_slot_count(AdamW(lr=1e-3)) == 2
    with pytest.raises(ValueError, match="LARS/LAMB"):
        zero1_slot_count(LARS(lr=0.1))
    with pytest.raises(ValueError, match="LARS/LAMB"):
        zero1_slot_count(LAMB(lr=1e-3))
    with pytest.raises(ValueError, match="exclude_norm_bias"):
        zero1_slot_count(AdamW(lr=1e-3, exclude_norm_bias=True))


# --------------------------------------------------------------------- #
# Forward-only reduction: bucketed == monolithic, bitwise (8 devices)
# --------------------------------------------------------------------- #


def _grad_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w1": jnp.asarray(rng.standard_normal((8, 32)).astype(np.float32)),
        "w2": jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((8,)).astype(np.float32)),
        "h": jnp.asarray(
            rng.standard_normal((8, 8)).astype(np.float32)
        ).astype(jnp.bfloat16),
    }


def _run_reduce(tree, cfg, op):
    mesh = Mesh(np.array(jax.devices()), (DATA,))

    def body(t):
        red = reduce_gradients(t, cfg, DATA, op=op)
        mono = jax.tree.map(
            lambda x: jax.lax.psum(x, DATA) if op == "psum" else jax.lax.pmean(x, DATA),
            t,
        )
        return red, mono

    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(P(DATA),), out_specs=P())
    )(tree)


@pytest.mark.parametrize("op", ["psum", "pmean"])
@pytest.mark.parametrize("bucket_mb", [25.0, 64 / 2**20])
def test_bucketed_reduce_matches_monolithic_bitwise(op, bucket_mb):
    """Concatenation commutes with elementwise reduction: whatever the
    bucketing (one giant bucket or a long barrier chain of tiny ones), the
    reduced tree must equal the per-leaf collective BITWISE."""
    tree = _grad_tree()
    red, mono = _run_reduce(tree, CommConfig(overlap=True, bucket_mb=bucket_mb), op)
    for a, b in zip(jax.tree.leaves(red), jax.tree.leaves(mono)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reduce_dtype_cast_roundtrip():
    """reduce_dtype=bfloat16: the collective runs in bf16 but every output
    leaf comes back in its own dtype, close to the f32 reduction."""
    tree = _grad_tree(seed=1)
    red, mono = _run_reduce(
        tree, CommConfig(overlap=True, bucket_mb=25.0, reduce_dtype="bfloat16"), "pmean"
    )
    for (k, a), b in zip(sorted(red.items()), [v for _, v in sorted(mono.items())]):
        assert a.dtype == tree[k].dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-2, atol=2e-2
        )


def test_bucket_bytes_histogram_recorded():
    from pytorch_distributed_training_tpu.telemetry import get_registry, reset_registry

    reset_registry()
    try:
        _run_reduce(_grad_tree(), CommConfig(overlap=True, bucket_mb=64 / 2**20), "psum")
        snap = get_registry().histogram("comm_bucket_bytes").snapshot()
        assert snap["count"] >= 2  # tiny cap -> several buckets observed
        assert snap["max"] > 0
    finally:
        reset_registry()


# --------------------------------------------------------------------- #
# DP image path (engine/steps.py)
# --------------------------------------------------------------------- #

_N_CLASSES = 4


def _tiny_cnn():
    import flax.linen as nn

    class _TinyNet(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(4, (3, 3))(x)
            x = nn.relu(x)
            x = x.mean(axis=(1, 2))
            return nn.Dense(_N_CLASSES)(x)

    return _TinyNet()


def _dp_fixtures(batch=16, seed=5):
    from pytorch_distributed_training_tpu.engine import init_train_state
    from pytorch_distributed_training_tpu.optimizers import SGD

    rng = np.random.default_rng(seed)
    img = jnp.asarray(rng.standard_normal((batch, 8, 8, 3)).astype(np.float32))
    label = jnp.asarray(rng.integers(0, _N_CLASSES, (batch,)).astype(np.int32))
    model = _tiny_cnn()
    opt = SGD(lr=0.05, momentum=0.9, weight_decay=1e-4)
    state = init_train_state(model, opt, jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    return model, opt, state, img, label


def test_dp_overlap_bitwise_on_single_device():
    """1-device mesh: collectives are identity in both paths, so the
    bucketed explicit reduction must reproduce the legacy step BITWISE."""
    from pytorch_distributed_training_tpu.engine import build_train_step
    from pytorch_distributed_training_tpu.parallel import make_mesh
    from pytorch_distributed_training_tpu.schedulers import multi_step_lr

    model, opt, state, img, label = _dp_fixtures()
    lr_fn = multi_step_lr(0.05, [], 0.1)
    mesh1 = make_mesh(devices=jax.devices()[:1])
    base = build_train_step(model, opt, lr_fn, mesh1, sync_bn=False, donate=False)
    over = build_train_step(
        model, opt, lr_fn, mesh1, sync_bn=False, donate=False,
        comm=CommConfig(overlap=True, bucket_mb=1e-4),
    )
    s_base, loss_base = base(state, img, label)
    s_over, loss_over = over(state, img, label)
    assert float(loss_base) == float(loss_over)
    for a, b in zip(jax.tree.leaves(s_base.params), jax.tree.leaves(s_over.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dp_overlap_8dev_matches_unsharded():
    """8-device overlap step == plain-jax full-batch step.  The overlap
    backward is collective-free (exact local AD) and pmean(g_local) over a
    power-of-two mesh is the full-batch mean up to reassociation."""
    from pytorch_distributed_training_tpu.engine import build_train_step
    from pytorch_distributed_training_tpu.ops import cross_entropy_loss
    from pytorch_distributed_training_tpu.parallel import batch_sharding, make_mesh
    from pytorch_distributed_training_tpu.schedulers import multi_step_lr

    model, opt, state, img, label = _dp_fixtures()
    lr_fn = multi_step_lr(0.05, [], 0.1)

    def ref_loss(p):
        return cross_entropy_loss(model.apply({"params": p}, img, train=False), label)

    _, grads = jax.value_and_grad(ref_loss)(state.params)
    ref_params, _ = opt.update(grads, opt.init(state.params), state.params, 0.05)

    mesh = make_mesh()
    step = build_train_step(
        model, opt, lr_fn, mesh, sync_bn=False, donate=False,
        comm=CommConfig(overlap=True, bucket_mb=1e-4),
    )
    s8, _ = step(
        state,
        jax.device_put(img, batch_sharding(mesh, 4)),
        jax.device_put(label, batch_sharding(mesh, 1)),
    )
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(s8.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-6)


# --------------------------------------------------------------------- #
# SP LM path (engine/sp_steps.py) + ZeRO-1 + grad accumulation
# --------------------------------------------------------------------- #

VOCAB, SEQ, BATCH = 32, 16, 16


def _lm_fixtures(seed=2):
    from pytorch_distributed_training_tpu.models.transformer_lm import TransformerLM
    from pytorch_distributed_training_tpu.optimizers import SGD

    rng = np.random.default_rng(seed)
    toks = rng.integers(0, VOCAB, (BATCH, SEQ + 1)).astype(np.int32)
    tokens, labels = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    mk = lambda ax: TransformerLM(  # noqa: E731
        vocab_size=VOCAB, max_len=SEQ, embed_dim=16, depth=1, num_heads=2,
        seq_axis=ax,
    )
    params = mk(None).init(jax.random.PRNGKey(0), tokens)["params"]
    return mk, params, SGD(lr=0.05, momentum=0.9, weight_decay=1e-4), tokens, labels


def _lm_reference(mk, params, opt, tokens, labels, steps=1):
    from pytorch_distributed_training_tpu.engine.sp_steps import lm_loss_local

    ref_model = mk(None)

    def ref_loss(p):
        return lm_loss_local(ref_model.apply({"params": p}, tokens), labels, labels.size)

    opt_state = opt.init(params)
    for _ in range(steps):
        _, grads = jax.value_and_grad(ref_loss)(params)
        params, opt_state = opt.update(grads, opt_state, params, 0.05)
    return params


def test_sp_overlap_bitwise_on_single_device():
    """(1, 1) mesh: the SP objective's psum is identity, so legacy vs
    overlap must agree BITWISE at grad_accum == 1 (identical sum)."""
    from pytorch_distributed_training_tpu.engine import TrainState, build_lm_train_step
    from pytorch_distributed_training_tpu.schedulers import multi_step_lr

    mk, params, opt, tokens, labels = _lm_fixtures()
    lr_fn = multi_step_lr(0.05, [], 0.1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), (DATA, SEQ_AXIS))
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    base = build_lm_train_step(mk(SEQ_AXIS), opt, lr_fn, mesh, donate=False)
    over = build_lm_train_step(
        mk(SEQ_AXIS), opt, lr_fn, mesh, donate=False,
        comm=CommConfig(overlap=True, bucket_mb=1e-4),
    )
    s_base, loss_base = base(state, tokens, labels)
    s_over, loss_over = over(state, tokens, labels)
    assert float(loss_base) == float(loss_over)
    for a, b in zip(jax.tree.leaves(s_base.params), jax.tree.leaves(s_over.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sp_overlap_8dev_matches_unsharded():
    from pytorch_distributed_training_tpu.engine import TrainState, build_lm_train_step
    from pytorch_distributed_training_tpu.parallel import make_sp_mesh
    from pytorch_distributed_training_tpu.schedulers import multi_step_lr

    mk, params, opt, tokens, labels = _lm_fixtures()
    ref_params = _lm_reference(mk, params, opt, tokens, labels)
    mesh = make_sp_mesh(1)  # data=8, sequence=1
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    step = build_lm_train_step(
        mk(SEQ_AXIS), opt, multi_step_lr(0.05, [], 0.1), mesh, donate=False,
        comm=CommConfig(overlap=True, bucket_mb=1e-4),
    )
    s2, _ = step(state, tokens, labels)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_sp_overlap_grad_accum_composition():
    """grad_accum=2 under overlap: micros accumulate locally, ONE bucketed
    reduction per step (DDP no_sync semantics) — same total, reassociated."""
    from pytorch_distributed_training_tpu.engine import TrainState, build_lm_train_step
    from pytorch_distributed_training_tpu.parallel import make_sp_mesh
    from pytorch_distributed_training_tpu.schedulers import multi_step_lr

    mk, params, opt, tokens, labels = _lm_fixtures(seed=3)
    ref_params = _lm_reference(mk, params, opt, tokens, labels)
    mesh = make_sp_mesh(1)
    state = TrainState(params=params, batch_stats={}, opt_state=opt.init(params))
    step = build_lm_train_step(
        mk(SEQ_AXIS), opt, multi_step_lr(0.05, [], 0.1), mesh, donate=False,
        grad_accum=2, comm=CommConfig(overlap=True, bucket_mb=1e-4),
    )
    s2, _ = step(state, tokens, labels)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_zero1_8dev_matches_unsharded():
    """Two ZeRO-1 steps (reduce-scatter + sharded update + all-gather) ==
    two plain full-batch steps.  Two steps exercise the momentum buffers
    living as flat 1/n shards, including SGD's first-step buffer init, and
    the tiny bucket_mb forces multi-bucket padding (size % 8 != 0)."""
    from pytorch_distributed_training_tpu.engine import TrainState, build_lm_train_step
    from pytorch_distributed_training_tpu.parallel import make_sp_mesh
    from pytorch_distributed_training_tpu.schedulers import multi_step_lr

    mk, params, opt, tokens, labels = _lm_fixtures(seed=4)
    ref_params = _lm_reference(mk, params, opt, tokens, labels, steps=2)
    cfg = CommConfig(overlap=True, bucket_mb=1e-3)
    mesh = make_sp_mesh(1)
    z0 = zero1_init(opt, params, cfg, 8)
    state = TrainState(params=params, batch_stats={}, opt_state=z0)
    step = build_lm_train_step(
        mk(SEQ_AXIS), opt, multi_step_lr(0.05, [], 0.1), mesh, donate=False,
        comm=cfg, zero1=True,
    )
    for _ in range(2):
        state, loss = step(state, tokens, labels)
    assert np.isfinite(float(loss))
    assert int(state.opt_state.step) == 2
    # moments really are 1/n-sharded over the data axis
    slot_leaf = state.opt_state.slots[0][0]
    assert not slot_leaf.sharding.is_fully_replicated
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)
