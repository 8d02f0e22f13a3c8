"""Pallas flash attention vs the naive reference (interpreter mode on CPU).

Same evidence pattern as the fused-CE kernel tests: the kernel must match
the XLA einsum attention (forward AND backward, causal and full) on the
CPU test mesh via the Pallas interpreter — including inside ``shard_map``,
where the vma typing exercised by the production call site
(engine/sp_steps runs the model under shard_map) applies.  Real-TPU
numbers are recorded in PERF.md.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_distributed_training_tpu.ops.attention import dot_product_attention
from pytorch_distributed_training_tpu.ops.flash_attention import flash_attention

B, S, H, D = 2, 256, 4, 32


def _qkv(seed=0, s=S, b=B, h=H, d=D):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
        for _ in range(3)
    )


def _assert_matches_naive(q, k, v, causal, atol=2e-5, gatol=5e-5):
    """Forward and dq/dk/dv of the kernels (interpreter) against the naive
    path on the same inputs, upcast to f32 where they are bf16."""
    f32 = tuple(x.astype(jnp.float32) for x in (q, k, v))
    ref = dot_product_attention(*f32, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=atol
    )

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v).astype(jnp.float32)))

    g_ref = jax.grad(
        loss(lambda q, k, v: dot_product_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(*f32)
    g_fa = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, causal=causal, interpret=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(g_ref, g_fa, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b, np.float32), np.asarray(a), atol=gatol, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.quick
def test_forward_matches_naive(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_naive(causal):
    """dq/dk/dv through the custom VJP == autodiff of the naive path (the
    sin() wrapper makes the cotangent non-constant so all three grads are
    nontrivial)."""
    q, k, v = _qkv(seed=1)

    def f(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    g_ref = jax.grad(
        f(lambda q, k, v: dot_product_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_fa = jax.grad(
        f(lambda q, k, v: flash_attention(q, k, v, causal=causal, interpret=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(g_ref, g_fa, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-5, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [False, True])
def test_multi_k_block_online_softmax(causal):
    """S=1536 in f32: three 512 x 512 tiles a side, so the online-softmax
    rescaling across K blocks (m/l carry), the full tiles under the
    diagonal and the two column blocks of each square on it all run (the
    fused backward takes the same tiles here) — forward AND all three grads
    vs the naive reference (the r2 review caught the 512 tile silently
    single-blocking the old S=384 version of this test)."""
    q, k, v = _qkv(seed=2, s=1536)
    _assert_matches_naive(q, k, v, causal=causal)


def test_halved_tile_fallback():
    """S=384: both tiles become one whole-array 384 tile (1024 and 512 do not
    divide it), which IS the diagonal, walked in three chunks of 128 rows
    (256 does not divide 384) — forward and all three grads stay exact."""
    q, k, v = _qkv(seed=2, s=384)
    _assert_matches_naive(q, k, v, causal=True)


def test_bf16_inputs():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(seed=3))
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )


def test_inside_shard_map_with_grad():
    """The production context (engine/sp_steps): kernel under shard_map
    with batch sharded over the mesh — forward and grads must equal the
    single-device naive computation (vma typing + psum-free locality)."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
    q, k, v = _qkv(seed=4)

    def local(q, k, v):
        def loss(q):
            return jnp.sum(
                jnp.sin(flash_attention(q, k, v, causal=True, interpret=True))
            )

        l, g = jax.value_and_grad(loss)(q)
        return jax.lax.psum(l, "data"), g

    # check_vma=False: the Pallas INTERPRETER's state discharge does not
    # propagate varying-axes through the kernels' in-kernel pl.ds reads
    # (mixed-vma dynamic_slice errors); real-TPU Mosaic lowering never
    # discharges, so the production shard_map paths (engine/sp_steps) are
    # unaffected — this flag is test-harness-only.
    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P(), P("data")),
        check_vma=False,
    )
    loss_sh, grad_sh = sharded(q, k, v)

    def ref_loss(q):
        return jnp.sum(jnp.sin(dot_product_attention(q, k, v, causal=True)))

    loss_ref, grad_ref = jax.value_and_grad(ref_loss)(q)
    np.testing.assert_allclose(float(loss_sh), float(loss_ref), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grad_sh), np.asarray(grad_ref), atol=5e-5
    )


def test_block_picker_edge_lengths():
    """Ragged lengths run as one whole-array tile — both below the
    preferred tile (s=200) and above it with no 8-aligned power-of-two
    factor (s=514 = 2x257): every length is legal, only the auto-dispatch
    gates (s % 128) decide what runs in production."""
    for s in (200, 514):
        q, k, v = _qkv(seed=5, s=s)
        ref = dot_product_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, err_msg=f"s={s}"
        )


def test_dispatch_gate_cpu_and_override():
    """On the CPU backend the auto path must stay XLA (impl=None), and the
    explicit impl='xla' override must always work."""
    from pytorch_distributed_training_tpu.ops.attention import _use_flash

    q, k, v = _qkv(seed=6)
    assert not _use_flash(q)  # cpu backend
    out = dot_product_attention(q, k, v, causal=True, impl="xla")
    assert out.shape == q.shape
    with pytest.raises(ValueError, match="impl"):
        dot_product_attention(q, k, v, impl="pallas")


# ----------------------------------------------------------------------
# Streamed kernels (round-3: K/V tiles ride the innermost grid dim, VMEM
# O(block*D) — lifts the resident kernels' S<=8k@D=128 ceiling).  Forced
# via PDT_FLASH_FORCE_STREAM so CPU-sized shapes exercise the streaming
# code path; real-TPU S=16384, D=128 fwd+bwd evidence is in PERF.md.
# ----------------------------------------------------------------------
@pytest.fixture
def force_stream(monkeypatch):
    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    monkeypatch.setenv("PDT_FLASH_FORCE_STREAM", "1")
    fa._make.cache_clear()
    yield
    fa._make.cache_clear()


@pytest.mark.parametrize("causal", [False, True])
def test_streamed_forward_matches_naive(causal, force_stream):
    # s=1024 with (256, 512) tiles: 4 Q tiles x 2 K tiles, so the streaming
    # carry crosses a real K-tile boundary (online-softmax state in scratch)
    q, k, v = _qkv(seed=7, s=1024)
    ref = dot_product_attention(q, k, v, causal=causal, impl="xla")
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_streamed_backward_matches_naive(causal, force_stream):
    q, k, v = _qkv(seed=8, s=1024)

    def f(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    g_ref = jax.grad(
        f(lambda q, k, v: dot_product_attention(q, k, v, causal=causal, impl="xla")),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_fa = jax.grad(
        f(lambda q, k, v: flash_attention(q, k, v, causal=causal, interpret=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(g_ref, g_fa, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-5, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [False, True])
def test_streamed_matches_resident_bitwise(causal, force_stream):
    """Same blocks, same f32 accumulate order => without a mask the streamed
    kernels are not just close to the resident ones, they are IDENTICAL (the
    grid-dim loop visits K tiles in the same order as the in-kernel
    fori_loop).  Causal, the resident forward walks its one 512 tile, which
    IS the diagonal, in two column blocks where the streamed one takes it
    whole: other partial sums per row, so equal to f32 rounding only (bitwise
    until PR 28)."""
    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    q, k, v = _qkv(seed=9, s=512)
    o_stream = np.asarray(flash_attention(q, k, v, causal=causal, interpret=True))
    fa._make.cache_clear()
    import os

    del os.environ["PDT_FLASH_FORCE_STREAM"]
    o_res = np.asarray(flash_attention(q, k, v, causal=causal, interpret=True))
    if causal:
        np.testing.assert_allclose(o_stream, o_res, rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_array_equal(o_stream, o_res)


def test_streamed_lse_grad(force_stream):
    """The lse output and its cotangent path (ring-attention's combine
    consumes lse) stay exact through the streamed backward kernels."""
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        flash_attention_lse,
    )

    q, k, v = _qkv(seed=10, s=1024)

    def f_flash(q, k, v):
        o, lse = flash_attention_lse(q, k, v, causal=True, interpret=True)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))

    def f_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        mask = jnp.tril(jnp.ones((q.shape[1], q.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B,H,S]
        p = jnp.exp(s - lse[..., None])
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(jnp.transpose(lse, (0, 2, 1))))

    g_fa = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ref, g_fa, "qkv"):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-5, err_msg=f"d{name}"
        )


# ----------------------------------------------------------------------
# Fused backward (round-5: one pass produces dq/dk/dv, dK/dV accumulated
# in revisited VMEM-resident f32 output blocks — the split two-pass path
# remains for shapes whose fused footprint exceeds VMEM and as the
# PDT_FLASH_NO_FUSED_BWD escape hatch).
# ----------------------------------------------------------------------
@pytest.fixture
def split_bwd(monkeypatch):
    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    monkeypatch.setenv("PDT_FLASH_NO_FUSED_BWD", "1")
    fa._make.cache_clear()
    yield
    fa._make.cache_clear()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_bwd_matches_split_bitwise(causal, dtype, split_bwd, monkeypatch):
    """Fused and split backwards accumulate the same per-tile f32 values in
    the same ascending order with one end-rounding each => bitwise-equal
    grads without a mask, in both dot-precision modes (s=1536 runs multiple
    tile pairs).  The split path is pinned to the fused path's tile pair:
    tile geometry determines f32 summation ORDER, so bitwise equality is
    only defined at matching tiles (the production defaults differ — fused
    halves the Q tile for scoped VMEM; cross-tile agreement is covered by
    the naive-reference tolerances).  Causal, the fused kernel follows the
    diagonal (one Q tile of 1536 rows, column blocks of 256) and the split
    pair, which runs in no cell, still masks whole tiles: the same pairs in
    other partial sums, so the two causal cases hold to the rounding of one
    result — f32 to 1e-5, bf16 to one ulp (bitwise until PR 28)."""
    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK_Q", fa._BLOCK_Q_FUSED)
    monkeypatch.setattr(fa, "_BLOCK_K", fa._BLOCK_K_FUSED)
    q, k, v = (x.astype(dtype) for x in _qkv(seed=11, s=1536))

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: jnp.sum(
                jnp.sin(
                    flash_attention(q, k, v, causal=causal, interpret=True)
                    .astype(jnp.float32)
                )
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    g_split = grads(q, k, v)
    fa._make.cache_clear()
    import os

    del os.environ["PDT_FLASH_NO_FUSED_BWD"]
    # guard against vacuous split==split: the second run must actually
    # take the fused kernel
    calls = []
    real_kernel = fa._dqkv_kernel

    def counting_kernel(*args, **kwargs):
        calls.append(1)
        return real_kernel(*args, **kwargs)

    monkeypatch.setattr(fa, "_dqkv_kernel", counting_kernel)
    g_fused = grads(q, k, v)
    assert calls, "fused path was not taken"
    for a, b, name in zip(g_split, g_fused, "qkv"):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if causal:
            # bf16: one ulp, and near zero the f32 sums' own noise (both
            # sides are 6e-3 to 4e-2 from the naive f32 path)
            rtol, atol = (2.0 ** -7, 5e-4) if dtype == jnp.bfloat16 else (1e-5, 1e-5)
            np.testing.assert_allclose(a, b, rtol=rtol, atol=atol, err_msg=f"d{name}")
        else:
            np.testing.assert_array_equal(a, b, err_msg=f"d{name}")


def test_fused_bwd_gate():
    """The fused path must bow out for shapes whose K/V + f32 dK/dV blocks
    exceed the VMEM budget (they fall back to the split resident or
    streamed kernels)."""
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        _fused_bwd_ok,
    )

    ok = lambda s, d, i: _fused_bwd_ok(s, d, i, bf16_dots=True, interpret=False)  # noqa: E731
    assert ok(2048, 64, 2)  # the LM bench shape, bf16
    assert ok(8192, 64, 2)
    assert not ok(16384, 64, 2)  # resident edge: split path
    assert not ok(8192, 128, 4)
    # on real TPU, f32 dots overflow the fused kernel's scoped VMEM
    assert not _fused_bwd_ok(2048, 64, 4, bf16_dots=False, interpret=False)
    assert _fused_bwd_ok(2048, 64, 4, bf16_dots=False, interpret=True)


def test_bf16_dots_grad_close_to_f32_dots():
    """The bf16-MXU-rate dot path must track the f32-dot path on bf16
    inputs (products are exact; p/ds round to bf16 before their dots) —
    and PDT_FLASH_F32_DOTS must actually flip the path (observable via
    a numeric difference in p@v rounding)."""
    import os

    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(seed=12, s=512))

    def run():
        fa._make.cache_clear()
        return jax.value_and_grad(
            lambda q: jnp.sum(
                flash_attention(q, k, v, causal=True, interpret=True).astype(
                    jnp.float32
                )
            )
        )(q)

    o_bf, g_bf = run()
    os.environ["PDT_FLASH_F32_DOTS"] = "1"
    try:
        o_f32, g_f32 = run()
    finally:
        del os.environ["PDT_FLASH_F32_DOTS"]
        fa._make.cache_clear()
    np.testing.assert_allclose(float(o_bf), float(o_f32), rtol=2e-2)
    np.testing.assert_allclose(
        np.asarray(g_bf, np.float32), np.asarray(g_f32, np.float32), atol=2e-1
    )
    # the flag must actually flip the path: p rounds to bf16 before the
    # p@v dot only on the bf16-dots side, so bit-identical grads mean the
    # escape hatch silently died (the cb874f2 bug class)
    assert not np.array_equal(
        np.asarray(g_bf, np.float32), np.asarray(g_f32, np.float32)
    )


def test_gate_no_longer_caps_sequence():
    """flash_shapes_ok must accept sequences past the old resident-VMEM
    ceiling (S=8192@D=128) — those dispatch to the streamed kernels now."""
    from pytorch_distributed_training_tpu.ops.flash_attention import (
        flash_shapes_ok,
    )

    assert flash_shapes_ok(16384, 128)
    assert flash_shapes_ok(65536, 128)
    assert not flash_shapes_ok(100, 64)  # still requires s % 128 == 0


# ----------------------------------------------------------------------
# The causal walk (PR 28): the resident forward and the fused backward take
# the K tiles wholly under a Q tile's diagonal without a mask, walk the
# square on the diagonal in sub-tiles and do not visit what lies above it.
# ----------------------------------------------------------------------
@pytest.fixture
def small_tiles(monkeypatch):
    """Main tiles of 256 (fused backward 256 x 128) over sub-tiles of 64, and
    no whole-sequence Q tile: a sequence of 512 then has a full tile, crossed
    sub-tiles and skipped ones in both kernels, at sizes the interpreter runs
    in seconds."""
    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    sizes = {"_BLOCK_Q": 256, "_BLOCK_K": 256, "_BLOCK_F32": 256,
             "_BLOCK_Q_FUSED": 256, "_BLOCK_K_FUSED": 128, "_BLOCK_DIAG": 64,
             "_BLOCK_Q_WHOLE": 0}
    for name, size in sizes.items():
        monkeypatch.setattr(fa, name, size)
    fa._make.cache_clear()
    yield fa
    fa._make.cache_clear()


def _walk_qkv(seed, s, d, dtype):
    return tuple(x.astype(dtype) for x in _qkv(seed, s, b=1, h=2, d=d))


# bf16: the kernels round p and ds to bf16 before their products and the
# gradients once at the end; a left-out or doubly counted sub-tile is an
# error of order 0.1 to 1 at these shapes
_WALK_TOL = {jnp.float32: (2e-5, 5e-5), jnp.bfloat16: (2e-2, 6e-2)}


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_causal_walk_matches_naive(small_tiles, dtype, d):
    """Two main tiles a side: Q tile 1 has a full K tile under it (no mask),
    each square on the diagonal four column blocks of crossed and skipped
    sub-tiles; the fused backward (256 x 128) takes two full tiles there.
    Forward and all three grads, both dot precisions."""
    fa = small_tiles
    assert fa._tiles(512, True, True) == (256, 256, 64)
    assert fa._tiles(512, True, True, fused=True) == (256, 128, 64)
    q, k, v = _walk_qkv(13, 512, d, dtype)
    atol, gatol = _WALK_TOL[dtype]
    _assert_matches_naive(q, k, v, causal=True, atol=atol, gatol=gatol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_tile_sequence_is_the_diagonal(small_tiles, dtype):
    """S = one main tile: no full tile exists, the only tile is the square on
    the diagonal (four chunks here; at the module's sizes a sequence of 128
    is one masked step, which test_forward_matches_naive's S=256 covers)."""
    q, k, v = _walk_qkv(14, 256, 64, dtype)
    atol, gatol = _WALK_TOL[dtype]
    _assert_matches_naive(q, k, v, causal=True, atol=atol, gatol=gatol)


def _cover(fa, s_len, tiles):
    """How often the walk's schedule visits and masks each query-key pair:
    two [S, S] count arrays built from the kernels' own ``_diag_walk`` and
    their bound on the full tiles."""
    block_q, block_k, sub = tiles
    visited = np.zeros((s_len, s_len), int)
    masked = np.zeros((s_len, s_len), int)
    for i in range(s_len // block_q):
        r0 = i * block_q
        visited[r0:r0 + block_q, :(r0 // block_k) * block_k] += 1
        for row0, rows, col0, cols in fa._diag_walk(block_q, sub):
            visited[r0 + row0:r0 + row0 + rows, r0 + col0:r0 + col0 + cols] += 1
            masked[r0 + row0:r0 + row0 + cols, r0 + col0:r0 + col0 + cols] += 1
    return visited, masked


@pytest.mark.parametrize(
    "s_len,tiles",
    [(2048, "fwd"), (2048, "fused"), (384, "fwd"), (1536, "fused"),
     (4096, "fwd"), (3072, "fused"), (1024, "f32"),
     (512, (256, 256, 64)), (512, (256, 128, 64)), (512, (512, 128, 128))],
)
def test_walk_covers_the_causal_pairs_once(s_len, tiles):
    """No pair on or under the diagonal is left out or visited twice, the
    mask is built only where the diagonal crosses a sub-tile, and
    ``_causal_pairs`` counts what the schedule does."""
    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    if isinstance(tiles, str):
        tiles = fa._tiles(s_len, tiles != "f32", True, fused=tiles == "fused")
    visited, masked = _cover(fa, s_len, tiles)
    causal = np.tril(np.ones((s_len, s_len), int))
    assert visited.max() == 1 and (visited >= causal).all()
    sub = tiles[2]
    on_diagonal = np.kron(np.eye(s_len // sub, dtype=int), np.ones((sub, sub), int))
    np.testing.assert_array_equal(masked, on_diagonal)
    # what is visited above the diagonal lies inside the crossed sub-tiles
    assert ((visited - causal) <= on_diagonal).all()
    assert fa._causal_pairs(s_len, True, tiles) == (visited.sum(), masked.sum())
    assert fa._causal_pairs(s_len, False, tiles) == (s_len * s_len, 0)


def test_walk_visits_at_most_a_quarter_more_than_causal_at_2048():
    """The cell's shape: both kernels visited 1.5 x the causal pairs before
    the walk (three 1024 x 1024 tiles, six of 512 x 1024) and masked all of
    them; now at most 1.25 x, and the mask touches the diagonal's sub-tiles
    alone."""
    from pytorch_distributed_training_tpu.ops import flash_attention as fa

    causal = 2048 * 2049 // 2
    for fused in (False, True):
        tiles = fa._tiles(2048, True, True, fused=fused)
        visited, masked = fa._causal_pairs(2048, True, tiles)
        assert causal <= visited <= 1.25 * causal
        assert masked == 2048 * tiles[2]


def _kernel_primitives(fn, *args):
    """Names of the primitives inside the Pallas kernels of ``fn``'s jaxpr."""
    names = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside:
                names.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inside or eqn.primitive.name == "pallas_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return names


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_non_causal_kernels_build_no_mask_and_one_loop(dtype):
    """``causal=False`` callers (ViT, the ring's past blocks) keep the loops
    they had: one loop over K tiles a kernel, no iota, compare or select, no
    unrolled sub-tile steps — the causal kernels of the same shapes do."""
    q, k, v = (x.astype(dtype) for x in _qkv(seed=15, s=1536))

    def grads(causal):
        return lambda q, k, v: jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=causal, interpret=True).astype(jnp.float32)
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    plain = _kernel_primitives(grads(False), q, k, v)
    assert not {"iota", "select_n", "ge"} & set(plain)
    loops = [n for n in plain if n in ("while", "scan")]
    # forward + fused backward (the interpreter admits f32 to the fused one)
    assert len(loops) == 2 and plain.count("dot_general") == 2 + 5
    walked = _kernel_primitives(grads(True), q, k, v)
    assert {"iota", "select_n"} <= set(walked)
    assert walked.count("dot_general") > plain.count("dot_general")
