"""Pallas TPU kernel: flash attention (forward + backward, causal-aware).

The transformer family's hot op.  The naive path (ops/attention.py)
materializes the full ``[B, H, S, S]`` score matrix in f32 — at S=2048
that is 16MB per (batch, head) of HBM traffic each way, and HBM bandwidth
is the TPU's usual bottleneck (PERF.md).  This kernel computes attention
with the online-softmax recurrence: scores live only as one
``[block_q, block_k]`` VMEM tile at a time, each Q/K/V element is read
from HBM once, and nothing quadratic is ever written back.

Shape contract (chosen to match ``dot_product_attention``):
``q, k, v: [BH, S, D] -> out [BH, S, D]`` with heads pre-folded into the
leading dim.  Compute is f32 regardless of input dtype (bf16 in, f32
accumulate, input-dtype out) — same convention as ops/fused_ce.py.

Kernel structure: grid ``(BH, S/block_q)``; each instance holds its Q tile
plus the FULL K/V rows for that (batch, head) in VMEM (S·D f32 ≤ ~2MB for
S=4096, D=128 — the dispatch gate in ops/attention.py falls back to XLA
when the estimate would overflow VMEM) and runs a ``fori_loop`` over K
blocks carrying ``(m, l, acc)`` in registers.

Causal work follows the diagonal (``_diag_walk``), in the resident forward
and the fused backward alike.  K tiles wholly under a Q tile's diagonal run
the loop body with no mask arithmetic at all.  The ``block_q`` x
``block_q`` square on the diagonal is walked in blocks of ``sub`` columns:
each block is ONE step over every row from the block's first down, only the
``sub`` x ``sub`` sub-tile the diagonal crosses builds the iota mask, and the
sub-tiles above it are not visited.  Rows of a Q tile are independent in the
online softmax, so the rows a step completes are written out and leave the
carry.  At S=2048 the kernels compute scores for 1.125x the ``S (S + 1) / 2``
pairs on and under the diagonal (``_causal_pairs``), 1.5x before, and mask
2048 x 256 of them, every visited pair before.  Without ``causal`` the
kernels are one loop over K tiles with no mask, as they always were.

Backward is the standard flash recomputation wired through
``jax.custom_vjp``.  For resident shapes it is ONE fused kernel
(``_dqkv_kernel``, round 5): grid over Q tiles with dK/dV accumulated
in-place in revisited f32 output blocks that stay VMEM-resident across the
whole (batch, head) — ``s``/``p``/``dp``/``ds`` are computed once per tile
pair instead of twice, cutting the backward from 7 to 5 matmuls per tile
and halving its HBM reads (the round-4 quantified D=64 backward MFU gap,
PERF.md).  Shapes whose fused VMEM footprint exceeds the budget fall back
to the original two-pass split: a dQ kernel (grid over Q tiles, loop over
K) and a dK/dV kernel (grid over K tiles, loop over Q, starting at the
diagonal when causal).  All variants recompute ``p = exp(s - lse)`` from
the forward's saved per-row logsumexp; ``delta = rowsum(dO * O)`` is one
cheap XLA elementwise pass outside the kernels.

MXU rate (round 5): for bf16 inputs the kernels feed the dots bf16
operands with f32 accumulation (``preferred_element_type``) instead of
upcasting to f32 first — f32 matmuls run at a fraction of the MXU's bf16
rate (multi-pass decomposition), so the upcast was throttling every score/
output contraction.  bf16xbf16 products are exact in f32 (8-bit
mantissas), so the forward's ``s`` is unchanged up to summation order; the
``p``/``ds`` operands are rounded to bf16 before their dots (the standard
flash-attention convention).  f32 inputs keep full-f32 dots, and
``PDT_FLASH_F32_DOTS=1`` forces them for bf16 too.

Masked scores use a large-negative finite constant (not ``-inf``): every
causal row has at least one valid column, so ``exp(-1e30 - m)`` underflows
to exactly 0 and no NaN can form — the classic ``-inf - -inf`` pitfall.

The kernels run on real TPU or, for the 8-virtual-device CPU test mesh, in
Pallas interpreter mode (``interpret=True``), mirroring ops/fused_ce.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


__all__ = [
    "flash_attention", "flash_attention_lse", "flash_prefill",
    "flash_shapes_ok", "flash_enabled",
]

_NEG = -1e30  # finite mask value; see module docstring
# Preferred tile sizes; ``_pick_block`` halves them until they divide the
# sequence, so any 128-multiple (and tiny interpreter-test shapes) still
# works.  Swept on a TPU v5e (``.bench_flash_tiles.py``: BH 64, bf16,
# causal, calls chained inside one jit, ms a call; "bwd" is forward +
# backward less the forward, so it holds 0.32 ms of XLA around the kernel):
#
#   S 2048             block_q x block_k, sub    D 128  fwd / bwd    D 64
#   before the walk    1024 x 1024 / 512 x 1024  1.006 / 1.799   1.002 / 1.812
#   walk, old tiles    same, 256                 0.758 / 1.855   0.754 / 1.849
#                      same, 512                 0.747 / 1.803   0.742 / 1.795
#   bwd turned         1024 x 512, 256 | 512         - / 1.603 | 1.597
#   ONE Q tile         2048, 128                 0.726 / 2.151
#                      2048, 256                 0.533 / 1.461   0.524 / 1.474
#                      2048, 512                 0.569 / 1.483   0.557 / 1.495
#   S 1024, one tile   1024, 256 (before)        0.193 / 0.468 (0.335 / 0.617)
#   S 4096, fwd only   1024 x 1024 | 512 | 256, sub 256: 1.273 | 1.326 | 1.576
#                      (before 1.520; a 2048-row tile overflows VMEM there)
#
# What the rows say.  D 64 times as D 128: the MXU pads either to 128.  A step
# costs by its COLUMNS far more than by its rows (presumably each K/V block
# is transposed and loaded as MXU weights once a step, whatever the rows):
# so steps are tall, every row under a column block in one product, and a Q
# tile is the whole sequence while that fits VMEM (``_BLOCK_Q_WHOLE``).
# Sub-tiles under 256 lose more to the count of steps than they save in
# pairs; 512 visits 1.25x the causal pairs for 256's 1.125x.  The mask
# itself is cheap (masking every row of a step, not its top sub-tile: 0.537
# / 1.470).  Past ``_BLOCK_Q_WHOLE`` the tiles under the diagonal stay wide:
# a [1024, 256] tile repeats the per-row softmax bookkeeping four times as
# often as a [1024, 1024] one.
# Grid-dimension semantics for Mosaic (ADVICE r3 #1): the batch*heads and
# row-block dims are embarrassingly parallel — marking them lets megacore
# parts (v4/v5p: 2 TensorCores/chip) split the grid; only the dim a VMEM
# scratch carry crosses must stay sequential ("arbitrary").  v5e has one
# core, so this is measured-neutral here and a pod-scale enabler.
def _sem(*dims):
    from jax.experimental.pallas import tpu as _pltpu

    return _pltpu.CompilerParams(dimension_semantics=dims)


_BLOCK_Q = 1024
_BLOCK_K = 1024
# The FUSED backward keeps s/p/dp/ds (plus their bf16 dot copies) live in
# one kernel body — at 1024x1024 those f32 tiles alone are ~16MB and Mosaic
# OOMs the 16MB scoped-VMEM stack (measured: 16.74M at S=2048 D=64 BH=64).
# Halving the Q tile halves every [bq, bk] intermediate (causal sequences
# past ``_BLOCK_Q_WHOLE`` run it at 512 x 512, ``_tiles``).
_BLOCK_Q_FUSED = 512
_BLOCK_K_FUSED = 1024
# Columns of one step of the square on the diagonal, and the longest causal
# sequence that is ONE Q tile (bf16 dots: q, o, K, V, dK, dV and the [2048,
# 256] f32 step tiles fit the 16MB scoped VMEM at D <= 128; at S=4096 a
# 2048-row tile does not).
_BLOCK_DIAG = 256
_BLOCK_Q_WHOLE = 2048
# Tiles for f32-operand dots; see ``_blocks``.
_BLOCK_F32 = 512
# VMEM budget for the RESIDENT kernels' K/V rows (f32): each instance holds
# 2 full [S, D] f32 operands plus tiles/accumulators; stay well under the
# ~16MB scoped VMEM.  Sequences past this budget no longer fall back to the
# naive O(S^2) path (the round-2 ceiling, VERDICT weak #5): they dispatch to
# the STREAMED kernels below, which add the K/V position as an innermost
# grid dimension so Pallas double-buffers [block, D] tiles through VMEM —
# per-instance VMEM is then O(block*D) regardless of S, and single-chip
# sequence length is bounded by HBM, not VMEM.
_VMEM_BYTES = 8 * 1024 * 1024
# lane width for the streamed kernels' m/l scratch rows (Mosaic wants the
# minor dim to be a full 128-lane vector; values are lane-replicated)
_LANES = 128


def _resident_ok(s_len: int, d: int) -> bool:
    """True when the tuned resident-K/V kernels fit scoped VMEM."""
    import os

    if os.environ.get("PDT_FLASH_FORCE_STREAM", "0") != "0":
        return False
    # under the budget, not at it: at 8,192 x 128 bf16 (S * D = 1M exactly,
    # a prefill's largest bucket) Mosaic refuses the resident forward, 18.63
    # MB of scoped VMEM for 16; 6,144 x 128 compiles
    # (tests/test_chip_compile.py asks both)
    return 2 * s_len * d * 4 < _VMEM_BYTES


def _fused_bwd_ok(
    s_len: int, d: int, itemsize: int, bf16_dots: bool, interpret: bool
) -> bool:
    """True when the fused dQ/dK/dV backward fits scoped VMEM: full K/V in
    the input dtype plus full dK/dV f32 accumulator blocks must all stay
    resident.  Shapes at the resident gate's edge (S*D near 1M) exceed this
    and fall back to the split two-pass backward.  On real TPU the fused
    path additionally requires bf16 dots: with f32 operand casts Mosaic's
    live [block_q, block_k] f32 intermediates (s/p/dp/ds at once, ~4MB each
    at the 1024 tiles) overflow the 16MB scoped-VMEM stack — measured OOM
    at S=2048 D=64; bf16-dot tiles fit.  f32 inputs keep the split kernels.
    ``PDT_FLASH_NO_FUSED_BWD=1`` forces the split path (A/B benching and
    the fused-vs-split bitwise oracle)."""
    import os

    if os.environ.get("PDT_FLASH_NO_FUSED_BWD", "0") != "0":
        return False
    if not (bf16_dots or interpret):
        return False
    return 2 * s_len * d * (itemsize + 4) <= _VMEM_BYTES


def flash_shapes_ok(s_len: int, d: int) -> bool:
    """Shape eligibility shared by all flash dispatch gates (ops/attention.py
    local path AND parallel/sequence.py ring inner).  No VMEM term anymore:
    oversized sequences stream K/V tiles instead of falling back to XLA."""
    return s_len >= 128 and s_len % 128 == 0


def flash_enabled() -> bool:
    """Backend + escape-hatch half of the dispatch gates (shared by
    ops.attention._use_flash and parallel.sequence._ring_flash_ok)."""
    import os

    return jax.default_backend() == "tpu" and not os.environ.get(
        "PDT_DISABLE_PALLAS"
    )


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct inheriting ``like``'s varying-mesh-axes type —
    the ONLY vma handling these kernels need: in-kernel constants stay
    unmarked (the Pallas interpreter's state discharge does not propagate
    vma through in-kernel ``pl.ds`` reads either way, which is why the
    shard_map interpreter test runs with ``check_vma=False``; Mosaic
    lowering on real TPU never discharges and is unaffected)."""
    try:
        vma = jax.typeof(like).vma
        if vma:
            return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    except (AttributeError, TypeError):
        pass
    return jax.ShapeDtypeStruct(shape, dtype)


def _diag_walk(block_q, sub):
    """The ``block_q`` x ``block_q`` square on the diagonal as a static
    schedule of steps ``(row0, rows, col0, cols)``, offsets relative to the
    square: a block of ``sub`` columns is taken by every row from its first
    down, in ONE step; what lies above it is not in the schedule.  The
    diagonal crosses only the step's top ``cols`` rows: they alone are
    masked, and they are complete once the step is done."""
    return [(c0, block_q - c0, c0, sub) for c0 in range(0, block_q, sub)]


def _mask_above_diagonal(s):
    """``s``: the scores of one step of :func:`_diag_walk`, ``[rows, cols]``
    with the diagonal through its top ``cols`` x ``cols`` square; the rows
    under that square pass through untouched."""
    rows, cols = s.shape
    top = s[:cols]
    on_or_under = jax.lax.broadcasted_iota(jnp.int32, top.shape, 0) >= (
        jax.lax.broadcasted_iota(jnp.int32, top.shape, 1)
    )
    top = jnp.where(on_or_under, top, _NEG)
    return top if rows == cols else jnp.concatenate([top, s[cols:]], axis=0)


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref=None,
    *, scale, causal, block_q, block_k, sub, bf16_dots,
):
    i = pl.program_id(1)
    s_len = k_ref.shape[1]
    d = q_ref.shape[-1]

    def load_q(rows):
        if bf16_dots:
            return q_ref[0, rows, :]  # bf16 into the MXU; scale folds into s
        return q_ref[0, rows, :].astype(jnp.float32) * scale

    def step(q, col0, cols, carry, masked=False):
        m_prev, l_prev, acc = carry
        kb = k_ref[0, pl.ds(col0, cols), :]
        vb = v_ref[0, pl.ds(col0, cols), :]
        if not bf16_dots:
            kb = kb.astype(jnp.float32)
            vb = vb.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, cols]
        if bf16_dots:
            s = s * scale
        if masked:
            s = _mask_above_diagonal(s)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        pv = p.astype(jnp.bfloat16) if bf16_dots else p
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            pv, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc

    def finish(rows, carry):
        m, l, acc = carry
        o_ref[0, rows, :] = (acc / l[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:  # a forward nobody differentiates has none
            lse_ref[0, rows, 0] = m + jnp.log(l)

    carry = (
        jnp.full((block_q,), _NEG, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
        jnp.zeros((block_q, d), jnp.float32),
    )
    # K tiles wholly under the diagonal (every tile when not causal): all
    # rows of the Q tile at once, no mask arithmetic; a Q tile that is the
    # whole sequence has none, and no loop is built for it
    n_full = (i * block_q) // block_k if causal else s_len // block_k
    if not causal or block_q < s_len:
        q = load_q(slice(None))
        carry = jax.lax.fori_loop(
            0, n_full, lambda j, c: step(q, j * block_k, block_k, c), carry
        )
    if not causal:
        finish(slice(None), carry)
        return
    # the square on the diagonal: rows of a Q tile are independent in the
    # online softmax, so the rows a step completes leave the carry
    for row0, rows, col0, cols in _diag_walk(block_q, sub):
        start = pl.multiple_of(i * block_q + col0, sub)
        carry = step(load_q(slice(row0, row0 + rows)), start, cols, carry, True)
        finish(slice(row0, row0 + cols), tuple(x[:cols] for x in carry))
        carry = tuple(x[cols:] for x in carry)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, scale, causal, block_q, block_k, bf16_dots,
):
    i = pl.program_id(1)
    s_len = k_ref.shape[1]
    nk = s_len // block_k
    q = q_ref[0] if bf16_dots else q_ref[0].astype(jnp.float32)
    do = do_ref[0] if bf16_dots else do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    nj = (
        jnp.minimum(nk, ((i + 1) * block_q + block_k - 1) // block_k)
        if causal
        else nk
    )

    def body(j, dq):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        if not bf16_dots:
            kb = kb.astype(jnp.float32)
            vb = vb.astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            qg = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kg = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qg >= kg, s, _NEG)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dsc = ds.astype(jnp.bfloat16) if bf16_dots else ds
        return dq + jax.lax.dot_general(
            dsc, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    d = q_ref.shape[-1]
    dq = jax.lax.fori_loop(0, nj, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dqkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    *, scale, causal, block_q, block_k, sub, bf16_dots,
):
    """Fused backward: one pass over the (Q tile, K tile) pairs produces dQ,
    dK AND dV.  Grid is (BH, S/block_q) with the Q-tile dim sequential
    ("arbitrary"): dK/dV ride in f32 output blocks whose index map ignores
    the Q-tile index, so Pallas keeps them VMEM-resident across the whole
    (batch, head) and the kernel accumulates into them in place (zeroed at
    the first Q tile).  ``s``/``p``/``dp``/``ds`` are computed once per
    visited tile pair — the split path computes them twice (once in each
    pass).  Causal work follows the diagonal exactly as in ``_fwd_kernel``;
    without ``causal`` the accumulation order over tiles is the split
    kernels' (ascending i for dK/dV, ascending j for dQ, f32 adds), so the
    results are bitwise-equal to the split path there (pinned in
    tests/test_flash_attention.py)."""
    i = pl.program_id(1)
    s_len = k_ref.shape[1]
    d = q_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        dk_ref[...] = jnp.zeros(dk_ref.shape, dk_ref.dtype)
        dv_ref[...] = jnp.zeros(dv_ref.shape, dv_ref.dtype)

    def load(rows):
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        if not bf16_dots:
            q, do = q.astype(jnp.float32), do.astype(jnp.float32)
        return q, do, lse_ref[0, rows, 0], delta_ref[0, rows, 0]

    def step(operands, col0, cols, dq, masked=False):
        q, do, lse, delta = operands
        ks = pl.ds(col0, cols)
        kb = k_ref[0, ks, :]
        vb = v_ref[0, ks, :]
        if not bf16_dots:
            kb = kb.astype(jnp.float32)
            vb = vb.astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if masked:
            s = _mask_above_diagonal(s)
        p = jnp.exp(s - lse[:, None])  # [rows, cols]
        pc = p.astype(jnp.bfloat16) if bf16_dots else p
        dv_ref[0, ks, :] = dv_ref[0, ks, :] + jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dsc = ds.astype(jnp.bfloat16) if bf16_dots else ds
        dk_ref[0, ks, :] = dk_ref[0, ks, :] + jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dq + jax.lax.dot_general(
            dsc, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    n_full = (i * block_q) // block_k if causal else s_len // block_k
    dq = jnp.zeros((block_q, d), jnp.float32)
    if not causal or block_q < s_len:
        tile = load(slice(None))
        dq = jax.lax.fori_loop(
            0, n_full, lambda j, acc: step(tile, j * block_k, block_k, acc), dq
        )
    if not causal:
        dq_ref[0] = dq.astype(dq_ref.dtype)
        return
    for row0, rows, col0, cols in _diag_walk(block_q, sub):
        start = pl.multiple_of(i * block_q + col0, sub)
        dq = step(load(slice(row0, row0 + rows)), start, cols, dq, True)
        dq_ref[0, row0:row0 + cols, :] = dq[:cols].astype(dq_ref.dtype)
        dq = dq[cols:]


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, scale, causal, block_q, block_k, bf16_dots,
):
    j = pl.program_id(1)
    s_len = q_ref.shape[1]
    nq = s_len // block_q
    kb = k_ref[0] if bf16_dots else k_ref[0].astype(jnp.float32)  # [bk, d]
    vb = v_ref[0] if bf16_dots else v_ref[0].astype(jnp.float32)
    # Q tiles strictly before this K tile's first row never attend to it
    i0 = (j * block_k) // block_q if causal else 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        if not bf16_dots:
            q = q.astype(jnp.float32)
            do = do.astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), 0]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), 0]
        s = scale * jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            qg = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kg = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qg >= kg, s, _NEG)
        p = jnp.exp(s - lse[:, None])  # [bq, bk]
        pc = p.astype(jnp.bfloat16) if bf16_dots else p
        dv = dv + jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dsc = ds.astype(jnp.bfloat16) if bf16_dots else ds
        dk = dk + jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    d = q_ref.shape[-1]
    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(i0, nq, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ----------------------------------------------------------------------
# Streamed kernels: K/V (resp. Q) positions ride the innermost grid dim,
# so Pallas' pipeline streams [block, D] tiles through VMEM (automatic
# double-buffered DMA) while the online-softmax state lives in VMEM scratch
# that persists across innermost grid steps (TPU grids execute the minor
# dimension sequentially).  Causal skipping is a `pl.when` on whole blocks
# above the diagonal — the skipped tiles' DMA still streams (static grid),
# so unlike the resident kernels the causal saving is compute-only.
# ----------------------------------------------------------------------
def _fwd_stream_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_k, nk, bf16_dots,
):
    i = pl.program_id(1)  # Q tile (outer)
    j = pl.program_id(2)  # K tile (inner, sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    run = (j * block_k < (i + 1) * block_q) if causal else (j >= 0)

    @pl.when(run)
    def _compute():
        if bf16_dots:
            q = q_ref[0]  # [bq, d] bf16; scale folds into s below
            kb = k_ref[0]
            vb = v_ref[0]
        else:
            q = q_ref[0].astype(jnp.float32) * scale  # [bq, d]
            kb = k_ref[0].astype(jnp.float32)  # [bk, d]
            vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if bf16_dots:
            s = s * scale
        if causal:
            qg = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kg = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qg >= kg, s, _NEG)
        m_prev = m_scr[...]  # [bq, LANES] lane-replicated
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        m_scr[...] = m_new
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1)[:, None]
        pv = p.astype(jnp.bfloat16) if bf16_dots else p
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            pv, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, :, 0] = (m_scr[...] + jnp.log(l))[:, 0]


def _dq_stream_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, causal, block_q, block_k, nk, bf16_dots,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    run = (j * block_k < (i + 1) * block_q) if causal else (j >= 0)

    @pl.when(run)
    def _compute():
        if bf16_dots:
            q, do, kb, vb = q_ref[0], do_ref[0], k_ref[0], v_ref[0]
        else:
            q = q_ref[0].astype(jnp.float32)
            do = do_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = scale * jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            qg = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kg = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qg >= kg, s, _NEG)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dsc = ds.astype(jnp.bfloat16) if bf16_dots else ds
        dq_scr[...] += jax.lax.dot_general(
            dsc, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_stream_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, block_q, block_k, nq, bf16_dots,
):
    j = pl.program_id(1)  # K tile (outer)
    i = pl.program_id(2)  # Q tile (inner, sequential)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    run = ((i + 1) * block_q > j * block_k) if causal else (i >= 0)

    @pl.when(run)
    def _compute():
        if bf16_dots:
            kb, vb, q, do = k_ref[0], v_ref[0], q_ref[0], do_ref[0]
        else:
            kb = k_ref[0].astype(jnp.float32)
            vb = v_ref[0].astype(jnp.float32)
            q = q_ref[0].astype(jnp.float32)
            do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = scale * jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if causal:
            qg = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kg = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qg >= kg, s, _NEG)
        p = jnp.exp(s - lse[:, None])  # [bq, bk]
        pc = p.astype(jnp.bfloat16) if bf16_dots else p
        dv_scr[...] += jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dsc = ds.astype(jnp.bfloat16) if bf16_dots else ds
        dk_scr[...] += jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pick_block(pref: int, s_len: int) -> int:
    """Largest power-of-two fraction of ``pref`` (clamped to ``s_len``)
    that divides ``s_len`` — seq 384 runs on 128-row tiles while seq 2048
    gets the full preferred tile; a short seq becomes one whole-array tile.
    Rejects lengths whose only tiling would violate Mosaic's block rule
    (multi-tile blocks must be 8-aligned; whole-array tiles are exempt)."""
    b = min(pref, s_len)
    while b > 1 and s_len % b:
        b //= 2
    # the loop guarantees b | s_len; Mosaic additionally requires
    # multi-tile blocks to be 8-aligned — whole-array tiles are exempt, so
    # a length with no 8-aligned power-of-two factor falls back to one
    # whole-array tile (legal for ANY length; the auto-dispatch gates
    # require s % 128 == 0 and bound VMEM, so only forced/test calls land
    # here, and an oversized forced call fails at Mosaic compile like any
    # other VMEM overflow)
    if b != s_len and b % 8:
        b = s_len
    return b


def _blocks(s_len: int, bf16_dots: bool):
    """Preferred (block_q, block_k).  Kernels that feed the MXU f32 operands
    (f32 inputs, or the ring path's f32-output mode) get 512x512: at
    1024x1024 the live f32 [bq, bk] tiles of the split backward overflow
    the 16MB scoped-VMEM stack under the v5e compiler (18.29M at S=2048
    D=128 f32; 17.55M at D=64 bf16-in/f32-out) — the smaller tile is the
    largest that compiles at every width tests/test_chip_compile.py asks."""
    pref_q, pref_k = (_BLOCK_Q, _BLOCK_K) if bf16_dots else (
        _BLOCK_F32, _BLOCK_F32
    )
    return _pick_block(pref_q, s_len), _pick_block(pref_k, s_len)


def _blocks_fused(s_len: int):
    return _pick_block(_BLOCK_Q_FUSED, s_len), _pick_block(_BLOCK_K_FUSED, s_len)


def _tiles(s_len: int, bf16_dots: bool, causal: bool, fused: bool = False):
    """``(block_q, block_k, sub)`` of the resident forward, or of the fused
    backward.  Causal: ``block_k`` is the width of a K tile wholly under a Q
    tile's diagonal, ``sub`` that of a column block of the square on it
    (``_diag_walk``).  With bf16 dots a causal sequence of up to
    ``_BLOCK_Q_WHOLE`` is ONE Q tile, so every step has all the rows under
    its column block.  Longer ones keep the Q tile they had and a K tile no
    wider than it, so that the tiles under the diagonal end where the
    square begins (no more VMEM than before: the fused backward at S=3072
    D=64 compiles at 512 x 1024 and at 512 x 512, not at 1024 x 512).
    Not causal: the preferred pair and no ``sub``."""
    bq, bk = _blocks_fused(s_len) if fused else _blocks(s_len, bf16_dots)
    if not causal:
        return bq, bk, None
    if bf16_dots and s_len <= _BLOCK_Q_WHOLE:
        sub = _pick_block(_BLOCK_DIAG, s_len)
        return s_len, sub, sub
    return bq, min(bk, bq), _pick_block(_BLOCK_DIAG, bq)


def _causal_pairs(s_len: int, causal: bool, tiles):
    """``(visited, masked)``: the query-key pairs of one head that the
    resident forward or the fused backward computes scores for at
    ``tiles = (block_q, block_k, sub)``, and those of them that go through
    the mask's iota, compare and select.  The schedule is the kernels' own
    (``_diag_walk`` and the bound on the full tiles); ``S (S + 1) / 2`` pairs
    are needed."""
    block_q, block_k, sub = tiles
    visited = masked = 0
    for i in range(s_len // block_q):
        if not causal:
            visited += block_q * s_len
            continue
        visited += block_q * ((i * block_q) // block_k) * block_k
        for _, rows, _, cols in _diag_walk(block_q, sub):
            visited += rows * cols
            masked += cols * cols
    return visited, masked


@functools.lru_cache(maxsize=None)
def _make(
    causal: bool, interpret: bool, scale: float, out_f32: bool = False,
    stream: bool = False, bf16_dots: bool = False, group: int = 1,
    lse: bool = True,
):
    """Build the custom-VJP'd flash attention for a static (causal, mode,
    scale, out-dtype, stream, dot-precision) tuple — scale is a trace-time
    constant folded into the kernels, and the cache sees only a handful of
    distinct head dims.  ``out_f32`` keeps the block output o in f32
    regardless of input dtype (the ring combine accumulates across blocks
    and must not round each partial to bf16).  ``stream`` selects the
    tile-streaming kernels (VMEM O(block*D) instead of O(S*D); chosen by
    the S·D dispatch in :func:`flash_attention_lse`).  ``bf16_dots`` keeps
    the MXU contractions in bf16 with f32 accumulation (set for bf16
    inputs; see module docstring).  ``group`` > 1: ``k`` and ``v`` hold one
    folded head for every ``group`` of ``q``'s (``[BH / group, S, D]``) and
    the blocks' index maps send query head ``b`` to K/V head ``b // group``;
    consecutive grid steps of a group name the same block, which the
    pipeline does not fetch again.  ``lse=False``: the forward has no
    logsumexp output (``[BH, S, 1]`` float32 lies in 128 lanes on the device:
    as many bytes as a float32 ``o``).  Both are the forward's alone:
    :func:`flash_prefill`."""
    # query head (folded) -> its K/V head; one a head as it always was
    kv = (lambda b: b) if group == 1 else (lambda b: b // group)

    def outputs(q, spec_o, spec_lse):
        """``(out_specs, out_shape)`` of a forward over ``q [BH, S, D]``."""
        specs = [spec_o, spec_lse]
        shapes = [
            _out_struct(q.shape, jnp.float32 if out_f32 else q.dtype, q),
            _out_struct(q.shape[:2] + (1,), jnp.float32, q),
        ]
        return (specs, shapes) if lse else (specs[:1], shapes[:1])

    def _forward_stream(q, k, v):
        from jax.experimental.pallas import tpu as pltpu

        bh, s_len, d = q.shape
        bq, bk = _blocks(s_len, bf16_dots)
        nk = s_len // bk
        kern = functools.partial(
            _fwd_stream_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, nk=nk, bf16_dots=bf16_dots,
        )
        if not lse:  # the scratch refs follow the outputs
            with_lse = kern
            kern = lambda q, k, v, o, *scratch: with_lse(  # noqa: E731
                q, k, v, o, None, *scratch)
        qrow = lambda b, i, j: (b, i, 0)  # noqa: E731
        krow = lambda b, i, j: (kv(b), j, 0)  # noqa: E731
        out_specs, out_shape = outputs(
            q, pl.BlockSpec((1, bq, d), qrow), pl.BlockSpec((1, bq, 1), qrow))
        return pl.pallas_call(
            kern,
            grid=(bh, s_len // bq, nk),
            compiler_params=_sem("parallel", "parallel", "arbitrary"),
            in_specs=[
                pl.BlockSpec((1, bq, d), qrow),
                pl.BlockSpec((1, bk, d), krow),
                pl.BlockSpec((1, bk, d), krow),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd_stream",
        )(q, k, v)

    # jitted: every layer of a model calls this with the same shapes, and a
    # jit is traced and lowered ONCE for them.  On a TPU the lowering of a
    # pallas_call runs Mosaic's passes over the unrolled kernel, which no
    # persistent cache skips: un-jitted, the 32 calls of the 271M LM's step
    # cost every launch 44 s of lowering (4.7 s before the kernels' causal
    # steps were unrolled).  Only the kernels are inside.
    @jax.jit
    def _forward(q, k, v):
        if stream:
            return _forward_stream(q, k, v)
        bh, s_len, d = q.shape
        bq, bk, sub = _tiles(s_len, bf16_dots, causal)
        kern = functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            sub=sub, bf16_dots=bf16_dots,
        )
        row = lambda b, i: (b, i, 0)  # noqa: E731
        full = lambda b, i: (kv(b), 0, 0)  # noqa: E731
        # lse rides as [bh, s, 1]: Mosaic requires the block's last two
        # dims be (8k, 128m) or array-equal — a [bh, s] layout with (1, bq)
        # blocks violates that
        out_specs, out_shape = outputs(
            q, pl.BlockSpec((1, bq, d), row), pl.BlockSpec((1, bq, 1), row))
        return pl.pallas_call(
            kern,
            grid=(bh, s_len // bq),
            compiler_params=_sem("parallel", "parallel"),
            in_specs=[
                pl.BlockSpec((1, bq, d), row),
                pl.BlockSpec((1, s_len, d), full),
                pl.BlockSpec((1, s_len, d), full),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name="flash_fwd",
        )(q, k, v)

    if group > 1 or not lse:
        # the forward alone (the backward kernels read the logsumexp, and a
        # group's dK/dV would be a sum over its query heads)
        return _forward

    @jax.custom_vjp
    def attn(q, k, v):
        return _forward(q, k, v)

    def attn_fwd(q, k, v):
        o, lse = _forward(q, k, v)
        return (o, lse), (q, k, v, o, lse)

    def attn_bwd_stream(res, cts):
        from jax.experimental.pallas import tpu as pltpu

        q, k, v, o, lse = res
        g, g_lse = cts
        bh, s_len, d = q.shape
        bq, bk = _blocks(s_len, bf16_dots)
        nq, nk = s_len // bq, s_len // bk
        delta = jnp.sum(
            g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
        )
        delta = delta - g_lse.astype(jnp.float32)
        qrow = lambda b, i, j: (b, i, 0)  # noqa: E731
        krow = lambda b, i, j: (b, j, 0)  # noqa: E731
        dq = pl.pallas_call(
            functools.partial(
                _dq_stream_kernel, scale=scale, causal=causal, block_q=bq,
                block_k=bk, nk=nk, bf16_dots=bf16_dots,
            ),
            grid=(bh, nq, nk),
            compiler_params=_sem("parallel", "parallel", "arbitrary"),
            in_specs=[
                pl.BlockSpec((1, bq, d), qrow),
                pl.BlockSpec((1, bk, d), krow),
                pl.BlockSpec((1, bk, d), krow),
                pl.BlockSpec((1, bq, d), qrow),
                pl.BlockSpec((1, bq, 1), qrow),
                pl.BlockSpec((1, bq, 1), qrow),
            ],
            out_specs=pl.BlockSpec((1, bq, d), qrow),
            out_shape=_out_struct(q.shape, q.dtype, q),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dq_stream",
        )(q, k, v, g, lse, delta)
        # dK/dV: K tile outer, Q tile inner (index maps swap roles)
        kout = lambda b, j, i: (b, j, 0)  # noqa: E731
        qin = lambda b, j, i: (b, i, 0)  # noqa: E731
        dk, dv = pl.pallas_call(
            functools.partial(
                _dkv_stream_kernel, scale=scale, causal=causal, block_q=bq,
                block_k=bk, nq=nq, bf16_dots=bf16_dots,
            ),
            grid=(bh, nk, nq),
            compiler_params=_sem("parallel", "parallel", "arbitrary"),
            in_specs=[
                pl.BlockSpec((1, bq, d), qin),
                pl.BlockSpec((1, bk, d), kout),
                pl.BlockSpec((1, bk, d), kout),
                pl.BlockSpec((1, bq, d), qin),
                pl.BlockSpec((1, bq, 1), qin),
                pl.BlockSpec((1, bq, 1), qin),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), kout),
                pl.BlockSpec((1, bk, d), kout),
            ],
            out_shape=[
                _out_struct(k.shape, k.dtype, k),
                _out_struct(v.shape, v.dtype, v),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            interpret=interpret,
            name="flash_bwd_dkv_stream",
        )(q, k, v, g, lse, delta)
        return dq, dk, dv

    def attn_bwd(res, cts):
        if stream:
            return attn_bwd_stream(res, cts)
        q, k, v, o, lse = res
        g, g_lse = cts  # cotangents for (o, lse)
        # d(lse)/d(s) = p, so an lse cotangent folds into the kernels as a
        # shift of delta: ds = p * (dp - (delta - g_lse)) — this is what
        # makes the ring-attention combine (which consumes lse) exactly
        # differentiable through the same two backward kernels
        delta = jnp.sum(
            g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
        )  # [bh, s, 1] (3-D for the same Mosaic block rule as lse)
        delta = delta - g_lse.astype(jnp.float32)
        bh, s_len, d = q.shape
        fused = _fused_bwd_ok(
            s_len, d, jnp.dtype(q.dtype).itemsize, bf16_dots, interpret
        )
        dq, dk, dv = _backward(q, k, v, g, lse, delta, fused=fused)
        # the fused kernel hands dK/dV over in f32: the same single
        # end-rounding as the split path's
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)

    # the kernels alone, jitted for the reason given at ``_forward``
    @functools.partial(jax.jit, static_argnames="fused")
    def _backward(q, k, v, g, lse, delta, *, fused):
        bh, s_len, d = q.shape
        bq, bk = _blocks(s_len, bf16_dots)
        row = lambda b, i: (b, i, 0)  # noqa: E731
        full = lambda b, i: (b, 0, 0)  # noqa: E731
        if fused:
            # One pass: dK/dV accumulate into revisited f32 output blocks
            # (VMEM-resident across the Q-tile grid dim, which must
            # therefore be sequential) and are cast to the primal dtype
            # by the caller.
            bq, bk, sub = _tiles(s_len, bf16_dots, causal, fused=True)
            return pl.pallas_call(
                functools.partial(
                    _dqkv_kernel, scale=scale, causal=causal, block_q=bq,
                    block_k=bk, sub=sub, bf16_dots=bf16_dots,
                ),
                grid=(bh, s_len // bq),
                compiler_params=_sem("parallel", "arbitrary"),
                in_specs=[
                    pl.BlockSpec((1, bq, d), row),
                    pl.BlockSpec((1, s_len, d), full),
                    pl.BlockSpec((1, s_len, d), full),
                    pl.BlockSpec((1, bq, d), row),
                    pl.BlockSpec((1, bq, 1), row),
                    pl.BlockSpec((1, bq, 1), row),
                ],
                out_specs=[
                    pl.BlockSpec((1, bq, d), row),
                    pl.BlockSpec((1, s_len, d), full),
                    pl.BlockSpec((1, s_len, d), full),
                ],
                out_shape=[
                    _out_struct(q.shape, q.dtype, q),
                    _out_struct(k.shape, jnp.float32, k),
                    _out_struct(v.shape, jnp.float32, v),
                ],
                interpret=interpret,
                name="flash_bwd",
            )(q, k, v, g, lse, delta)
        dq = pl.pallas_call(
            functools.partial(
                _dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
                bf16_dots=bf16_dots,
            ),
            grid=(bh, s_len // bq),
            compiler_params=_sem("parallel", "parallel"),
            in_specs=[
                pl.BlockSpec((1, bq, d), row),
                pl.BlockSpec((1, s_len, d), full),
                pl.BlockSpec((1, s_len, d), full),
                pl.BlockSpec((1, bq, d), row),
                pl.BlockSpec((1, bq, 1), row),
                pl.BlockSpec((1, bq, 1), row),
            ],
            out_specs=pl.BlockSpec((1, bq, d), row),
            out_shape=_out_struct(q.shape, q.dtype, q),
            interpret=interpret,
            name="flash_bwd_dq",
        )(q, k, v, g, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(
                _dkv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
                bf16_dots=bf16_dots,
            ),
            grid=(bh, s_len // bk),
            compiler_params=_sem("parallel", "parallel"),
            in_specs=[
                pl.BlockSpec((1, s_len, d), full),
                pl.BlockSpec((1, bk, d), row),
                pl.BlockSpec((1, bk, d), row),
                pl.BlockSpec((1, s_len, d), full),
                pl.BlockSpec((1, s_len, 1), full),
                pl.BlockSpec((1, s_len, 1), full),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), row),
                pl.BlockSpec((1, bk, d), row),
            ],
            out_shape=[
                _out_struct(k.shape, k.dtype, k),
                _out_struct(v.shape, v.dtype, v),
            ],
            interpret=interpret,
            name="flash_bwd_dkv",
        )(q, k, v, g, lse, delta)
        return dq, dk, dv

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    *,
    interpret: bool = False,
):
    """Flash attention: ``q, k, v [B, S, H, D] -> [B, S, H, D]``.

    Numerically equivalent to :func:`..ops.attention.dot_product_attention`
    (tested to ~1e-5 in tests/test_flash_attention.py); O(S) memory instead
    of O(S^2).  Heads are folded into the batch dim for the kernels.

    Args:
      interpret: run the kernels in Pallas interpreter mode (for CPU test
        meshes); on TPU leave False.
    """
    return flash_attention_lse(
        q, k, v, causal=causal, sm_scale=sm_scale, interpret=interpret,
        out_f32=False,  # hot path: write o in input dtype (bf16), not f32
    )[0]


def flash_attention_lse(
    q,
    k,
    v,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    *,
    interpret: bool = False,
    out_f32: bool = True,
):
    """Like :func:`flash_attention`, additionally returning the per-row
    logsumexp ``[B, S, H]`` (f32) — the quantity blockwise/ring attention
    needs to combine partial attention results across K/V blocks.  The
    custom VJP is exact for cotangents on BOTH outputs (an lse cotangent
    shifts the backward's delta; see ``attn_bwd``).  ``out_f32`` (default)
    returns o in f32 so a cross-block combine does not round each partial
    to the input dtype."""
    b, s_len, h, _ = q.shape
    out, lse = _folded(q, k, v, causal, sm_scale, interpret, out_f32)
    lse = jnp.transpose(lse.reshape(b, h, s_len), (0, 2, 1))  # [B, S, H]
    return out, lse


def flash_prefill(q, k, v, sm_scale: Optional[float] = None, *,
                  interpret: bool = False):
    """The causal flash FORWARD as an inference call wants it: ``q [B, S, H,
    D]`` over ``k``, ``v [B, S, Hkv, D]`` with ``H`` a multiple of ``Hkv``
    (query head ``h`` reads K/V head ``h // (H / Hkv)`` through the blocks'
    index maps: no repeated copy of K or V exists) ``-> [B, S, H, D]`` in
    ``q``'s dtype, and no logsumexp output.  The kernels, tiles and
    per-shape dispatch are :func:`flash_attention`'s; not differentiable."""
    return _folded(q, k, v, True, sm_scale, interpret, False, lse=False)[0]


def _folded(q, k, v, causal, sm_scale, interpret, out_f32, lse=True):
    """``(out [B, S, H, D], lse [B * H, S, 1] or None)``: heads folded into
    the batch dim for the kernels, ``k`` and ``v`` with their own head
    count."""
    b, s_len, h, d = q.shape
    kv_heads = k.shape[2]
    if h % kv_heads or v.shape[2] != kv_heads:
        raise ValueError(
            f"{h} query heads over {kv_heads} / {v.shape[2]} K/V heads")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    def fold(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * x.shape[2], s_len, d)

    # per-shape dispatch: tuned resident-K/V kernels while they fit scoped
    # VMEM, tile-streaming kernels beyond (lifts the round-2 S<=8k@D=128
    # single-chip ceiling; PDT_FLASH_FORCE_STREAM=1 forces streaming)
    stream = not _resident_ok(s_len, d)
    # bf16-rate MXU dots for all-bf16 inputs (module docstring).  out_f32
    # keeps f32 dots: its cotangent arrives f32 (ring combine path) and the
    # cross-block combine is precision-sensitive by design.
    import os

    bf16_dots = (
        not out_f32
        and all(x.dtype == jnp.bfloat16 for x in (q, k, v))
        and os.environ.get("PDT_FLASH_F32_DOTS", "0") == "0"
    )
    out = _make(
        bool(causal), bool(interpret), float(scale), bool(out_f32),
        bool(stream), bool(bf16_dots), h // kv_heads, bool(lse),
    )(fold(q), fold(k), fold(v))
    return (jnp.swapaxes(out[0].reshape(b, h, s_len, d), 1, 2),
            out[1] if lse else None)
