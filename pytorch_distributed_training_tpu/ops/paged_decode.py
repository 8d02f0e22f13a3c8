"""Pallas TPU kernel: one query position a row against the paged K/V pool.

A decode step asks, for every row of the fixed-width batch, the attention of
ONE query position over the row's own sequence, whose K/V rows lie scattered
over the pool's blocks.  The gather arm of :func:`..ops.attention.paged_attention`
copies every row's FULL block table into a ``[B, L, Hkv, hd]`` array first,
live or not (at the 271M LM's serving widths 20 MiB a leaf, 32 leaves a
step), and then reads the copy three more times.  This kernel reads the pool
where it lies: per row it walks the block table up to the row's own length,
256 positions a loop step, each block one contiguous piece brought into VMEM
by its own DMA (double-buffered: the next step's blocks, or the next row's
first ones, are in flight while this step's are scored), and never touches a
block past the length.

The mathematics is the gather arm's: K and V as stored, the scores, the
softmax (online: running maximum and sum) and the accumulation in float32,
positions beyond the length masked to ``-inf`` and their VALUE rows zeroed
(a block's dead tail, and whatever an earlier step left in the buffer, may
hold a NaN: ``0 * NaN`` must not reach the contraction, the serving output
guard rests on a NaN staying in the row that made it).

Heads.  The pool's rows are ``[Hkv, hd]`` and a block is ``[bs, Hkv, hd]``;
taking one K/V head out of it is a strided read the hardware does not like.
So a step's blocks are read as ONE ``[positions * Hkv, hd]`` matrix and all
``Hkv * G`` query heads are scored against all of its rows in one product;
the columns that pair a query head with another K/V head's row are masked
like dead positions, their probabilities are exactly 0, and the second
product sums over the matching rows alone.  The matrix unit's time is the
K/V rows pushed through it, the same either way; ``G = 1`` (``TransformerLM``)
and ``G = 8`` (``GroupedQueryAttention``) are one kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode", "fits"]

# positions a loop step scores: 16 blocks of 16 in both served families
_STEP_POSITIONS = 256
_VMEM_LIMIT = 64 * 1024 * 1024


def fits(head_dim: int, kv_heads: int, dtype) -> bool:
    """Whether the pool's rows can be read as one matrix by Mosaic: the lane
    axis whole tiles of 128, and the heads of a row whole 32-bit sublanes
    (two bfloat16 heads share one)."""
    packing = max(1, 4 // jnp.dtype(dtype).itemsize)
    return head_dim % 128 == 0 and kv_heads % packing == 0


def _precision(dtype):
    """Products of float32 operands at full precision; narrower operands
    are exact in one pass of the matrix unit."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _weighted_sum(p, v):
    """``p @ v`` accumulated in float32 with the probabilities NOT rounded
    to a narrower pool's dtype (the gather arm of ``TransformerLM`` keeps
    them float32): ``p`` goes through the matrix unit as the two halves
    ``hi + lo`` of its mantissa, stacked as rows of ONE product, so ``v`` is
    pushed once."""
    if v.dtype == jnp.float32:
        return jnp.dot(p, v, precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype).astype(jnp.float32)
    halves = jnp.concatenate([hi, p - hi], axis=0).astype(v.dtype)
    out = jnp.dot(halves, v, preferred_element_type=jnp.float32)
    return out[: p.shape[0]] + out[p.shape[0]:]


def _kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, *, scale: float, group: int):
    rows, heads, head_dim = q_ref.shape
    _, step_blocks, bs, kv_heads, _ = k_buf.shape
    step_positions = step_blocks * bs
    cols = step_positions * kv_heads

    def live_blocks(row, step):
        """Blocks of loop step ``step`` of ``row`` that hold live positions."""
        blocks = pl.cdiv(lengths_ref[row], bs)
        return jnp.minimum(step_blocks, blocks - step * step_blocks)

    def copies(row, step, slot, j):
        block = tables_ref[row, step * step_blocks + j]
        return (
            pltpu.make_async_copy(
                k_hbm.at[block], k_buf.at[slot, j], sems.at[0, slot]),
            pltpu.make_async_copy(
                v_hbm.at[block], v_buf.at[slot, j], sems.at[1, slot]),
        )

    def each_copy(row, step, slot, act):
        """``act`` (start it, or wait for it) on the copy of every live
        block of a loop step: the dead ones are never asked for."""
        def one(j, carry):
            for copy in copies(row, step, slot, j):
                act(copy)
            return carry

        lax.fori_loop(0, live_blocks(row, step), one, 0)

    def start(row, step, slot):
        each_copy(row, step, slot, lambda copy: copy.start())

    def wait(row, step, slot):
        each_copy(row, step, slot, lambda copy: copy.wait())

    # which K/V head a column's row belongs to, and which one a query head
    # reads: the same for every step
    col = lax.broadcasted_iota(jnp.int32, (heads, cols), 1)
    own_head = col % kv_heads == lax.broadcasted_iota(
        jnp.int32, (heads, cols), 0) // group
    col_position = col // kv_heads
    value_position = lax.broadcasted_iota(
        jnp.int32, (cols, head_dim), 0) // kv_heads

    def one_row(row, slot):
        length = lengths_ref[row]
        steps = pl.cdiv(pl.cdiv(length, bs), step_blocks)
        q = q_ref[row]

        def one_step(step, carry):
            m, l, acc, slot = carry
            # what is scored next is fetched while this step is: the row's
            # next blocks, or after its last ones the next row's first
            last = step + 1 == steps
            next_row = jnp.where(last, row + 1, row)
            next_step = jnp.where(last, 0, step + 1)

            @pl.when(next_row < rows)
            def _():
                start(next_row, next_step, 1 - slot)

            wait(row, step, slot)
            left = length - step * step_positions  # live positions from here
            k = k_buf.at[slot].reshape(cols, head_dim)[...]
            v = v_buf.at[slot].reshape(cols, head_dim)[...]
            s = lax.dot_general(
                q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                precision=_precision(q.dtype),
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(own_head & (col_position < left), s, -jnp.inf)
            # position 0 is live for every head, so ``m_new`` is finite from
            # the first step on and ``exp(-inf - m_new)`` is a plain 0
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            v = jnp.where(value_position < left, v, jnp.zeros_like(v))
            acc = alpha * acc + _weighted_sum(p, v)
            return m_new, l, acc, 1 - slot

        m, l, acc, slot = lax.fori_loop(0, steps, one_step, (
            jnp.full((heads, 1), -jnp.inf, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, head_dim), jnp.float32),
            slot,
        ))
        o_ref[row] = (acc / l).astype(o_ref.dtype)
        return slot

    start(0, 0, 0)
    lax.fori_loop(0, rows, one_row, 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode(q, k_pool, v_pool, block_tables, lengths, *, scale: float,
                 interpret: bool = False):
    """Attention of ``q [B, Hkv, G, hd]``, one position a row, over the pool.

    ``k_pool``, ``v_pool`` ``[num_blocks, bs, Hkv, hd]`` (this call's own
    rows already scattered in); ``block_tables [B, T]`` int32, the pool
    block holding positions ``[t * bs, (t + 1) * bs)`` of row ``b``;
    ``lengths [B]`` int32, at least 1: row ``b`` reads positions
    ``[0, lengths[b])`` and no block past them.  Query head ``(h, g)`` reads
    K/V head ``h``.  Returns ``[B, Hkv, G, hd]`` in ``q``'s dtype.

    Jitted: the layers of a program call ONE traced function, so a decode
    program's set-up pays one trace and one Mosaic lowering of the kernel
    and not one a layer.
    """
    b, kv_heads, group, head_dim = q.shape
    _, bs, pool_heads, pool_dim = k_pool.shape
    if (pool_heads, pool_dim) != (kv_heads, head_dim) or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"q {q.shape} does not read pools {k_pool.shape} / {v_pool.shape}")
    heads = kv_heads * group
    step_blocks = max(1, min(_STEP_POSITIONS // bs, block_tables.shape[1]))
    buffer = pltpu.VMEM((2, step_blocks, bs, kv_heads, head_dim), k_pool.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((b, heads, head_dim), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((b, heads, head_dim), lambda i, *_: (0, 0, 0)),
            scratch_shapes=[buffer, buffer, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, head_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        name="paged_decode",
        interpret=interpret,
    )(block_tables, lengths, q.reshape(b, heads, head_dim), k_pool, v_pool)
    return out.reshape(q.shape)
