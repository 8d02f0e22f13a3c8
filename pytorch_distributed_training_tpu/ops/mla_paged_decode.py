"""Pallas TPU kernel: one query position a row against the paged LATENT pool.

The sibling of :mod:`.paged_decode` for the leaf of :mod:`.mla`: a cached
position is ONE row of ``rank + rope`` values (the normalised latent ``c``
and the rotated key ``k_pe``) that every head shares, the key of a head is
the whole row and its value the row's first ``rank`` lanes.  The leaf holds
a row in whole lane tiles (:func:`lanes_up`), zeros past ``rank + rope``.
The gather arm of :class:`..ops.mla.MLAttention` copies every row's FULL
block table into a ``[B, L, rank + rope]`` array (at DeepSeek-V2-Lite's
serving widths 94 MB a layer), rewrites the copy through a ``where`` and
reads it three times more.  This kernel reads the pool where it lies: per
row it walks the block table up to the row's own length, 256 positions a
loop step, each live block one ``[bs, width]`` slab brought into VMEM by its
own DMA, double-buffered (the next step's blocks, or the next row's first
ones, are in flight while this step's are scored), and never asks for a
block past the length.

The walk is the kernel's GRID, one grid step a loop step of a row, and the
DMAs are the pipeline's own: the pool is handed over once a block of a loop
step, and each operand's index map reads its block from the
scalar-prefetched tables by way of three short lists (:func:`_walk`,
:func:`_block`): the row's table entry where the entry is live, and where
it is not the block the operand had before, which the pipeline does not
fetch again.  The grid's length is the number of loop steps the batch's
rows have between them, known only on the device.

The mathematics is the absorbed form's (``ops/mla.py::_absorbed``): the
queries arrive on the latent's side (``q_lat = q_nope W_UK``, rounded to the
pool's dtype by the caller as that form rounds it), the rows are taken as
stored, ``scores = (q_lat . c + q_pe . k_pe) * scale`` in float32, the
softmax online (running maximum and sum, float32), the probabilities rounded
to the pool's dtype before ``p . c`` and that product accumulated in
float32.  Positions at or past the length are masked to ``-inf`` and their
VALUE rows zeroed (a block's dead tail, and whatever an earlier step left in
an operand's buffer, may hold a NaN: ``0 * NaN`` must not reach the
contraction, the serving output guard rests on a NaN staying in the row
that made it).

Heads.  All of them read the same row, so a step's blocks are ONE
``[positions, width]`` matrix and every column the matrix unit scores
is one each head wants: there is no other head's column to mask.  The
``rope`` lanes, half a lane tile, are scored in a product of their own, and
the lanes past them in none.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mla_paged_decode", "fits", "lanes_up"]

# positions a loop step scores: 16 blocks of 16 as DeepSeek-V2-Lite is served
_STEP_POSITIONS = 256


def lanes_up(width: int) -> int:
    """``width`` rounded up to whole lane tiles of 128: what a row of the
    leaf takes in the device's memory whatever its logical width, and the
    leaf's last axis.  A last axis of 4.5 tiles (512 + 64) the TPU's
    compiler lays out TRANSPOSED at rest, and every program that scatters
    into the leaf or hands it to this kernel then turns the whole leaf
    round and back, a layer; one of whole tiles it leaves row-major."""
    return -(-width // 128) * 128


def fits(rank: int, block_size: int, dtype) -> bool:
    """Whether Mosaic can read the leaf a block at a time: the latent (the
    value's lanes) whole tiles of 128, and a block's rows whole sublane
    tiles of the dtype (8 rows of 32 bits: 16 of bfloat16)."""
    packing = max(1, 4 // jnp.dtype(dtype).itemsize)
    return rank % 128 == 0 and block_size % (8 * packing) == 0


def _walk(lengths, rows: int, table_blocks: int, bs: int, step_blocks: int):
    """The batch's walk as lists a grid step reads: ``(steps, row [W],
    step [W], source [W * step_blocks])`` with ``W`` the most loop steps the
    tables could hold.  Grid step ``i < steps`` scores loop step ``step[i]``
    of row ``row[i]`` (the rows in order, each row's steps in order).  Its
    operand ``j`` holds entry ``step[i] * step_blocks + j`` of the row's
    table where that entry has live positions; where it has none the
    operand keeps what it read last, and ``source[i * step_blocks + j]`` is
    the grid step that read it (:func:`_block`).  What lies past ``steps``
    is never read.  Comparisons and sums over ``[W, rows]`` and one running
    maximum: no gather, so the lists cost a few microseconds a decode step
    (XLA computes them once for all the layers)."""
    i32 = jnp.int32
    blocks = -(-lengths // bs)  # live blocks a row, at least 1
    steps = -(-blocks // step_blocks)
    ends = jnp.cumsum(steps, dtype=i32)
    starts = ends - steps
    item = jnp.arange(rows * -(-table_blocks // step_blocks), dtype=i32)[:, None]
    own = (item >= starts[None, :]) & (item < ends[None, :])  # the item's row
    pick = lambda per_row: jnp.sum(jnp.where(own, per_row[None, :], 0), axis=1)  # noqa: E731
    row = pick(jnp.arange(rows, dtype=i32))
    step = item[:, 0] - pick(starts)
    entry = step[:, None] * step_blocks + jnp.arange(step_blocks, dtype=i32)
    live = entry < pick(blocks)[:, None]
    # an operand with no live entry yet names grid step 0, whose own entry
    # for it is a (dead) entry of the first row's table: a block of the pool
    source = lax.cummax(jnp.where(live, item, 0), axis=0)
    return ends[-1], row, step, source.reshape(-1)


def _block(tables, row, step, source, i, j, step_blocks: int):
    """The pool block operand ``j`` holds at grid step ``i``: scalars read
    from the prefetched lists (or, in the tests, from arrays)."""
    read_at = source[i * step_blocks + j]
    return tables[row[read_at], step[read_at] * step_blocks + j]


def _dot(a, b, contract):
    """``a`` against ``b`` over the pair of axes ``contract``, accumulated
    in float32: float32 operands at full precision, narrower ones are exact
    in one pass of the matrix unit."""
    return lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        precision=lax.Precision.HIGHEST if a.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    )


def _kernel(tables_ref, lengths_ref, row_ref, step_ref, source_ref, q_lat_ref,
            q_pe_ref, *refs, scale: float):
    *block_refs, o_ref, m_ref, l_ref = refs
    _, heads, rank = q_lat_ref.shape
    rope = q_pe_ref.shape[-1]
    bs = block_refs[0].shape[0]
    step_positions = len(block_refs) * bs
    item = pl.program_id(0)
    row, step = row_ref[item], step_ref[item]
    left = lengths_ref[row] - step * step_positions  # live positions from here

    @pl.when(step == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[row] = jnp.zeros((heads, rank), jnp.float32)

    rows = jnp.concatenate([ref[...] for ref in block_refs], axis=0)
    c, k_pe = rows[:, :rank], rows[:, rank:rank + rope]
    s = (_dot(q_lat_ref[row], c, (1, 1)) + _dot(q_pe_ref[row], k_pe, (1, 1))) * scale
    position = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(position < left, s, -jnp.inf)
    # position 0 is live, so ``m_new`` is finite from a row's first step on
    # and ``exp(-inf - m_new)`` is a plain 0
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    value_position = lax.broadcasted_iota(jnp.int32, c.shape, 0)
    v = jnp.where(value_position < left, c, jnp.zeros_like(c))
    acc = alpha * o_ref[row] + _dot(p.astype(v.dtype), v, (1, 0))
    m_ref[...], l_ref[...] = m_new, l

    # normalised at the row's last step, the running sum until then
    o_ref[row] = acc / jnp.where(left > step_positions, 1.0, l)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_paged_decode(q_lat, q_pe, pool, block_tables, lengths, *, scale: float,
                     interpret: bool = False):
    """Absorbed latent attention of one position a row over the pool.

    ``q_lat [B, H, rank]`` and ``q_pe [B, H, rope]`` in the pool's dtype;
    ``pool [num_blocks, bs, lanes_up(rank + rope)]`` (this call's own rows
    already scattered in; the lanes past ``rank + rope`` are not read);
    ``block_tables [B, T]`` int32, the pool block holding
    positions ``[t * bs, (t + 1) * bs)`` of row ``b``; ``lengths [B]``
    int32, at least 1: row ``b`` reads positions ``[0, lengths[b])`` and no
    block past them.  Returns ``o_lat [B, H, rank]`` float32, the
    probabilities' sum over the latent rows: ``W_UV`` is the caller's.

    Jitted: the layers of a program call ONE traced function, so a decode
    program's set-up pays one trace and one Mosaic lowering of the kernel
    and not one a layer.
    """
    b, heads, rank = q_lat.shape
    _, bs, width = pool.shape
    rope = q_pe.shape[-1]
    if (q_pe.shape[:2] != (b, heads) or width != lanes_up(rank + rope)
            or not q_lat.dtype == q_pe.dtype == pool.dtype):
        raise ValueError(
            f"q_lat {q_lat.shape} {q_lat.dtype} and q_pe {q_pe.shape} "
            f"{q_pe.dtype} do not read pool {pool.shape} {pool.dtype}")
    step_blocks = max(1, min(_STEP_POSITIONS // bs, block_tables.shape[1]))
    steps, row, step, source = _walk(
        lengths, b, block_tables.shape[1], bs, step_blocks)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    def block(j):
        return pl.BlockSpec(
            (None, bs, width),
            lambda i, tables, lengths, row, step, source: (
                _block(tables, row, step, source, i, j, step_blocks), 0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps,),
            in_specs=[whole(b, heads, rank), whole(b, heads, rope)]
            + [block(j) for j in range(step_blocks)],
            out_specs=whole(b, heads, rank),
            scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32)] * 2,
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="mla_paged_decode",
        interpret=interpret,
    )(block_tables, lengths, row, step, source, q_lat, q_pe, *[pool] * step_blocks)
