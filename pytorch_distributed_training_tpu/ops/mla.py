"""Multi-head latent attention (MLA, DeepSeek-V2) with a paged latent cache.

The sibling of :mod:`.attention` for models whose cache row is NOT a K/V
pair: a token leaves one row of ``kv_lora_rank + qk_rope_head_dim`` values a
layer (the normalised latent ``c`` and the rotated key ``k_pe``, shared by
every head) and each head's ``k_nope`` and ``v`` are linear maps of ``c``:

    q = x W_q -> per head q_nope | q_pe;   x W_kv_a -> c | k_pe;  c <- RMSNorm(c)
    c W_kv_b -> per head k_nope | v;       rotary on q_pe and k_pe only
    scores = (q_nope.k_nope + q_pe.k_pe) * softmax_scale,  o = softmax(scores) v

Two forms compute the same function and read the same rows:

  - EXPANDED: ``k_nope`` and ``v`` are rebuilt from the rows a query can see
    and ordinary attention runs over them.  Cheaper when many queries share
    the rows (prefill, the plain forward).
  - ABSORBED: ``W_kv_b``'s two halves move to the query's side,
    ``q_lat = q_nope W_UK^T`` and ``o = (P c) W_UV``, so a query attends over
    the latent rows themselves and nothing of a head's width is ever built
    for a cached position.  One query a row (decode).

The paged path is :func:`..attention.paged_attention`'s contract over ONE
pool leaf ``[pool_rows, lanes_up(rank + rope)]`` a layer in the ``"cache"``
collection (a row in whole lane tiles, zeros past ``rank + rope``: the leaf
is then row-major at rest on a TPU and no program turns it round,
:func:`..mla_paged_decode.lanes_up`): scatter this call's rows (padding
dropped out of bounds), read each row's logical sequence through its block table, mask keys to
``key_pos <= q_pos``, and zero dead rows before they meet any product, so
that a NaN in a row stays with the request that owns it.  Which call reads
the pool how is decided by what the call shows, as there:

  - ONE position a row in the absorbed form on a TPU (the scheduler's
    ``decode_step``), with a leaf the kernel can read
    (:func:`..mla_paged_decode.fits`): the Pallas kernel
    :func:`..mla_paged_decode.mla_paged_decode` walks each row's block
    table up to the row's own length over the leaf where it lies.  No copy
    of a table's rows exists; the masking and the zeroing are the kernel's.
  - More positions a row (whole-prompt prefill through the expanded form,
    the speculative ``verify``), any backend but a TPU, a leaf that does
    not fit: each row's FULL table is gathered, a block at a time, into
    ``[B, L, rank + rope]`` and that copy is masked, zeroed and scored.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from . import mla_paged_decode
from .attention import LATENT_POOL, rms_norm
from .rotary import yarn_inv_freq, yarn_mscale

__all__ = ["MLAttention", "rms_norm", "yarn_inv_freq", "yarn_mscale"]


def _rotate(x, cos, sin):
    """The published layout: pairs ``(x0, x1), (x2, x3), ...`` de-interleaved
    into two halves, then rotated as halves.  ``cos``/``sin`` [..., d/2]
    broadcast against ``x [..., d]``."""
    first = x[..., 0::2].astype(jnp.float32)
    second = x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    ).astype(x.dtype)


class MLAttention(nn.Module):
    """Latent attention over ``x [B, S, D]``; bias-free projections held in
    ``dtype``.  ``rope_scaling`` is the config's dict as a tuple of items
    (flax fields are hashed)."""

    num_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float = 10000.0
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    # serving (the MultiHeadAttention contract): ``decode`` + ``paged`` read
    # and write the shared pool; ``decode`` alone (a contiguous cache a
    # batch) is not written for this family
    decode: bool = False
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    # queries of one call above which the expanded form runs a batch row at
    # a time (its float32 scores are [H, S, L] a row)
    absorb_max_queries: int = 1

    @nn.compact
    def __call__(self, x, positions=None, block_tables=None):
        b, s, dim = x.shape
        h, dn, dr, dv, r = (self.num_heads, self.qk_nope_head_dim,
                            self.qk_rope_head_dim, self.v_head_dim,
                            self.kv_lora_rank)
        init = nn.initializers.lecun_normal()
        wq = self.param("wq", init, (dim, h * (dn + dr)), self.dtype)
        wkv_a = self.param("wkv_a", init, (dim, r + dr), self.dtype)
        kv_norm = self.param("kv_norm", nn.initializers.ones, (r,), self.dtype)
        wkv_b = self.param("wkv_b", init, (r, h * (dn + dv)), self.dtype)
        wo = self.param("wo", init, (h * dv, dim), self.dtype)
        scaling = dict(self.rope_scaling) if self.rope_scaling else None

        if self.decode and not self.paged:
            raise NotImplementedError(
                "latent attention serves through the paged pool only "
                "(serving.scheduler.enabled); the contiguous per-batch cache "
                "of build_generate_fn is not written for it"
            )
        if self.paged and (positions is None or block_tables is None):
            raise ValueError("paged mode needs positions and block_tables")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        valid = positions >= 0  # [B, S]
        safe_pos = jnp.maximum(positions, 0)

        q = jnp.dot(x, wq).reshape(b, s, h, dn + dr)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        ckv = jnp.dot(x, wkv_a)
        c = rms_norm(ckv[..., :r], kv_norm, self.rms_norm_eps)
        k_pe = ckv[..., r:]
        angles = safe_pos.astype(jnp.float32)[..., None] * jnp.asarray(
            yarn_inv_freq(dr, self.rope_theta, scaling))  # [B, S, dr/2]
        # cos/sin carry mscale(factor, mscale) / mscale(factor, mscale_all_dim)
        # and the scores mscale(factor, mscale_all_dim)^2
        amp, m_all = 1.0, 1.0
        if scaling:
            m_all = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
            amp = yarn_mscale(scaling["factor"], scaling["mscale"]) / m_all
        cos, sin = jnp.cos(angles) * amp, jnp.sin(angles) * amp
        q_pe = _rotate(q_pe, cos[:, :, None], sin[:, :, None])
        k_pe = _rotate(k_pe, cos, sin)
        rows = jnp.concatenate([c, k_pe], axis=-1).astype(self.dtype)  # [B,S,r+dr]
        scale = (dn + dr) ** -0.5 * m_all * m_all
        w_kv = wkv_b.reshape(r, h, dn + dv)

        def project(out):  # [B, S, H, dv] float32
            return jnp.dot(out.reshape(b, s, h * dv).astype(self.dtype), wo)

        if self.paged:
            bs, nb = self.kv_block_size, self.kv_num_blocks
            if bs <= 0 or nb <= 0:
                raise ValueError(
                    f"paged mode needs kv_block_size/kv_num_blocks > 0, "
                    f"got {bs}/{nb}"
                )
            pool_rows = nb * bs
            # a fresh pool is zeros and every write writes zeros past
            # ``r + dr``: those lanes are zero for ever, and no arm reads them
            width = mla_paged_decode.lanes_up(r + dr)
            pool = self.variable(
                "cache", LATENT_POOL, jnp.zeros, (pool_rows, width), self.dtype
            )
            blk = jnp.take_along_axis(block_tables, safe_pos // bs, axis=1)
            phys = jnp.where(valid, blk * bs + safe_pos % bs, pool_rows)  # OOB=drop
            filled = pool.value.at[phys.reshape(-1)].set(
                jnp.pad(rows.reshape(b * s, r + dr), ((0, 0), (0, width - r - dr))),
                mode="drop",
            )
            pool.value = filled
            from .flash_attention import flash_enabled

            blocks = filled.reshape(nb, bs, width)  # a bitcast: rows split only
            if (s == 1 <= self.absorb_max_queries and flash_enabled()
                    and mla_paged_decode.fits(r, bs, self.dtype)):
                # one position a row, on a TPU: the kernel reads the leaf
                # where it lies, each row's live blocks and no others.  A
                # padding row (position -1) reads key 0 of its table's first
                # block, as below.
                def over_pool(q_lat, q_pe):
                    return mla_paged_decode.mla_paged_decode(
                        q_lat[:, 0], q_pe[:, 0], blocks, block_tables,
                        safe_pos[:, 0] + 1, scale=scale)[:, None]

                return project(
                    _absorbed(q_nope, q_pe, w_kv, dn, self.dtype, over_pool))
            length = block_tables.shape[1] * bs
            # gathered a BLOCK at a time: a block's rows lie together in
            # the pool, so one table entry moves a [bs, r + dr] slab (row by
            # row the same gather was a third of a decode step; splitting
            # only the row axis keeps the view free of a relayout)
            keys = blocks[block_tables, :, :r + dr].reshape(
                b, length, r + dr
            )  # [B, L, r+dr] in logical-position order
            key_pos = jnp.arange(length, dtype=jnp.int32)
        else:
            keys, key_pos = rows, jnp.arange(s, dtype=jnp.int32)
        # padding queries keep key 0 live so that their softmax stays finite
        live = key_pos[None, None, :] <= safe_pos[:, :, None]  # [B, S, L]
        # a row dead for a row's every query is zeroed before any product:
        # recycled blocks keep an evicted request's contents and padded table
        # entries alias block 0, and 0 * NaN would carry a NaN across requests
        # (causal, so live for any query of the row = live for its last one)
        keys = jnp.where(live.any(axis=1)[:, :, None], keys, 0)

        if s <= self.absorb_max_queries:
            # the CPU's dot thunk has no bfloat16 x bfloat16 = float32 for
            # these shapes: there the operands are upcast, which rounds nothing
            dt = keys.dtype if jax.default_backend() == "tpu" else jnp.float32
            out = _absorbed(
                q_nope, q_pe, w_kv, dn, dt,
                functools.partial(_over_rows, keys.astype(dt), live, r, scale))
        else:
            out = jax.lax.map(
                lambda a: _expanded(*a, w_kv, r, dn, scale),
                (q_nope, q_pe, keys, live),
            )
        return project(out)


def _softmax(scores, live):
    """Causal softmax in float32 over the last axis; ``live [..., S, L]``
    broadcasts over the heads in front."""
    return jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)


def _expanded(q_nope, q_pe, keys, live, w_kv, r, dn, scale):
    """One batch row: ``q_* [S, H, d]``, ``keys [L, r+dr]``, ``live [S, L]``."""
    f32 = jnp.float32
    c, k_pe = keys[:, :r], keys[:, r:]
    kv = jnp.einsum("lr,rhd->lhd", c, w_kv)  # [L, H, dn+dv], the cache's dtype
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (
        jnp.einsum("shd,lhd->hsl", q_nope.astype(f32), k_nope.astype(f32))
        + jnp.einsum("shd,ld->hsl", q_pe.astype(f32), k_pe.astype(f32))
    ) * scale
    p = _softmax(scores, live[None])
    return jnp.einsum("hsl,lhd->shd", p, v.astype(f32))


def _absorbed(q_nope, q_pe, w_kv, dn, dt, attend):
    """All rows at once, ``q_* [B, S, H, d]``: ``W_kv_b``'s two halves on the
    query's side, operands in ``dt``, products accumulated in float32.
    ``attend(q_lat [B, S, H, r], q_pe)`` is the attention over the latent
    rows themselves, ``o_lat [B, S, H, r]`` float32: :func:`_over_rows` of a
    gathered copy, or the kernel over the pool."""
    f32 = jnp.float32
    w_uk, w_uv = w_kv[..., :dn].astype(dt), w_kv[..., dn:].astype(dt)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(dt), w_uk,
                       preferred_element_type=f32)
    o_lat = attend(q_lat.astype(dt), q_pe.astype(dt))
    return jnp.einsum("bshr,rhd->bshd", o_lat.astype(dt), w_uv,
                      preferred_element_type=f32)


def _over_rows(keys, live, r, scale, q_lat, q_pe):
    """``keys [B, L, r+dr]`` taken as they are stored (no float32 copy of
    them is made: at 32 rows of 2,560 positions that copy was the step's
    largest operation), scores, softmax and sums in float32."""
    f32 = jnp.float32
    c, k_pe = keys[..., :r], keys[..., r:]
    scores = (
        jnp.einsum("bshr,blr->bhsl", q_lat, c, preferred_element_type=f32)
        + jnp.einsum("bshd,bld->bhsl", q_pe, k_pe, preferred_element_type=f32)
    ) * scale
    p = _softmax(scores, live[:, None])
    return jnp.einsum("bhsl,blr->bshr", p.astype(keys.dtype), c,
                      preferred_element_type=f32)
