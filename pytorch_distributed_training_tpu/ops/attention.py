"""Multi-head attention with pluggable sequence-parallel strategies.

An addition beyond the reference (its zoo is ResNets only, SURVEY.md §5.7 —
no attention anywhere); this op is the compute core of the transformer
family in :mod:`..models.vit` and the consumer of the sequence-parallel
collectives in :mod:`..parallel.sequence`.

Strategy selection is static (trace-time):

  - ``seq_axis=None``         — plain full attention on the local shard
                                (sequence replicated or short),
  - ``seq_impl="ring"``       — ring attention over the ``seq_axis`` mesh
                                axis (O(S_local) memory, ICI neighbor DMA),
  - ``seq_impl="ulysses"``    — all-to-all head-parallel attention.

All strategies compute the same math (softmax(QK^T/sqrt(d))V) — tested
equivalent in tests/test_sequence_parallel.py.

The paged pool (serving/kv_pool.py) holds rows of whatever the layer's
attention stores, one row a token a layer, in the ``"cache"`` collection.
Two layouts exist: a K/V PAIR (:class:`MultiHeadAttention` and
:class:`GroupedQueryAttention`: two leaves ``[pool_rows, Hkv, hd]``, read
through the one :func:`paged_attention`) and a LATENT row
(:class:`..ops.mla.MLAttention`: one leaf ``[pool_rows, rank + rope]``
shared by every head, its rows held in whole lane tiles).  Serving code
finds the leaves through :func:`pool_leaf_role`, which the attention
modules answer, never by spelling a leaf's name itself.

A WINDOW layer (:class:`GroupedQueryAttention` with ``window``) keeps no row
in the pool: a query reads its ``window`` newest keys and nothing older, so
its K/V rows live in a RING a slot, two leaves ``[slots * window, Hkv, hd]``
among the slot-addressed leaves (``STATE_LEAVES``), position ``p`` of slot
``s`` at row ``s * window + p % window``.  The pool's block table, the
admission and the frees know nothing of it: a slot's ring is a fresh row's
from its first position on.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from ..parallel.sequence import ring_attention, ulysses_attention
from ..utils.vma import varying_axes_of
from .rotary import rotate_halves

__all__ = [
    "GroupedQueryAttention", "MultiHeadAttention", "dot_product_attention",
    "is_state_leaf", "paged_attention", "pool_leaf_role", "rms_norm",
    "whole_prompt_flash", "window_attention",
]

# The paged pool's leaves, under the names the attention modules give them.
# A SCORED leaf is one a query's logits are computed against: a NaN in one of
# its rows makes the logits of the row's owner NaN and is masked to -inf for
# every other reader (scheduler ``_corrupt_pool_rows`` rests on that).
KEY_POOL, VALUE_POOL, LATENT_POOL = "k_pool", "v_pool", "latent_pool"
_POOL_ROLES = {KEY_POOL: "scored", VALUE_POOL: "value", LATENT_POOL: "scored"}
# The cache tree's OTHER kind of leaf: ``[slots, ...]``, one entry a sequence
# (:mod:`..ops.kda`: the delta-rule state and the convolution's last rows;
# :mod:`..ops.mamba2`: the state-space state and its convolution's rows;
# :mod:`..ops.gated_delta`: the scalar-decay delta rule's rectangular state
# and its convolution's rows;
# :class:`GroupedQueryAttention` with ``window``: the ring of the layer's K/V
# rows, ``window`` rows a slot),
# addressed by ``state_rows`` and never through a block table.  They have no
# role among the pool's rows, whatever their leading size.
KDA_STATE, KDA_CONV = "kda_state", "kda_conv"
MAMBA_STATE, MAMBA_CONV = "mamba_state", "mamba_conv"
GDN_STATE, GDN_CONV = "gdn_state", "gdn_conv"
# a window layer's ring of K/V rows a slot: the fourth slot-addressed kind
WINDOW_KEYS, WINDOW_VALUES = "window_k", "window_v"
# query rows of one batch row whose scores the grouped path builds at once
QUERY_BLOCK = 512
WINDOW_LEAVES = (WINDOW_KEYS, WINDOW_VALUES)
STATE_LEAVES = (
    KDA_STATE, KDA_CONV, MAMBA_STATE, MAMBA_CONV, GDN_STATE, GDN_CONV,
) + WINDOW_LEAVES


def _leaf_name(path) -> str:
    """The name of the variable a cache leaf belongs to: the last component
    of its jax key path that is a name at all."""
    for part in reversed(path):
        name = str(getattr(part, "key", getattr(part, "name", "")))
        if name:
            return name
    return ""


def pool_leaf_role(path, leaf, pool_rows: int) -> Optional[str]:
    """``"scored"`` / ``"value"`` for a per-row leaf of the paged pool,
    ``None`` for anything else in the cache tree.  ``path`` is the leaf's
    jax key path; a pool leaf is one an attention module declared (by the
    name of its variable) AND whose leading dimension is the pool's rows.
    A per-slot state leaf (``STATE_LEAVES``) is told by its name: it has no
    role here even where the slots happen to be as many as the rows."""
    role = _POOL_ROLES.get(_leaf_name(path))
    if role is None or not (
        hasattr(leaf, "ndim") and leaf.ndim >= 1 and leaf.shape[0] == pool_rows
    ):
        return None
    return role


def is_state_leaf(path) -> bool:
    """True for a per-slot state leaf of the cache tree (by its name)."""
    return _leaf_name(path) in STATE_LEAVES

def _use_flash(q) -> bool:
    """Trace-time flash-kernel eligibility for the local-attention path.

    The Pallas path runs when (a) on real TPU, (b) INSIDE shard_map
    (varying mesh axes present) — under plain GSPMD jit a pallas_call has
    no SPMD partitioning rule, so without a mesh hint the sharded
    TP/ZeRO/MoE paths keep the einsum attention XLA can partition (the
    ``mesh`` argument to :func:`dot_product_attention` lifts this via a
    shard_map island; see :func:`_gspmd_island_spec`), while the shard_map
    LM paths (engine/sp_steps — also the plain-DP default) get the kernel —
    (c) the sequence divides the 128 blocks, and (d) the kernel's resident
    K/V rows fit the VMEM budget.  ``PDT_DISABLE_PALLAS=1`` forces XLA
    (same escape hatch as ops/losses.py).
    """
    from .flash_attention import flash_enabled, flash_shapes_ok

    if not flash_enabled():
        return False
    if not varying_axes_of(q):
        return False
    b, s_len, h, d = q.shape
    return flash_shapes_ok(s_len, d)


def _gspmd_island_spec(q_shape, mesh):
    """Partitioning plan for the flash island inside a GSPMD program, or
    ``None`` to stay on the XLA einsum path.

    Returns ``(spec, interpret)``: ``spec`` is the q/k/v/out
    ``PartitionSpec`` — batch over ``data``, heads over every present
    model-ish axis (``model`` and, on 3-D meshes, ``sequence``: resharding
    sequence-sharded activations to head-sharded full-sequence blocks is
    exactly the DeepSpeed-Ulysses all-to-all, and GSPMD inserts it from
    the spec change).  Attention is independent per (batch, head), so the
    island body needs no collectives and shard_map AD stays collective-free
    too.  ``None`` when shapes don't divide the mesh, flash is ineligible,
    or ``PDT_FLASH_GSPMD=0``.  ``interpret`` (``PDT_FLASH_GSPMD_INTERPRET=1``,
    CPU test meshes) runs the island kernels in Pallas interpreter mode.
    """
    import os

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS
    from ..parallel.sequence import SEQUENCE_AXIS
    from .flash_attention import flash_enabled, flash_shapes_ok

    if os.environ.get("PDT_FLASH_GSPMD", "1") == "0":
        return None
    interpret = os.environ.get("PDT_FLASH_GSPMD_INTERPRET", "0") != "0"
    if not (flash_enabled() or interpret):
        return None
    b, s_len, h, d = q_shape
    if not flash_shapes_ok(s_len, d):
        return None
    head_axes = tuple(
        ax for ax in (MODEL_AXIS, SEQUENCE_AXIS) if ax in mesh.axis_names
    )
    n_head = 1
    for ax in head_axes:
        n_head *= mesh.shape[ax]
    dp = mesh.shape[DATA_AXIS] if DATA_AXIS in mesh.axis_names else 1
    if b % dp or h % n_head:
        return None
    spec = P(
        DATA_AXIS if DATA_AXIS in mesh.axis_names else None,
        None,
        head_axes if head_axes else None,
        None,
    )
    return spec, interpret


def _gspmd_flash(q, k, v, causal, sm_scale, mesh, spec, interpret):
    """shard_map island: per-device [B/dp, S, H/n, D] blocks run the local
    Pallas flash kernel; the GSPMD partitioner reshards operands to the
    island's layout (and back) around it.  check_vma=False only under the
    interpreter (its state discharge does not propagate varying-axes
    through in-kernel pl.ds reads — same caveat as
    tests/test_flash_attention.py; Mosaic lowering never discharges)."""
    from .flash_attention import flash_attention

    def local(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, interpret=interpret
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not interpret,
    )(q, k, v)


def dot_product_attention(
    q,
    k,
    v,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
    interpret: bool = False,
    mesh=None,
):
    """Full attention on the local shard: ``[B, S, H, D] -> [B, S, H, D]``.

    ``impl``: ``None`` auto-selects the Pallas flash kernel
    (:mod:`.flash_attention`) when eligible (see :func:`_use_flash`),
    ``"flash"``/``"xla"`` force a path.  ``interpret`` runs a forced
    flash path in Pallas interpreter mode (CPU test meshes).

    ``mesh``: set by the GSPMD step builders (engine/tp_steps via
    ``TransformerLM.flash_mesh``) — under plain jit a ``pallas_call`` has
    no SPMD partitioning rule, so the kernel runs inside a shard_map
    island partitioned per :func:`_gspmd_island_spec` (TP/ZeRO/FSDP/MoE
    paths stop paying the O(S^2) einsum).  Ignored inside shard_map or
    when the island is ineligible.
    """
    if impl not in (None, "flash", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl == "flash" or (impl is None and _use_flash(q)):
        from .flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, interpret=interpret
        )
    if impl is None and mesh is not None and not varying_axes_of(q):
        plan = _gspmd_island_spec(q.shape, mesh)
        if plan is not None:
            return _gspmd_flash(q, k, v, causal, sm_scale, mesh, *plan)
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        n = s.shape[-1]
        mask = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(mask[None, None], s, float("-inf"))
    p = jnp.asarray(nn.softmax(s, axis=-1))
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


class MultiHeadAttention(nn.Module):
    """QKV-projected MHA whose inner attention can be sequence-parallel.

    Attributes:
      num_heads: attention heads (embed dim must divide evenly).
      seq_axis: mesh axis name the sequence dim is sharded over, or None.
        When set, this module MUST be applied inside ``shard_map`` with that
        axis in scope and inputs sharded ``[B, S/n, ...]``.
      seq_impl: "ring" or "ulysses" (ignored when ``seq_axis`` is None).
      dtype: compute dtype (bf16 for mixed precision); fp32 accumulation
        happens inside the attention strategies regardless.
    """

    num_heads: int
    causal: bool = False
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    dtype: jnp.dtype = jnp.float32
    # mesh hint for the GSPMD flash island (engine/tp_steps sets it via
    # TransformerLM.flash_mesh); None = einsum under plain jit
    flash_mesh: Optional[Any] = None
    # KV-cache incremental decode (serving/decode.py): ``decode=True``
    # allocates ``cached_key``/``cached_value`` [B, cache_len, H, hd] in the
    # "cache" variable collection.  A call with ``decode_pos=None`` is the
    # PREFILL: normal causal attention over the prompt, cache rows [0, S)
    # written as a side effect.  A call with ``decode_pos`` ([B] int32,
    # per-row position of the single new token) is one DECODE STEP: k/v are
    # scattered at each row's position and q attends over the whole cache
    # masked to ``<= decode_pos`` — per-row positions support right-padded
    # batches of different prompt lengths in one jit program.
    decode: bool = False
    cache_len: int = 0
    # Paged KV cache (serving/kv_pool.py + serving/scheduler.py): instead of
    # a per-row contiguous [B, cache_len] cache, k/v live in a SHARED pool of
    # ``kv_num_blocks`` blocks of ``kv_block_size`` token rows, and each row
    # of a call carries a block table mapping its logical positions to
    # physical pool blocks.  ``decode_pos`` becomes [B, S] per-TOKEN global
    # positions (-1 = padding: its scatter is dropped and its output is
    # garbage the host ignores), so ONE program shape handles cold prefill,
    # chunked prefix-hit prefill, and single-token decode (S=1).  Blocks
    # reused from a prefix cache are read-only here by construction: the
    # scatter only covers the caller's own (suffix) positions.
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    # Multi-LoRA serving (serving/lora.py + ops/lora.py): with
    # ``lora_rank > 0`` the qkv and proj Denses each carry STACKED
    # low-rank factors for ``lora_adapters`` adapters ([N, din, r] /
    # [N, r, dout] in the regular params tree — grafted from the adapter
    # registry at engine build), and ``adapter_ids`` [B] selects each
    # row's adapter per call (-1 = base model, zero delta).  Base
    # parameter shapes are unchanged, so train-time checkpoints still
    # restore directly.
    lora_rank: int = 0
    lora_adapters: int = 0

    @nn.compact
    def __call__(self, x, decode_pos=None, block_tables=None, adapter_ids=None):
        b, s, dim = x.shape
        if dim % self.num_heads != 0:
            raise ValueError(f"embed dim {dim} not divisible by {self.num_heads} heads")
        if self.lora_rank > 0 and self.lora_adapters < 1:
            raise ValueError(
                f"lora_rank {self.lora_rank} needs lora_adapters >= 1, "
                f"got {self.lora_adapters}"
            )
        if adapter_ids is not None and self.lora_rank <= 0:
            raise ValueError(
                "adapter_ids given but the module has no LoRA factors "
                "(lora_rank is 0)"
            )
        head_dim = dim // self.num_heads
        qkv = nn.Dense(3 * dim, dtype=self.dtype, name="qkv")(x)
        if self.lora_rank > 0:
            from .lora import lora_delta

            # B zero-init: a freshly-initialized adapter is an exact
            # no-op, the standard LoRA construction; real factors are
            # grafted over these leaves by the serving registry
            qkv_a = self.param(
                "qkv_lora_a", nn.initializers.normal(stddev=0.02),
                (self.lora_adapters, dim, self.lora_rank), jnp.float32,
            )
            qkv_b = self.param(
                "qkv_lora_b", nn.initializers.zeros,
                (self.lora_adapters, self.lora_rank, 3 * dim), jnp.float32,
            )
            if adapter_ids is not None:
                qkv = qkv + lora_delta(x, qkv_a, qkv_b, adapter_ids).astype(
                    qkv.dtype
                )
        # heads-major layout: the flat 3*dim output factors as (H, 3, hd), so
        # sharding the qkv kernel's output axis over a model mesh axis (k | H)
        # splits on whole-head boundaries and GSPMD propagates it through this
        # reshape — Megatron-style head-parallel attention with no manual
        # collectives (see parallel.tensor)
        qkv = qkv.reshape(b, s, self.num_heads, 3, head_dim)
        q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
        if self.decode and self.paged:
            with jax.named_scope("paged_attention"):
                out = self._paged_attention(q, k, v, decode_pos, block_tables)
        elif self.decode:
            out = self._decode_attention(q, k, v, decode_pos)
        elif self.seq_axis is None:
            out = dot_product_attention(
                q, k, v, causal=self.causal, mesh=self.flash_mesh
            )
        elif self.seq_impl == "ring":
            out = ring_attention(q, k, v, axis_name=self.seq_axis, causal=self.causal)
        elif self.seq_impl == "ulysses":
            out = ulysses_attention(q, k, v, axis_name=self.seq_axis, causal=self.causal)
        else:
            raise ValueError(f"unknown seq_impl {self.seq_impl!r}")
        out = out.reshape(b, s, dim)
        proj = nn.Dense(dim, dtype=self.dtype, name="proj")(out)
        if self.lora_rank > 0:
            from .lora import lora_delta

            proj_a = self.param(
                "proj_lora_a", nn.initializers.normal(stddev=0.02),
                (self.lora_adapters, dim, self.lora_rank), jnp.float32,
            )
            proj_b = self.param(
                "proj_lora_b", nn.initializers.zeros,
                (self.lora_adapters, self.lora_rank, dim), jnp.float32,
            )
            if adapter_ids is not None:
                proj = proj + lora_delta(
                    out, proj_a, proj_b, adapter_ids
                ).astype(proj.dtype)
        return proj

    def _decode_attention(self, q, k, v, decode_pos):
        """Prefill / single-step attention against the KV cache."""
        if self.seq_axis is not None:
            raise ValueError("decode mode is single-shard (seq_axis must be None)")
        if not self.causal:
            raise ValueError("decode mode requires causal attention")
        cache_len = self.cache_len
        if cache_len <= 0:
            raise ValueError(f"decode mode needs cache_len > 0, got {cache_len}")
        b, s, num_heads, head_dim = q.shape
        kv_shape = (b, cache_len, num_heads, head_dim)
        cached_key = self.variable("cache", "cached_key", jnp.zeros, kv_shape, self.dtype)
        cached_value = self.variable("cache", "cached_value", jnp.zeros, kv_shape, self.dtype)
        if decode_pos is None:
            # prefill: the prompt's k/v land in rows [0, S); attention over
            # the prompt itself is the ordinary causal path.  Right-padded
            # rows write garbage k/v beyond their true length, but each
            # row's k/v depend only on that position's own token, so real
            # positions are untouched — and decode steps overwrite the pad
            # rows before any masked-in query ever reads them.
            if s > cache_len:
                raise ValueError(f"prompt length {s} exceeds cache_len {cache_len}")
            cached_key.value = cached_key.value.at[:, :s].set(k.astype(self.dtype))
            cached_value.value = cached_value.value.at[:, :s].set(v.astype(self.dtype))
            return dot_product_attention(q, k, v, causal=True, impl="xla")
        # single step: scatter this token's k/v at each row's position, then
        # attend q over the full cache masked to the row's live prefix
        if s != 1:
            raise ValueError(f"decode step takes one token per row, got S={s}")
        hit = (
            jnp.arange(cache_len, dtype=jnp.int32)[None, :] == decode_pos[:, None]
        )  # [B, L]
        ck = jnp.where(hit[:, :, None, None], k.astype(self.dtype), cached_key.value)
        cv = jnp.where(hit[:, :, None, None], v.astype(self.dtype), cached_value.value)
        cached_key.value = ck
        cached_value.value = cv
        scale = 1.0 / math.sqrt(head_dim)
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), ck.astype(jnp.float32)
        ) * scale
        live = (
            jnp.arange(cache_len, dtype=jnp.int32)[None, :] <= decode_pos[:, None]
        )  # [B, L]
        logits = jnp.where(live[:, None, None, :], logits, float("-inf"))
        p = jnp.asarray(nn.softmax(logits, axis=-1))
        out = jnp.einsum("bhqk,bkhd->bqhd", p, cv.astype(jnp.float32))
        return out.astype(q.dtype)

    def _paged_attention(self, q, k, v, positions, block_tables):
        """Attention against the shared paged KV pool
        (:func:`paged_attention`, one K/V head a query head; where it
        gathers, the gathered rows upcast to float32 as this module always
        had them)."""
        if self.seq_axis is not None:
            raise ValueError("paged decode is single-shard (seq_axis must be None)")
        if not self.causal:
            raise ValueError("paged decode requires causal attention")
        return paged_attention(
            self, q, k, v, positions, block_tables,
            block_size=self.kv_block_size, num_blocks=self.kv_num_blocks,
            dtype=self.dtype, as_stored=False,
        )


def _stored_heads(kv_heads: int) -> int:
    """K/V heads a row of the pool holds for ``kv_heads`` that are used.  A
    row's heads are the sublanes of the device's tiles: past one tile of 8
    they come in whole tiles (the device pads a ``[.., 30, 128]`` leaf to 32
    heads in memory whatever its shape says, and Mosaic refuses to copy a
    block out of it: "slice shape must be aligned to tiling (8), but is
    30"), so the pool is declared with what it takes up.  Up to 8 heads, and
    any multiple of 8, are stored as they are."""
    return kv_heads if kv_heads <= 8 else -(-kv_heads // 8) * 8


def whole_prompt_flash(whole_prompts: bool, s: int, head_dim: int) -> bool:
    """Whether :func:`paged_attention` scores a call of ``s`` positions a row
    through the causal flash forward over the call's own K/V: the caller
    states that the call holds whole prompts, the backend runs the kernel
    and the shape is one it takes.  The scheduler counts its flash calls by
    the same rule (``ContinuousScheduler._flash_layers``)."""
    from .flash_attention import flash_enabled, flash_shapes_ok

    return bool(
        whole_prompts and s > 1 and flash_enabled() and flash_shapes_ok(s, head_dim))


# tests set this to run the flash arm's kernel in the Pallas interpreter
_FLASH_INTERPRET = False


def paged_attention(module, q, k, v, positions, block_tables, *, block_size,
                    num_blocks, dtype, as_stored, query_block=0,
                    whole_prompts=False):
    """Block-table gather attention against the shared paged KV pool, for
    ``q [B, S, H, hd]`` and ``k``, ``v [B, S, Hkv, hd]`` with ``H`` a
    multiple of ``Hkv``: query head ``h`` reads K/V head ``h // (H / Hkv)``.

    ``positions`` [B, S] int32: each token's GLOBAL sequence position in
    its request (-1 = padding column).  ``block_tables`` [B, T] int32:
    physical pool block holding logical block ``t`` (positions
    ``[t*bs, (t+1)*bs)``) of row ``b``.  The pool lives flattened as
    ``[num_blocks * block_size, Hkv, hd]`` in ``module``'s "cache"
    collection — scatter this call's k/v at their physical rows (padding
    scatters are dropped via an out-of-bounds index), then read each row's
    sequence back through its block table with keys masked to
    ``key_pos <= q_pos``.  Because suffix k/v are scattered before the
    read, cold prefill (positions 0..len-1), chunked prefix-hit prefill
    (positions cached_len..len-1 reading the shared prefix blocks) and
    single-token decode (S=1) are the same computation.  Rows beyond a
    sequence's written length are masked to -inf and their values zeroed,
    so recycled block contents never leak into the softmax.

    Which call reads the pool how is decided by what the call shows:

    - ``S == 1`` on a TPU (the scheduler's ``decode_step``, of
      every served family), with rows the kernel
      can read (:func:`..ops.paged_decode.fits`): the Pallas kernel
      :func:`..ops.paged_decode.paged_decode` walks each row's block table
      up to the row's own length, K and V as stored, scores, softmax and
      accumulation in float32; ``as_stored`` and ``query_block`` have
      nothing to say there.  No copy of the table's rows exists.  The
      LATENT leaf, whose rows have no head axis, has the same contract and
      a kernel of its own, chosen the same way by
      :class:`..ops.mla.MLAttention`:
      :func:`..ops.mla_paged_decode.mla_paged_decode` (the absorbed form:
      all heads against the one ``rank + rope`` row a position, the value
      the row's first ``rank`` lanes).
    - ``S > 1`` with ``whole_prompts`` on a TPU, ``S`` a multiple of 128
      (:func:`whole_prompt_flash`; the prefill of a model that carries a
      state a sequence, every bucket of the four served): the caller states
      that every row's columns are positions ``0 .. n - 1`` and then
      padding (the scheduler refuses such a model any other call,
      ``ContinuousScheduler._refuse_a_piece``), so what the table would
      give back is, value for value, the ``k.astype(dtype)`` and
      ``v.astype(dtype)`` just written, and a causal mask by COLUMN is the
      mask by position (a padding column lies after a row's last real one:
      no real query sees it, and what a padding query reads is ignored).
      The pool is written as ever and not read: the scores, softmax and sum
      are the causal flash forward over the call's own K/V
      (:func:`..ops.flash_attention.flash_prefill`: a K/V head read by its
      group's query heads where it lies; bf16 operands, float32 scores,
      softmax and accumulation, the probabilities rounded to the values'
      dtype before the second product, as the gather arm has them).  No
      ``[H, S, S]`` array exists.
    - any other ``S > 1`` (a call that may start past 0: a prefix hit's
      suffix, the speculative ``verify``; a shape the kernel does not
      take), and any call off a TPU: the GATHER arms below, as they were:
      each row's FULL table is gathered into ``[B, L, Hkv, hd]`` and
      scored.  Left to a later change: the flash kernel's query tiles
      against a walk of the block table, for the calls that start past 0.

    ``as_stored=False`` upcasts the gathered rows to float32 before the
    products (``TransformerLM``'s programs, unchanged); ``as_stored=True``
    takes them in the pool's dtype and accumulates in float32 (at 32 rows of
    4,608 positions the float32 copy is 1.2 GB a step).  ``query_block > 0``
    builds the scores of a call longer than that for ``query_block`` query
    rows of ONE batch row at a time, so that no ``[B, H, S, L]`` array
    exists.  ``whole_prompts`` (static): the caller's statement about what a
    call of more than one position holds, above.
    """
    bs, nb = block_size, num_blocks
    if bs <= 0 or nb <= 0:
        raise ValueError(
            f"paged mode needs kv_block_size/kv_num_blocks > 0, "
            f"got {bs}/{nb}"
        )
    if positions is None or block_tables is None:
        raise ValueError("paged mode needs positions and block_tables")
    b, s, num_heads, head_dim = q.shape
    kv_heads = k.shape[2]
    group = num_heads // kv_heads
    if group * kv_heads != num_heads:
        raise ValueError(
            f"{num_heads} query heads are no multiple of {kv_heads} K/V heads")
    pool_rows = nb * bs
    # a row of the pool holds whole sublane tiles of heads: the heads that
    # fill the last tile stay zeros, are never written and never scored
    stored = _stored_heads(kv_heads)
    k_pool = module.variable(
        "cache", KEY_POOL, jnp.zeros, (pool_rows, stored, head_dim), dtype,
    )
    v_pool = module.variable(
        "cache", VALUE_POOL, jnp.zeros, (pool_rows, stored, head_dim), dtype,
    )
    valid = positions >= 0  # [B, S]
    safe_pos = jnp.maximum(positions, 0)
    blk = jnp.take_along_axis(block_tables, safe_pos // bs, axis=1)  # [B, S]
    phys = jnp.where(valid, blk * bs + safe_pos % bs, pool_rows)  # OOB=drop

    def written(pool):  # the call's rows of a leaf, the heads in use
        rows = phys.reshape(-1)
        return pool.at[rows] if stored == kv_heads else pool.at[rows, :kv_heads]

    kp = written(k_pool.value).set(
        k.astype(dtype).reshape(b * s, kv_heads, head_dim), mode="drop"
    )
    vp = written(v_pool.value).set(
        v.astype(dtype).reshape(b * s, kv_heads, head_dim), mode="drop"
    )
    k_pool.value, v_pool.value = kp, vp
    scale = 1.0 / math.sqrt(head_dim)
    from . import paged_decode
    from .flash_attention import flash_enabled

    if s == 1 and flash_enabled() and paged_decode.fits(head_dim, stored, dtype):
        # one position a row, on a TPU: the kernel reads the pool where it
        # lies, each row's live blocks and no others.  A padding row
        # (position -1) reads key 0 of its table's first block, as below.
        heads = q.reshape(b, kv_heads, group, head_dim)
        if stored != kv_heads:  # zero queries for the tile's spare heads
            heads = jnp.pad(
                heads, ((0, 0), (0, stored - kv_heads), (0, 0), (0, 0)))
        out = paged_decode.paged_decode(
            heads,
            kp.reshape(nb, bs, stored, head_dim),
            vp.reshape(nb, bs, stored, head_dim),
            block_tables, safe_pos[:, 0] + 1, scale=scale,
        )
        return out[:, :kv_heads].reshape(b, 1, num_heads, head_dim)
    if whole_prompt_flash(whole_prompts, s, head_dim):
        # the write before the scores, as the gather arm's read of the pool
        # orders them: nothing else waits for a pool nobody reads, and the
        # compiler otherwise defers every layer's write to the program's end
        # and keeps its K and V until then (151 MB of a 1 x 8,192 prefill's
        # temporaries over Laguna's five full layers)
        kp, vp, k, v = jax.lax.optimization_barrier(
            (kp, vp, k.astype(dtype), v.astype(dtype)))
        k_pool.value, v_pool.value = kp, vp
        from .flash_attention import flash_prefill

        return flash_prefill(q, k, v, interpret=_FLASH_INTERPRET)
    t_blocks = block_tables.shape[1]
    length = t_blocks * bs
    # [B, L] physical rows in logical-position order (the row-at-a-time gather)
    rows = None if as_stored else (
        (block_tables * bs)[:, :, None]
        + jnp.arange(bs, dtype=jnp.int32)[None, None, :]
    ).reshape(b, length)

    def used(gathered):  # without the heads that only fill the last tile
        return gathered if stored == kv_heads else gathered[:, :, :kv_heads]

    def gather(pool, tables):
        """A batch's rows in logical order, ``[b, L, Hkv, hd]``.  Taken as
        stored they are gathered a BLOCK at a time (the ``[blocks, bs, ...]``
        view is a bitcast; a block is ``bs`` rows in one piece, where a row
        at a time ran at a tenth of the memory's rate, PERF.md PR 26)."""
        if not as_stored:
            return used(pool[rows])
        blocks = pool.reshape(nb, bs, stored, head_dim)[tables]
        return used(blocks.reshape(tables.shape[0], length, stored, head_dim))

    wide = (lambda x: x) if as_stored else (lambda x: x.astype(jnp.float32))
    accumulate = jnp.float32 if as_stored else None
    # one K/V head a query head: the products as they always were; a group
    # of query heads a K/V head: the group is one more axis of q
    if group == 1:
        to_scores, to_out, head_axes = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd", 1
    else:
        to_scores, to_out, head_axes = "bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd", 2
        q = q.reshape(b, s, kv_heads, group, head_dim)

    def attend(q, q_pos, ck, cv):
        """``q [b, s', ...]`` at positions ``q_pos [b, s']`` over the
        gathered ``ck``, ``cv [b, L, Hkv, hd]``."""
        logits = jnp.einsum(
            to_scores, wide(q), wide(ck), preferred_element_type=accumulate
        ) * scale
        live = (
            jnp.arange(length, dtype=jnp.int32)[None, None, :]
            <= q_pos[:, :, None]
        )  # [b, s', L]; padding queries keep key 0 live so softmax stays finite
        logits = jnp.where(
            live[(slice(None),) + (None,) * head_axes], logits, float("-inf"))
        p = jnp.asarray(nn.softmax(logits, axis=-1))
        # zero non-live VALUES too, not just their softmax weight: a NaN in
        # a dead gathered row (padded block-table entries alias block 0;
        # recycled blocks keep an evicted request's contents) would
        # otherwise leak through the contraction as 0 * NaN = NaN — the
        # serving output guard depends on NaN staying confined to the row
        # that produced it.  Causal mask => a position live for any of
        # these queries is live for the last of them, so reduce over s'.
        cv = jnp.where(live.any(axis=1)[:, :, None, None], wide(cv), 0.0)
        if as_stored:
            p = p.astype(cv.dtype)
        return jnp.einsum(to_out, p, cv, preferred_element_type=accumulate)

    if query_block and s > query_block:
        if s % query_block:
            raise ValueError(
                f"call of {s} positions is no multiple of query_block {query_block}")
        pieces = s // query_block

        def one_row(args):
            q_row, pos_row, table_row = args  # [S, ...], [S], [T]
            ck, cv = gather(kp, table_row[None]), gather(vp, table_row[None])
            out = jax.lax.map(
                lambda piece: attend(piece[0][None], piece[1][None], ck, cv)[0],
                (q_row.reshape((pieces, query_block) + q_row.shape[1:]),
                 pos_row.reshape(pieces, query_block)),
            )
            return out.reshape((s,) + out.shape[2:])

        out = jax.lax.map(one_row, (q, safe_pos, block_tables))
    else:
        ck = gather(kp, block_tables)  # [B, L, Hkv, hd]
        cv = gather(vp, block_tables)
        out = attend(q, safe_pos, ck, cv)
    if group > 1:
        out = out.reshape(b, s, num_heads, head_dim)
    return out.astype(q.dtype)


def rms_norm(x, weight, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * w``, statistics in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def _banded_scores(q, k, v, window: int, scale: float):
    """A window layer's attention over the call's OWN keys, ``q [B, S, Hkv,
    G, hd]`` and ``k``, ``v [B, S, Hkv, hd]`` at positions ``0 .. S - 1``
    (the column IS the position: a prompt is prefilled whole, padding to its
    right): query ``i`` reads the keys ``i - window < j <= i``.  A block of
    ``band = min(window, S)`` query rows is scored against its own block of
    keys and the one before it, ``S x 2 band`` products and one ``[Hkv, G,
    band, 2 band]`` array of float32 scores at a time, where the masked
    ``[S, S]`` form builds ``S x S``.  Returns ``[B, S, Hkv, G, hd]`` in
    float32."""
    b, s, hkv, group, hd = q.shape
    band = min(window, s)
    if s % band:
        raise ValueError(
            f"a window layer's call of {s} positions is no multiple of its "
            f"window {window}: give the scheduler sequence buckets that are")
    blocks = s // band

    def in_blocks(a):  # [B, S, ...] -> [B * blocks, band, ...]
        return a.reshape((b * blocks, band) + a.shape[2:])

    def before(a):  # the block before each block, zeros before the first
        shifted = jnp.pad(a, ((0, 0), (band, 0)) + ((0, 0),) * (a.ndim - 2))
        return in_blocks(shifted[:, :s])

    first = jnp.tile(jnp.arange(blocks, dtype=jnp.int32) * band, b)
    rows = jnp.arange(band, dtype=jnp.int32)
    cols = jnp.arange(2 * band, dtype=jnp.int32) - band

    def one_block(args):
        q_rows, k_prev, k_own, v_prev, v_own, at = args
        keys = jnp.concatenate([k_prev, k_own], axis=0)  # [2 band, Hkv, hd]
        values = jnp.concatenate([v_prev, v_own], axis=0)
        scores = jnp.einsum(
            "qhgd,khd->hgqk", q_rows, keys, preferred_element_type=jnp.float32,
        ) * scale
        ahead = cols[None, :] - rows[:, None]  # key position - query position
        seen = (ahead <= 0) & (ahead > -window) & (at + cols >= 0)[None, :]
        p = nn.softmax(jnp.where(seen, scores, float("-inf")), axis=-1)
        return jnp.einsum(
            "hgqk,khd->qhgd", p.astype(values.dtype), values,
            preferred_element_type=jnp.float32)

    out = jax.lax.map(one_block, (
        in_blocks(q), before(k), in_blocks(k), before(v), in_blocks(v), first))
    return out.reshape(b, s, hkv, group, hd)


def window_attention(module, q, k, v, positions, slots, *, window: int,
                     state_slots: int, block_size: int, dtype):
    """A window layer against its ring, for ``q [B, S, H, hd]`` and ``k``,
    ``v [B, S, Hkv, hd]`` (already rotated: a key is stored as it is
    scored): query position ``p`` reads the keys at ``p - window < j <= p``,
    its own among them, and nothing older is kept.

    The ring lives as two leaves ``[state_slots * window, Hkv, hd]`` in
    ``module``'s "cache" collection; position ``p`` of slot ``s`` lies at row
    ``s * window + p % window``.  ``positions [B, S]`` as in
    :func:`paged_attention` (-1 = padding); ``slots [B]`` int32 names each
    batch row's slot (-1 = padding: nothing is written).

    - ``S == 1``, a decode step: the row's key and value overwrite the one
      that left the window, then the query reads the ring's ``min(p + 1,
      window)`` written rows.  Softmax does not care in which order keys
      come, so on a TPU this is :func:`..ops.paged_decode.paged_decode` AS
      IT IS over the ring, the slot's ``window / block_size`` blocks its
      table and ``min(p + 1, window)`` its length; elsewhere the slot's rows
      are gathered, masked past that length and scored.
    - ``S > 1``, a prefill: the prompt is prefilled WHOLE (positions ``0 ..
      n - 1`` in columns ``0 .. n - 1``; a model that carries slot-addressed
      leaves has no prefix cache and no prefill in pieces, and the scheduler
      refuses a call that starts anywhere else), so the call
      scores its own keys in a band (:func:`_banded_scores`) and reads no
      cache, then writes the last ``min(n, window)`` real positions into the
      ring (a padding column writes nothing).
    """
    b, s, num_heads, head_dim = q.shape
    kv_heads = k.shape[2]
    group = num_heads // kv_heads
    if group * kv_heads != num_heads:
        raise ValueError(
            f"{num_heads} query heads are no multiple of {kv_heads} K/V heads")
    if state_slots < 1:
        raise ValueError(
            f"a window layer keeps a ring a slot: state_slots >= 1, got {state_slots}")
    if positions is None or slots is None:
        raise ValueError("a window layer's paged call needs positions and slots")
    ring_rows = state_slots * window
    ring = [
        module.variable(
            "cache", name, jnp.zeros, (ring_rows, kv_heads, head_dim), dtype)
        for name in (WINDOW_KEYS, WINDOW_VALUES)
    ]
    scale = 1.0 / math.sqrt(head_dim)
    safe_slot = jnp.maximum(slots, 0)
    grouped = q.reshape(b, s, kv_heads, group, head_dim)
    if s > 1:
        out = _banded_scores(grouped, k, v, window, scale)
        # the newest ``window`` real positions of each row, by column
        n = jnp.max(positions, axis=1) + 1  # [B]
        cols = n[:, None] - window + jnp.arange(window, dtype=jnp.int32)[None, :]
        keep = (cols >= 0) & (slots >= 0)[:, None]
        rows = jnp.where(
            keep, safe_slot[:, None] * window + cols % window, ring_rows)
        take = jnp.clip(cols, 0, s - 1)[:, :, None, None]
        for leaf, new in zip(ring, (k, v)):
            newest = jnp.take_along_axis(new, take, axis=1).astype(dtype)
            leaf.value = leaf.value.at[rows.reshape(-1)].set(
                newest.reshape(b * window, kv_heads, head_dim), mode="drop")
        return out.reshape(b, s, num_heads, head_dim).astype(q.dtype)
    pos = positions[:, 0]
    live = (pos >= 0) & (slots >= 0)
    safe_pos = jnp.maximum(pos, 0)
    row = jnp.where(live, safe_slot * window + safe_pos % window, ring_rows)
    for leaf, new in zip(ring, (k, v)):
        leaf.value = leaf.value.at[row].set(new[:, 0].astype(dtype), mode="drop")
    lengths = jnp.minimum(safe_pos + 1, window)  # a padding row reads row 0
    from . import paged_decode
    from .flash_attention import flash_enabled

    if (flash_enabled() and block_size > 0 and window % block_size == 0
            and paged_decode.fits(head_dim, kv_heads, dtype)):
        per_slot = window // block_size
        tables = safe_slot[:, None] * per_slot + jnp.arange(
            per_slot, dtype=jnp.int32)[None, :]
        in_ring_blocks = (state_slots * per_slot, block_size, kv_heads, head_dim)
        out = paged_decode.paged_decode(
            grouped[:, 0], ring[0].value.reshape(in_ring_blocks),
            ring[1].value.reshape(in_ring_blocks), tables, lengths, scale=scale)
        return out.reshape(b, 1, num_heads, head_dim)
    by_slot = (state_slots, window, kv_heads, head_dim)
    keys = ring[0].value.reshape(by_slot)[safe_slot]  # [B, window, Hkv, hd]
    values = ring[1].value.reshape(by_slot)[safe_slot]
    written = jnp.arange(window, dtype=jnp.int32)[None, :] < lengths[:, None]
    scores = jnp.einsum(
        "bhgd,bkhd->bhgk", grouped[:, 0], keys,
        preferred_element_type=jnp.float32) * scale
    p = nn.softmax(
        jnp.where(written[:, None, None, :], scores, float("-inf")), axis=-1)
    # an unwritten row may hold what an evicted request left there, a NaN too
    values = jnp.where(written[:, :, None, None], values, 0)
    out = jnp.einsum(
        "bhgk,bkhd->bhgd", p.astype(values.dtype), values,
        preferred_element_type=jnp.float32)
    return out.reshape(b, 1, num_heads, head_dim).astype(q.dtype)


class GroupedQueryAttention(nn.Module):
    """Causal softmax attention with fewer K/V heads than query heads: ``q =
    x W_q`` (``H`` heads), ``k, v = x W_k, x W_v`` (``Hkv`` heads), query
    head ``h`` reads K/V head ``h // (H / Hkv)``, scores ``q.k / sqrt(hd)``,
    softmax in float32, then the output gate and ``W_o``.  No bias.
    Everything below is static and off by default: a family that leaves it
    out runs the program it ran.

    ``rotary_dim > 0``: a rotary position term over the first ``rotary_dim``
    lanes of every head of ``q`` and ``k`` (:func:`.rotary.rotate_halves`:
    pairs ``(i, i + rotary_dim / 2)``), its ``rotary_dim / 2`` frequencies
    ``rotary_inv_freq`` and its amplitude ``rotary_amp`` handed in by the
    model (a default term, or YaRN's blend and attention factor,
    :func:`.rotary.yarn_inv_freq`); the other lanes pass unchanged.  0 (the
    default): NO position term (NoPE).  A key enters the cache rotated.

    ``window > 0``: a query reads its ``window`` newest keys, its own among
    them (``i - window < j <= i``), and the layer keeps a ring a slot beside
    the pool (:func:`window_attention`).

    ``gate``: ``True``, ``o * sigmoid(x W_gate)`` with
    ``W_gate [dim, H * hd]``, one number a value (arXiv:2505.06708);
    ``"head"``, ``W_gate [dim, H]``, ONE number a head (the same paper's
    headwise form); ``False``, none.

    ``qk_norm``: an RMSNorm with a learned weight over the WHOLE projection
    of ``q`` and of ``k``, before the heads are split (Olmo 2's QK-norm,
    arXiv:2501.00656).

    ``decode=False``: plain causal (and windowed) attention over the call's
    own tokens at positions ``0 .. S - 1``.  ``decode=True, paged=True``: K/V
    rows of ``Hkv`` heads in the paged pool (:func:`paged_attention`: a
    decode step through the paged kernel; a prefill that holds
    ``whole_prompts`` through the causal flash forward over its own K/V;
    any other call's rows gathered as stored and a long call's scores built
    ``query_block`` query rows at a time) or, a window layer, in its ring.
    ``whole_prompts``: the model's statement that a paged call of more than
    one position holds each row's positions ``0 .. n - 1`` and then padding
    (a model that carries a state a sequence is prefilled so and no other
    way: ``serving/scheduler.py::_refuse_a_piece``).  Device scopes: ``gqa_attention``
    around the layer and, inside it, ``rotary``, ``full_attention`` or
    ``window_attention`` (scores, softmax and weighted sum, with the cache's
    write and read) and ``head_gate``."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    gate: Any = True
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    rotary_dim: int = 0
    rotary_inv_freq: Optional[Tuple[float, ...]] = None
    rotary_amp: float = 1.0
    window: int = 0
    # a paged call longer than this builds its scores this many query rows
    # of one batch row at a time
    query_block: int = QUERY_BLOCK
    dtype: Any = jnp.float32
    decode: bool = False
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    # slots of a window layer's ring (the scheduler's slots)
    state_slots: int = 0
    # a paged call of more than one position holds whole prompts
    whole_prompts: bool = False

    @nn.compact
    def __call__(self, x, positions=None, block_tables=None, state_rows=None):
        b, s, dim = x.shape
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        gate = self.gate
        if not (gate is True or gate is False or gate == "head"):
            raise ValueError(f"gate is True, 'head' or False, got {gate!r}")
        if self.rotary_dim and (
                self.rotary_dim % 2 or self.rotary_dim > hd
                or len(self.rotary_inv_freq or ()) != self.rotary_dim // 2):
            raise ValueError(
                f"rotary_dim {self.rotary_dim} of a head of {hd} needs "
                f"{self.rotary_dim // 2} frequencies, got "
                f"{len(self.rotary_inv_freq or ())}")
        init = nn.initializers.lecun_normal()
        wq = self.param("wq", init, (dim, h * hd), self.dtype)
        wk = self.param("wk", init, (dim, hkv * hd), self.dtype)
        wv = self.param("wv", init, (dim, hkv * hd), self.dtype)
        wo = self.param("wo", init, (h * hd, dim), self.dtype)
        with jax.named_scope("gqa_attention"):
            def whole(a, name):  # the norm over all of a projection's heads
                if not self.qk_norm:
                    return a
                weight = self.param(
                    name, nn.initializers.ones, (a.shape[-1],), self.dtype)
                return rms_norm(a, weight, self.qk_norm_eps)

            q = whole(jnp.dot(x, wq), "q_norm").reshape(b, s, h, hd)
            k = whole(jnp.dot(x, wk), "k_norm").reshape(b, s, hkv, hd)
            v = jnp.dot(x, wv).reshape(b, s, hkv, hd)
            if self.decode and not self.paged:
                raise ValueError(
                    "GroupedQueryAttention has no contiguous cache: decode "
                    "mode is the paged pool's (paged=True)")
            if self.rotary_dim:
                with jax.named_scope("rotary"):
                    at = jnp.broadcast_to(
                        jnp.arange(s, dtype=jnp.int32), (b, s)
                    ) if positions is None else jnp.maximum(positions, 0)
                    turn = functools.partial(
                        rotate_halves, positions=at,
                        inv_freq=self.rotary_inv_freq, amplitude=self.rotary_amp)
                    q, k = turn(q), turn(k)
            scores_scope = "window_attention" if self.window else "full_attention"
            if self.decode and self.window:
                with jax.named_scope(scores_scope):
                    out = window_attention(
                        self, q, k, v, positions, state_rows, window=self.window,
                        state_slots=self.state_slots,
                        block_size=self.kv_block_size, dtype=self.dtype)
            elif self.decode:
                with jax.named_scope(scores_scope):
                    out = paged_attention(
                        self, q, k, v, positions, block_tables,
                        block_size=self.kv_block_size,
                        num_blocks=self.kv_num_blocks, dtype=self.dtype,
                        as_stored=True, query_block=self.query_block,
                        whole_prompts=self.whole_prompts,
                    )
            else:
                group = h // hkv
                scores = jnp.einsum(
                    "bqhgd,bkhd->bhgqk", q.reshape(b, s, hkv, group, hd), k,
                    preferred_element_type=jnp.float32,
                ) / math.sqrt(hd)
                causal = jnp.tril(jnp.ones((s, s), bool))
                if self.window:  # the masked [S, S] form: i - window < j <= i
                    causal &= ~jnp.tril(jnp.ones((s, s), bool), -self.window)
                p = nn.softmax(jnp.where(causal, scores, float("-inf")), axis=-1)
                out = jnp.einsum(
                    "bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32,
                ).astype(x.dtype)
            if gate == "head":
                with jax.named_scope("head_gate"):
                    w_gate = self.param("w_gate", init, (dim, h), self.dtype)
                    out = out.reshape(b, s, h, hd) * jax.nn.sigmoid(
                        jnp.dot(x, w_gate).astype(jnp.float32)
                    ).astype(out.dtype)[..., None]
            out = out.reshape(b, s, h * hd)
            if gate is True:
                w_gate = self.param("w_gate", init, (dim, h * hd), self.dtype)
                out = out * jax.nn.sigmoid(
                    jnp.dot(x, w_gate).astype(jnp.float32)).astype(out.dtype)
            return jnp.dot(out, wo)
