"""Decoder-only transformer LM — the long-context / sequence-parallel model.

Beyond the reference (image classification only, SURVEY.md §5.7), but
required by the framework's first-class long-context mandate: a GPT-style
causal LM whose every component is *per-token*, which is what makes
sequence parallelism exact — with the loss summed per token and normalized
by the global token count, every parameter gradient is a partial sum, and
one ``psum`` over the (data, sequence) axes reconstructs the exact global
gradient (see ``engine.sp_steps``).

With ``seq_axis`` set the model must run inside ``shard_map`` with that
mesh axis in scope, taking token shards ``[B, S/n]``; attention runs as
ring attention (or Ulysses) over the axis, and the position embedding is
sliced to the shard via ``lax.axis_index``.  With ``seq_axis=None`` the
same module is an ordinary single-shard LM — the two configurations share
identical parameter shapes, so init happens once (unsharded) and the params
are fed to the sharded step.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.attention import MultiHeadAttention
from .vit import MLP

__all__ = ["TransformerLM"]


def resolve_remat_policy(name: str):
    """``model.remat_policy`` -> jax checkpoint policy (None = nothing
    saveable, flax's nn.remat default).  Shared by the plain/GSPMD paths
    (this module) and the pipeline step's own scan-level remat wrapper
    (engine/pp_steps.py) so the mapping cannot drift.  Raises on unknown
    names even when remat is off."""
    policies = {
        "nothing": None,
        # matmul outputs saved, elementwise recomputed: +8.6% tokens/sec
        # for remat runs on the bench chip (PERF.md round 4)
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # every dot saved (incl. batch dims — attention scores too):
        # more memory than "dots", less recompute; the third point on the
        # memory/recompute curve for training.remat sweeps
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
    }
    if name not in policies:
        raise ValueError(
            f"model.remat_policy must be one of {sorted(policies)}, "
            f"got {name!r}"
        )
    return policies[name]


class DecoderBlock(nn.Module):
    num_heads: int
    mlp_ratio: float
    seq_axis: Optional[str]
    seq_impl: str
    dtype: Any = jnp.float32
    # mesh hint for the GSPMD flash island (ops/attention.py); set by the
    # GSPMD step builders via TransformerLM.flash_mesh
    flash_mesh: Optional[Any] = None
    # MoE (ops/moe.py): experts > 0 swaps the dense MLP for a top-k routed
    # mixture; the residual around it means capacity-dropped tokens pass
    # through unchanged
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # KV-cache decode (serving/decode.py; see ops/attention.py): static
    # flag + cache capacity, with the per-call position carried alongside
    # the activations.  Params are unchanged, so train-time checkpoints
    # serve directly.
    decode: bool = False
    cache_len: int = 0
    # Paged KV cache (serving/kv_pool.py): blocks of ``kv_block_size`` token
    # rows from a shared ``kv_num_blocks`` pool, addressed per call through
    # ``block_tables`` — see MultiHeadAttention.paged.
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    # Fuse the residual-add+ln2 and fc1-bias+gelu elementwise tails into
    # single Pallas kernels (ops/fused_elementwise.py).  Same parameter
    # tree either way (checkpoint-compatible); off by default.
    fused_tails: bool = False
    # Multi-LoRA serving (serving/lora.py): stacked per-adapter low-rank
    # factors on the attention qkv/proj Denses, selected per batch row
    # via ``adapter_ids`` — see MultiHeadAttention.lora_rank.
    lora_rank: int = 0
    lora_adapters: int = 0

    @nn.compact
    def __call__(self, x, decode_pos=None, block_tables=None, adapter_ids=None):
        dim = x.shape[-1]
        y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        attn_out = MultiHeadAttention(
            num_heads=self.num_heads,
            causal=True,
            seq_axis=self.seq_axis,
            seq_impl=self.seq_impl,
            dtype=self.dtype,
            flash_mesh=self.flash_mesh,
            decode=self.decode,
            cache_len=self.cache_len,
            paged=self.paged,
            kv_block_size=self.kv_block_size,
            kv_num_blocks=self.kv_num_blocks,
            lora_rank=self.lora_rank,
            lora_adapters=self.lora_adapters,
            name="attn",
        )(y, decode_pos, block_tables, adapter_ids)
        if self.fused_tails and self.moe_experts == 0:
            from ..ops.fused_elementwise import FusedResidualLayerNorm

            # one kernel emits BOTH the new residual stream and its LN —
            # ln1 has no preceding add (its input IS the stream) and the
            # final x+mlp add feeds the next block's ln1 across the block
            # boundary (out of scope for a per-block module), so add+ln2
            # is the fusable pair
            x, y = FusedResidualLayerNorm(dtype=self.dtype, name="ln2")(x, attn_out)
        else:
            x = x + attn_out
            y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        if self.moe_experts > 0:
            from ..ops.moe import MoEMLP

            return x + MoEMLP(
                num_experts=self.moe_experts,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                hidden=int(dim * self.mlp_ratio),
                out=dim,
                aux_weight=self.moe_aux_weight,
                dtype=self.dtype,
                name="moe",
            )(y)
        return x + MLP(
            hidden=int(dim * self.mlp_ratio), out=dim, dtype=self.dtype,
            fused_tails=self.fused_tails, name="mlp",
        )(y)


class TransformerLM(nn.Module):
    """Causal LM over integer tokens ``[B, S(_local)] -> logits [B, S, V]``."""

    # what serving/engine.py and engine/topology.py ask of a model's class
    is_language_model = True

    vocab_size: int
    max_len: int = 1024
    embed_dim: int = 256
    depth: int = 4
    num_heads: int = 8
    mlp_ratio: float = 4.0
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    remat: bool = False
    # Remat policy when ``remat`` is on (config ``model.remat_policy``):
    # "nothing" (default: full recompute, minimal memory) or "dots"
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable: matmul
    # outputs saved, elementwise recomputed — part of the memory saving at
    # a fraction of the recompute; swept on the bench chip, PERF.md r4).
    remat_policy: str = "nothing"
    dtype: Any = jnp.float32
    # MoE (beyond reference; ops/moe.py): every ``moe_every``-th block uses
    # a routed mixture of ``moe_experts`` expert MLPs (0 = dense everywhere).
    # Expert weights stack [E, ...] and shard over the ``model`` mesh axis
    # under training.tensor_parallelism (= expert parallelism).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_every: int = 2
    # Mesh hint for the GSPMD flash island: the GSPMD step builders
    # (engine/tp_steps) clone the model with the step's mesh so attention
    # runs the Pallas flash kernel inside a shard_map island instead of
    # the O(S^2) einsum the partitioner would otherwise get.  Static
    # config only — parameter shapes/values are unchanged.
    flash_mesh: Optional[Any] = None
    # Fuse the per-block elementwise tails (residual-add+ln2, fc1
    # bias+gelu) into single Pallas kernels — config ``model.fused_tails``.
    # Checkpoint-compatible both ways.
    fused_tails: bool = False
    # KV-cache incremental decode (serving): ``model.clone(decode=True)``
    # gives the serving-side module — same params, plus a "cache" variable
    # collection of capacity ``max_len`` per block.  ``__call__`` with
    # ``decode_pos=None`` is the prefill over the prompt; with ``decode_pos``
    # ([B] int32 per-row positions) it consumes one token per row and
    # returns its logits.  Mutually exclusive with seq_axis/MoE (serving is
    # single-shard dense; enforced below).
    decode: bool = False
    # Paged KV cache (serving/kv_pool.py): with ``decode=True, paged=True``
    # the per-layer cache is a shared pool of ``kv_num_blocks`` blocks of
    # ``kv_block_size`` token rows; ``decode_pos`` becomes [B, S] per-token
    # global positions (-1 = padding) and ``block_tables`` [B, T] maps each
    # row's logical blocks to physical pool blocks, so one program shape
    # covers cold prefill, prefix-hit chunked prefill, and S=1 decode.
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    # Multi-LoRA multiplexing (serving/lora.py): ``lora_rank > 0`` adds
    # stacked per-adapter factors to every block's attention qkv/proj
    # ([lora_adapters, ...] leaves in the params tree; base shapes are
    # unchanged, so plain checkpoints still restore).  ``adapter_ids``
    # [B] int32 selects each row's adapter per call; -1 = base model.
    lora_rank: int = 0
    lora_adapters: int = 0

    @nn.compact
    def __call__(self, tokens, decode_pos=None, block_tables=None, adapter_ids=None):
        if self.moe_experts > 0 and self.moe_every < 1:
            raise ValueError(f"moe_every must be >= 1, got {self.moe_every}")
        if self.decode and self.seq_axis is not None:
            raise ValueError("decode mode is single-shard: seq_axis must be None")
        if self.decode and self.moe_experts > 0:
            raise ValueError("decode mode does not support MoE blocks yet")
        if decode_pos is not None and not self.decode:
            raise ValueError("decode_pos given but model was not cloned with decode=True")
        if self.paged and not self.decode:
            raise ValueError("paged KV mode requires decode=True")
        if self.paged and decode_pos is not None and block_tables is None:
            raise ValueError("paged KV mode needs block_tables alongside decode_pos")
        if adapter_ids is not None and self.lora_rank <= 0:
            raise ValueError(
                "adapter_ids given but the model has no LoRA factors "
                "(clone with lora_rank/lora_adapters set)"
            )
        b, s = tokens.shape
        emb = self.param(
            "tok_embedding",
            nn.initializers.normal(stddev=0.02),
            (self.vocab_size, self.embed_dim),
            jnp.float32,
        )
        pos = self.param(
            "pos_embedding",
            nn.initializers.normal(stddev=0.02),
            (self.max_len, self.embed_dim),
            jnp.float32,
        )
        x = jnp.take(emb, tokens, axis=0).astype(self.dtype)
        if decode_pos is not None and self.paged:
            # paged decode_pos is [B, S] per-token global positions; -1
            # padding clamps to row 0 (its output is discarded by the host)
            pe = jnp.take(
                pos, jnp.clip(decode_pos, 0, self.max_len - 1), axis=0
            )  # [B, S, E]
        elif decode_pos is not None:
            # one new token per row at its own position: gather that row's
            # position embedding instead of slicing a shared prefix
            pe = jnp.take(pos, decode_pos, axis=0)[:, None]  # [B, 1, E]
        elif self.seq_axis is not None and not self.is_initializing():
            # local shard i holds global positions [i*s, (i+1)*s)
            n_seq = jax.lax.psum(1, self.seq_axis)  # static axis size
            if s * n_seq > self.max_len:
                # dynamic_slice would clamp silently, giving shards beyond
                # max_len the SAME position rows — fail loudly instead
                raise ValueError(
                    f"global sequence {s * n_seq} (= {s} local x {n_seq} shards)"
                    f" exceeds max_len {self.max_len}"
                )
            off = jax.lax.axis_index(self.seq_axis) * s
            pe = jax.lax.dynamic_slice_in_dim(pos, off, s, axis=0)[None]
        else:
            pe = pos[:s][None]
        x = x + pe.astype(self.dtype)
        # remat (rematerialization): recompute block activations in the
        # backward pass instead of storing them — trades ~1/3 extra FLOPs
        # for O(depth) less activation HBM, the standard long-context lever
        # (config: model.remat: true).  Parameter shapes/values are
        # unchanged, so remat toggling is checkpoint-compatible.
        # validated regardless of ``remat`` so a typo'd policy on a
        # remat-off config fails at init, not silently much later
        policy = resolve_remat_policy(self.remat_policy)
        block_cls = (
            nn.remat(DecoderBlock, policy=policy) if self.remat
            else DecoderBlock
        )
        for i in range(self.depth):
            # GShard convention: MoE in every moe_every-th block (the
            # (moe_every-1) offset puts the first MoE at block 1 for the
            # default stride 2, matching the usual dense-first layout)
            is_moe_block = (
                self.moe_experts > 0 and i % self.moe_every == self.moe_every - 1
            )
            x = block_cls(
                num_heads=self.num_heads,
                mlp_ratio=self.mlp_ratio,
                seq_axis=self.seq_axis if not self.is_initializing() else None,
                seq_impl=self.seq_impl,
                dtype=self.dtype,
                moe_experts=self.moe_experts if is_moe_block else 0,
                moe_top_k=self.moe_top_k,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_aux_weight=self.moe_aux_weight,
                flash_mesh=(
                    self.flash_mesh if not self.is_initializing() else None
                ),
                decode=self.decode,
                cache_len=self.max_len if self.decode else 0,
                paged=self.paged,
                kv_block_size=self.kv_block_size,
                kv_num_blocks=self.kv_num_blocks,
                fused_tails=self.fused_tails,
                lora_rank=self.lora_rank,
                lora_adapters=self.lora_adapters,
                name=f"block{i}",
            )(x, decode_pos, block_tables, adapter_ids)
        # a scope of the trace, not of the parameters: the final norm and
        # the logits matmul read as ``loss_head`` with the CE that follows
        with jax.named_scope("loss_head"):
            x = nn.LayerNorm(dtype=self.dtype, name="ln")(x)
            return nn.Dense(self.vocab_size, dtype=jnp.float32, name="head")(x)
