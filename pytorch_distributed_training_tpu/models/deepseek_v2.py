"""DeepSeek-V2 decoder (``model_type: deepseek_v2``) — the second LM family.

What :class:`.transformer_lm.TransformerLM` is not: RMSNorm, no position
table (rotary inside the attention, YaRN-scaled), bias-free projections,
multi-head latent attention whose cache row is one latent a token
(:mod:`..ops.mla`), a gated SwiGLU feed-forward, and a per-layer choice of
it: the first ``first_k_dense_replace`` layers dense, the rest
``n_routed_experts`` dropless experts beside ``n_shared_experts`` shared
ones (:class:`..ops.moe.DroplessMoE`).  The fields are the published
``config.json`` keys under their published names, so a ``model:`` section
is the model card's config with ``name: DeepseekV2`` in front.

Serving only: ``clone(decode=True, paged=True, kv_block_size=,
kv_num_blocks=)`` is the contract of ``serving/decode.py::build_paged_fns``
(``tokens``/``decode_pos`` [B, S] with -1 = padding, ``block_tables`` [B, T]),
and parameters are created and kept in ``dtype`` (at 4 bytes a parameter the
published widths do not fit a chip).  Training is refused by
``engine/topology.py``: the dropless layer has no backward here and the
model's ``seq_aux`` balance loss is not written.

Two things the serving programs learn from the model instead of from its
name: ``is_language_model`` (tokens in, logits out) and ``moe_shape``
(not None: the decode program also returns, summed over the expert layers,
how many experts got a token and the largest count at one expert, sown into
the ``moe_stats`` collection).  ``logit_cols`` [B] asks for the logits of one
column a row: a prefill needs no more, and ``[B, S, 102400]`` in float32
does not fit beside the weights.
"""
from __future__ import annotations

import collections
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.mla import MLAttention
from .lm_parts import (
    GatedMLP, RMSNorm, add_moe_counts, expert_ffn, final_logits, sow_moe_stats,
)

__all__ = ["DeepseekV2LM"]


_LAYER_FIELDS = (
    "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "kv_lora_rank", "rope_theta", "rope_scaling", "rms_norm_eps", "dtype",
    "decode", "paged", "kv_block_size", "kv_num_blocks", "intermediate_size",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
    "experts_held",
)
# the LM's fields a layer reads, as one hashable value (a flax module cannot
# hold its parent as a field)
LayerConfig = collections.namedtuple("LayerConfig", _LAYER_FIELDS)


class DecoderLayer(nn.Module):
    """``h = x + MLA(RMSNorm(x)); x' = h + FFN(RMSNorm(h))`` with the FFN a
    dense SwiGLU or, in an ``expert`` layer, the dropless experts.  Returns
    ``(x', group_sizes)``: how many token-expert pairs each held expert
    computed, ``None`` in a dense layer.  ``config`` is the LM's fields as a
    :class:`LayerConfig`."""

    config: "LayerConfig"
    expert: bool

    @nn.compact
    def __call__(self, x, decode_pos, block_tables, token_mask):
        c = self.config
        b, s, dim = x.shape
        y = RMSNorm(c.rms_norm_eps, c.dtype, name="attn_norm")(x)
        with jax.named_scope("mla_attention"):
            x = x + MLAttention(
                num_heads=c.num_attention_heads,
                qk_nope_head_dim=c.qk_nope_head_dim,
                qk_rope_head_dim=c.qk_rope_head_dim,
                v_head_dim=c.v_head_dim,
                kv_lora_rank=c.kv_lora_rank,
                rope_theta=c.rope_theta,
                rope_scaling=c.rope_scaling,
                rms_norm_eps=c.rms_norm_eps,
                dtype=c.dtype,
                decode=c.decode,
                paged=c.paged,
                kv_block_size=c.kv_block_size,
                kv_num_blocks=c.kv_num_blocks,
                name="attn",
            )(y, decode_pos, block_tables)
        flat = RMSNorm(c.rms_norm_eps, c.dtype, name="ffn_norm")(x).reshape(b * s, dim)
        if not self.expert:
            out = GatedMLP(c.intermediate_size, c.dtype, name="mlp")(flat)
            return x + out.reshape(b, s, dim), None
        out, sizes = expert_ffn(c, flat, token_mask)
        return x + out.reshape(b, s, dim), sizes


class DeepseekV2LM(nn.Module):
    """Causal LM over integer tokens ``[B, S] -> logits [B, S, V]`` (or
    ``[B, 1, V]`` with ``logit_cols``)."""

    # what the serving engine and the training topology ask a model
    is_language_model = True
    # ``__call__`` takes ``logit_cols`` (serving/decode.py's prefill gives it)
    takes_logit_cols = True
    training_unsupported = (
        "DeepseekV2 is served, not trained, by this repository: the dropless "
        "expert layer (ops/moe.py::DroplessMoE) has no backward pass here and "
        "the model's seq_aux balance loss is not written; train with "
        "TransformerLM or serve it through python -m "
        "pytorch_distributed_training_tpu.serving"
    )

    vocab_size: int
    hidden_size: int = 2048
    intermediate_size: int = 10944
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    num_key_value_heads: Optional[int] = None  # MLA: every head has its own k
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    scoring_func: str = "softmax"
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1
    seq_aux: bool = True  # a training loss; nothing of it at inference
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    model_type: str = "deepseek_v2"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Any] = None  # the config's dict (or its items)
    max_position_embeddings: int = 163840
    # which experts this chip holds, ``(first, count)``; None = all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    decode: bool = False
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            # flax hashes a module's fields: keep the dict as its items
            object.__setattr__(
                self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        super().__post_init__()

    @property
    def max_len(self) -> int:
        """The most positions the config declares: a limit, not a table."""
        return self.max_position_embeddings

    @property
    def moe_shape(self) -> Optional[Tuple[int, int, int]]:
        """``(expert layers, experts a token, experts held)``; None for a
        model with no expert layer.  A model that states it returns the
        step's expert counts from its decode program (serving/decode.py)."""
        layers = sum(self._is_expert_layer(i) for i in range(self.num_hidden_layers))
        held = (self.experts_held or (0, self.n_routed_experts))[1]
        return (layers, self.num_experts_per_tok, held) if layers else None

    def _is_expert_layer(self, i: int) -> bool:
        return i >= self.first_k_dense_replace and i % self.moe_layer_freq == 0

    def _check(self):
        unsupported = {
            "q_lora_rank": (self.q_lora_rank, None),
            "scoring_func": (self.scoring_func, "softmax"),
            "topk_method": (self.topk_method, "greedy"),
            "n_group": (self.n_group, 1),
            "hidden_act": (self.hidden_act, "silu"),
            "attention_bias": (self.attention_bias, False),
            "tie_word_embeddings": (self.tie_word_embeddings, False),
            "model_type": (self.model_type, "deepseek_v2"),
        }
        for key, (got, want) in unsupported.items():
            if got != want:
                raise ValueError(
                    f"DeepseekV2: model.{key} = {got!r} is not written "
                    f"(only {want!r})"
                )
        heads = self.num_attention_heads
        if self.num_key_value_heads not in (None, heads):
            raise ValueError(
                "DeepseekV2: latent attention gives every head its own key "
                f"(num_key_value_heads {self.num_key_value_heads} != {heads})"
            )
        scaling = dict(self.rope_scaling) if self.rope_scaling else None
        if scaling and scaling.get("type") != "yarn":
            raise ValueError(
                f"DeepseekV2: rope_scaling type {scaling.get('type')!r} is "
                "not written (only 'yarn')"
            )

    @nn.compact
    def __call__(self, tokens, decode_pos=None, block_tables=None,
                 adapter_ids=None, logit_cols=None):
        self._check()
        if adapter_ids is not None:
            raise ValueError("DeepseekV2 has no LoRA factors")
        if decode_pos is not None and not self.decode:
            raise ValueError("decode_pos given but model was not cloned with decode=True")
        if self.paged and not self.decode:
            raise ValueError("paged KV mode requires decode=True")
        b, s = tokens.shape
        emb = self.param(
            "tok_embedding", nn.initializers.normal(stddev=0.02),
            (self.vocab_size, self.hidden_size), self.dtype,
        )
        x = jnp.take(emb, tokens, axis=0).astype(self.dtype)
        token_mask = None if decode_pos is None else (decode_pos >= 0).reshape(-1)
        counts = add_moe_counts(None, None)
        config = LayerConfig(*(getattr(self, f) for f in _LAYER_FIELDS))
        for i in range(self.num_hidden_layers):
            x, sizes = DecoderLayer(
                config=config, expert=self._is_expert_layer(i), name=f"layer{i}"
            )(x, decode_pos, block_tables, token_mask)
            counts = add_moe_counts(counts, sizes)
        if self.moe_shape:
            sow_moe_stats(self, counts)
        return final_logits(
            x, logit_cols, self.rms_norm_eps, self.vocab_size, self.dtype)
