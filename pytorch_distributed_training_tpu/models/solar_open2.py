"""Solar-Open2 decoder (``model_type: solar_open2``) — the third LM family:
a hybrid of linear and full attention over dropless experts.

Every layer is ``h = x + Mixer(RMSNorm(x)); x' = h + Experts(RMSNorm(h))``,
no bias anywhere but the decay's ``dt_bias``.  The mixer of a layer in
``gqa_layers`` is grouped-query softmax attention with no position term
(``use_rope: false``) and an output gate (``use_gqa_gate``;
:class:`..ops.attention.GroupedQueryAttention`); every other layer's is
Kimi Delta Attention (:class:`..ops.kda.KimiDeltaAttention`, the config's
``kda_*`` keys and ``linear_attn_config``).  ``first_k_dense_replace: 0``:
every layer has ``n_routed_experts`` dropless SwiGLU experts of
``moe_intermediate_size`` beside ``n_shared_experts`` shared, gates
renormalised over the chosen (``norm_topk_prob``;
:class:`..ops.moe.DroplessMoE`); ``intermediate_size`` is the width of a
dense layer this model does not have.  The fields are the published
``config.json`` keys under their published names plus ``experts_held``, so
a ``model:`` section is the model card's config with ``name: SolarOpen2`` in
front.

Serving only, as :mod:`.deepseek_v2` (whose norm, head, expert layer and
expert counts it shares through :mod:`.lm_parts`): parameters are created
and kept in ``dtype``, ``clone(decode=True, paged=True, kv_block_size=,
kv_num_blocks=, state_slots=)`` is the contract of
``serving/decode.py::build_paged_fns``.  What the serving programs learn from
the class: ``is_language_model``, ``takes_logit_cols``, ``moe_shape`` and
``state_shape`` — not None: the model carries a fixed-size state a sequence
(the KDA layers'), its cache tree holds ``[slots, ...]`` leaves beside the
pool's ``[pool_rows, ...]`` ones, and every paged call names each row's
slot in ``state_rows [B]``.
"""
from __future__ import annotations

import collections
from typing import Any, Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn

from ..ops.attention import GroupedQueryAttention
from ..ops.kda import KimiDeltaAttention
from .lm_parts import (
    RMSNorm, add_moe_counts, expert_ffn, final_logits, sow_moe_stats,
)

__all__ = ["SolarOpen2LM"]

_LAYER_FIELDS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "use_gqa_gate",
    "linear_attn_config", "kda_allow_neg_eigval", "rms_norm_eps", "dtype",
    "decode", "paged", "kv_block_size", "kv_num_blocks", "state_slots",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
    "experts_held",
)
# the LM's fields a layer reads, as one hashable value (a flax module cannot
# hold its parent as a field)
LayerConfig = collections.namedtuple("SolarLayerConfig", _LAYER_FIELDS)


class DecoderLayer(nn.Module):
    """One layer; ``full`` chooses the mixer.  Returns ``(x',
    group_sizes)`` as :class:`.deepseek_v2.DecoderLayer` does."""

    config: "LayerConfig"
    full: bool

    @nn.compact
    def __call__(self, x, positions, block_tables, state_rows, token_mask,
                 rows_are_slots=False):
        c = self.config
        b, s, dim = x.shape
        y = RMSNorm(c.rms_norm_eps, c.dtype, name="attn_norm")(x)
        if self.full:
            x = x + GroupedQueryAttention(
                num_heads=c.num_attention_heads,
                num_kv_heads=c.num_key_value_heads,
                head_dim=c.head_dim,
                gate=c.use_gqa_gate,
                dtype=c.dtype,
                decode=c.decode,
                paged=c.paged,
                kv_block_size=c.kv_block_size,
                kv_num_blocks=c.kv_num_blocks,
                # a model that carries a state is prefilled a whole prompt a call
                # (``ContinuousScheduler._refuse_a_piece``)
                whole_prompts=bool(c.state_slots),
                name="attn",
            )(y, positions, block_tables)
        else:
            linear = dict(c.linear_attn_config)
            x = x + KimiDeltaAttention(
                num_heads=linear["num_heads"],
                head_dim=linear["head_dim"],
                conv_size=linear["short_conv_kernel_size"],
                allow_neg_eigval=c.kda_allow_neg_eigval,
                rms_norm_eps=c.rms_norm_eps,
                dtype=c.dtype,
                decode=c.decode,
                state_slots=c.state_slots,
                name="kda",
            )(y, positions, state_rows, rows_are_slots)
        flat = RMSNorm(c.rms_norm_eps, c.dtype, name="ffn_norm")(x).reshape(b * s, dim)
        out, sizes = expert_ffn(c, flat, token_mask)
        return x + out.reshape(b, s, dim), sizes


class SolarOpen2LM(nn.Module):
    """Causal LM over integer tokens ``[B, S] -> logits [B, S, V]`` (or
    ``[B, 1, V]`` with ``logit_cols``)."""

    is_language_model = True
    takes_logit_cols = True
    training_unsupported = (
        "SolarOpen2 is served, not trained, by this repository: the dropless "
        "expert layer (ops/moe.py::DroplessMoE) and the chunked delta-rule "
        "scan (ops/kda.py) have no backward pass here; train with "
        "TransformerLM or serve it through python -m "
        "pytorch_distributed_training_tpu.serving"
    )

    vocab_size: int
    hidden_size: int = 4096
    intermediate_size: int = 10240  # a dense layer's width: this model has none
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_attn_config: Optional[Any] = None  # the config's dict (or its items)
    gqa_interval: int = 3
    gqa_layers: Optional[Tuple[int, ...]] = None
    use_gqa_gate: bool = True
    use_rope: bool = False
    partial_rotary_factor: float = 1.0
    rope_theta: float = 10000.0
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    tie_word_embeddings: bool = False
    model_type: str = "solar_open2"
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    # which experts this chip holds, ``(first, count)``; None = all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    decode: bool = False
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    # slots of the per-sequence state (the scheduler's slots)
    state_slots: int = 0

    def __post_init__(self):
        # flax hashes a module's fields: dicts as their items, lists as tuples
        linear = self.linear_attn_config
        if linear is None:
            linear = {
                "short_conv_kernel_size": 4, "head_dim": self.head_dim,
                "num_heads": self.num_attention_heads, "num_kv_heads": None,
            }
        if isinstance(linear, dict):
            linear = tuple(sorted(linear.items()))
        object.__setattr__(self, "linear_attn_config", tuple(linear))
        layers = self.gqa_layers
        if layers is None:
            layers = range(0, self.num_hidden_layers, self.gqa_interval + 1)
        object.__setattr__(self, "gqa_layers", tuple(int(i) for i in layers))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        super().__post_init__()

    @property
    def max_len(self) -> int:
        """The most positions the config declares: a limit, not a table."""
        return self.max_position_embeddings

    @property
    def moe_shape(self) -> Tuple[int, int, int]:
        """``(expert layers, experts a token, experts held)``: every layer
        has experts."""
        held = (self.experts_held or (0, self.n_routed_experts))[1]
        return (self.num_hidden_layers, self.num_experts_per_tok, held)

    @property
    def state_shape(self) -> Optional[Tuple[int, int, int, int]]:
        """``(layers that carry a state, heads, d_k, d_v)`` of the float32
        state a sequence; None for a model with no such layer.  A model that
        states it takes ``state_rows`` in every paged call
        (serving/decode.py), and the serving layers that assume a cache of
        token rows alone refuse it."""
        linear = dict(self.linear_attn_config)
        layers = sum(not self._is_full_layer(i) for i in range(self.num_hidden_layers))
        d = linear["head_dim"]
        return (layers, linear["num_heads"], d, d) if layers else None

    def _is_full_layer(self, i: int) -> bool:
        return i in self.gqa_layers

    def _check(self):
        unsupported = {
            "use_rope": (self.use_rope, False),
            "kda_use_full_proj": (self.kda_use_full_proj, False),
            "first_k_dense_replace": (self.first_k_dense_replace, 0),
            "tie_word_embeddings": (self.tie_word_embeddings, False),
            "model_type": (self.model_type, "solar_open2"),
            "linear_attn_config.num_kv_heads": (
                dict(self.linear_attn_config).get("num_kv_heads"), None),
        }
        for key, (got, want) in unsupported.items():
            if got != want:
                raise ValueError(
                    f"SolarOpen2: model.{key} = {got!r} is not written "
                    f"(only {want!r})"
                )

    @nn.compact
    def __call__(self, tokens, decode_pos=None, block_tables=None,
                 adapter_ids=None, logit_cols=None, state_rows=None,
                 rows_are_slots=False):
        self._check()
        if adapter_ids is not None:
            raise ValueError("SolarOpen2 has no LoRA factors")
        if decode_pos is not None and not self.decode:
            raise ValueError("decode_pos given but model was not cloned with decode=True")
        if self.decode and not self.paged:
            raise ValueError(
                "SolarOpen2 carries a state a sequence and has no contiguous "
                "cache: decode mode is the paged scheduler's (paged=True)")
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
                config=config, full=self._is_full_layer(i), name=f"layer{i}"
            )(x, decode_pos, block_tables, state_rows, token_mask, rows_are_slots)
            counts = add_moe_counts(counts, sizes)
        sow_moe_stats(self, counts)
        return final_logits(
            x, logit_cols, self.rms_norm_eps, self.vocab_size, self.dtype)
