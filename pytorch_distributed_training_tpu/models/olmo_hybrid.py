"""Olmo-Hybrid decoder (``model_type: olmo_hybrid``) — the fifth LM family: a
dense hybrid of linear and full attention under Olmo's post-norm block.

Every layer is ``h = x + RMSNorm(Mixer(x)); x' = h + RMSNorm(MLP(h))``: the
norm is on the sublayer's OUTPUT and nothing is normalised before it (the
Olmo 2 / Olmo 3 convention, arXiv:2501.00656).  The mixer of layer ``i`` is
chosen by ``layer_types[i]``: ``linear_attention`` is Gated DeltaNet
(:class:`..ops.gated_delta.GatedDeltaNet`, the config's ``linear_*`` keys:
one decay a head, a ``[linear_num_value_heads, linear_key_head_dim,
linear_value_head_dim]`` float32 state a sequence), ``full_attention`` is
softmax attention with as many K/V heads as query heads, an RMSNorm over the
whole projection of ``q`` and of ``k`` and no rotary term
(``rope_parameters.rope_theta: null``;
:class:`..ops.attention.GroupedQueryAttention` with ``qk_norm``, no gate).
``MLP(x) = W_down(silu(W_gate x) * W_up x)`` of ``intermediate_size`` in every
layer (:class:`.lm_parts.GatedMLP`); no bias but the decay's ``dt_bias``;
final RMSNorm and an untied head.  The fields are the published
``config.json`` keys under their published names, so a ``model:`` section is
the model card's config with ``name: OlmoHybrid`` in front; only the first
``num_hidden_layers`` entries of ``layer_types`` are built.

Serving only, as :mod:`.solar_open2` (whose norm and head it shares through
:mod:`.lm_parts`): parameters are created and kept in ``dtype``,
``clone(decode=True, paged=True, kv_block_size=, kv_num_blocks=,
state_slots=)`` is the contract of ``serving/decode.py::build_paged_fns``.
What the serving programs learn from the class: ``is_language_model``,
``takes_logit_cols``, no ``moe_shape`` (a dense model: the decode program has
three outputs) and ``state_shape`` — a third kind of state a sequence (the
Gated DeltaNet layers'), in ``[slots, ...]`` leaves beside the pool's rows,
addressed by ``state_rows [B]`` in every paged call.
"""
from __future__ import annotations

import collections
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.attention import GroupedQueryAttention
from ..ops.gated_delta import GatedDeltaNet
from .lm_parts import GatedMLP, RMSNorm, final_logits

__all__ = ["OlmoHybridLM"]

LINEAR, FULL = "linear_attention", "full_attention"
# query rows of one batch row a prefill scores at once: all 30 heads against
# a row's whole block table (3,072 positions in the benchmark's cell) are
# 47 MB of float32 scores at 128 rows; the shared layer's 512, and every row
# of a 32 x 256 call at once (256 is no more than 512), were 3.0 GB twice
QUERY_BLOCK = 128

_LAYER_FIELDS = (
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "linear_allow_neg_eigval", "intermediate_size",
    "rms_norm_eps", "dtype", "decode", "paged", "kv_block_size",
    "kv_num_blocks", "state_slots",
)
# the LM's fields a layer reads, as one hashable value (a flax module cannot
# hold its parent as a field)
LayerConfig = collections.namedtuple("OlmoHybridLayerConfig", _LAYER_FIELDS)


class DecoderLayer(nn.Module):
    """One post-norm layer; ``kind`` (an entry of ``layer_types``) chooses
    the mixer."""

    config: "LayerConfig"
    kind: str

    @nn.compact
    def __call__(self, x, positions, block_tables, state_rows,
                 rows_are_slots=False):
        c = self.config
        b, s, dim = x.shape
        if self.kind == FULL:
            y = GroupedQueryAttention(
                num_heads=c.num_attention_heads,
                num_kv_heads=c.num_key_value_heads,
                head_dim=c.head_dim,
                gate=False,
                qk_norm=True,
                qk_norm_eps=c.rms_norm_eps,
                query_block=QUERY_BLOCK,
                dtype=c.dtype,
                decode=c.decode,
                paged=c.paged,
                kv_block_size=c.kv_block_size,
                kv_num_blocks=c.kv_num_blocks,
                # a model that carries a state is prefilled a whole prompt a call
                # (``ContinuousScheduler._refuse_a_piece``)
                whole_prompts=bool(c.state_slots),
                name="attn",
            )(x, positions, block_tables)
        else:
            y = GatedDeltaNet(
                num_heads=c.linear_num_value_heads,
                key_dim=c.linear_key_head_dim,
                value_dim=c.linear_value_head_dim,
                conv_size=c.linear_conv_kernel_dim,
                allow_neg_eigval=c.linear_allow_neg_eigval,
                rms_norm_eps=c.rms_norm_eps,
                dtype=c.dtype,
                decode=c.decode,
                state_slots=c.state_slots,
                name="gdn",
            )(x, positions, state_rows, rows_are_slots)
        x = x + RMSNorm(c.rms_norm_eps, c.dtype, name="attn_norm")(y)
        with jax.named_scope("mlp"):
            y = GatedMLP(c.intermediate_size, c.dtype, name="mlp")(
                x.reshape(b * s, dim)).reshape(b, s, dim)
        return x + RMSNorm(c.rms_norm_eps, c.dtype, name="ffn_norm")(y)


class OlmoHybridLM(nn.Module):
    """Causal LM over integer tokens ``[B, S] -> logits [B, S, V]`` (or
    ``[B, 1, V]`` with ``logit_cols``)."""

    is_language_model = True
    takes_logit_cols = True
    moe_shape = None  # a dense model
    training_unsupported = (
        "OlmoHybrid is served, not trained, by this repository: the chunked "
        "scalar-decay delta-rule scan (ops/gated_delta.py) has no backward "
        "pass here; train with TransformerLM or serve it through python -m "
        "pytorch_distributed_training_tpu.serving"
    )

    vocab_size: int
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    head_dim: Optional[int] = None  # null in the config: hidden / heads
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    layer_types: Optional[Tuple[str, ...]] = None  # None: (linear x 3, full) repeated
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_parameters: Optional[Any] = None  # the config's dict (or its items)
    model_type: str = "olmo_hybrid"
    dtype: Any = jnp.float32
    decode: bool = False
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    # slots of the per-sequence state (the scheduler's slots)
    state_slots: int = 0

    def __post_init__(self):
        # flax hashes a module's fields: dicts as their items, lists as tuples
        kinds = self.layer_types
        if kinds is None:
            kinds = ((LINEAR,) * 3 + (FULL,)) * (-(-self.num_hidden_layers // 4))
        object.__setattr__(self, "layer_types", tuple(kinds))
        rope = self.rope_parameters
        if isinstance(rope, dict):
            rope = tuple(sorted(rope.items()))
        object.__setattr__(self, "rope_parameters", tuple(rope or ()))
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_attention_heads)
        super().__post_init__()

    @property
    def max_len(self) -> int:
        """The most positions the config declares: a limit, not a table."""
        return self.max_position_embeddings

    def _kinds(self) -> Tuple[str, ...]:
        return self.layer_types[:self.num_hidden_layers]

    @property
    def state_shape(self) -> Optional[Tuple[int, int, int, int]]:
        """``(layers that carry a state, heads, d_k, d_v)`` of the float32
        state a sequence (:attr:`.solar_open2.SolarOpen2LM.state_shape`)."""
        layers = sum(kind == LINEAR for kind in self._kinds())
        return (layers, self.linear_num_value_heads, self.linear_key_head_dim,
                self.linear_value_head_dim) if layers else None

    def _check(self):
        unsupported = {
            "hidden_act": (self.hidden_act, "silu"),
            "attention_bias": (self.attention_bias, False),
            "tie_word_embeddings": (self.tie_word_embeddings, False),
            "model_type": (self.model_type, "olmo_hybrid"),
            # no base given is read as no rotary term; a base would change
            # the full layers' scores and is not written
            "rope_parameters.rope_theta": (
                dict(self.rope_parameters).get("rope_theta"), None),
            "linear_num_key_heads": (
                self.linear_num_key_heads, self.linear_num_value_heads),
        }
        for key, (got, want) in unsupported.items():
            if got != want:
                raise ValueError(
                    f"OlmoHybrid: model.{key} = {got!r} is not written "
                    f"(only {want!r})"
                )
        kinds = self._kinds()
        if len(kinds) < self.num_hidden_layers or set(kinds) - {LINEAR, FULL}:
            raise ValueError(
                f"OlmoHybrid: model.layer_types must name {self.num_hidden_layers} "
                f"layers, each {LINEAR!r} or {FULL!r}; got {kinds!r}")

    @nn.compact
    def __call__(self, tokens, decode_pos=None, block_tables=None,
                 adapter_ids=None, logit_cols=None, state_rows=None,
                 rows_are_slots=False):
        self._check()
        if adapter_ids is not None:
            raise ValueError("OlmoHybrid has no LoRA factors")
        if decode_pos is not None and not self.decode:
            raise ValueError("decode_pos given but model was not cloned with decode=True")
        if self.decode and not self.paged:
            raise ValueError(
                "OlmoHybrid carries a state a sequence and has no contiguous "
                "cache: decode mode is the paged scheduler's (paged=True)")
        emb = self.param(
            "tok_embedding", nn.initializers.normal(stddev=0.02),
            (self.vocab_size, self.hidden_size), self.dtype,
        )
        x = jnp.take(emb, tokens, axis=0).astype(self.dtype)
        config = LayerConfig(*(getattr(self, f) for f in _LAYER_FIELDS))
        for i, kind in enumerate(self._kinds()):
            x = DecoderLayer(config=config, kind=kind, name=f"layer{i}")(
                x, decode_pos, block_tables, state_rows, rows_are_slots)
        return final_logits(
            x, logit_cols, self.rms_norm_eps, self.vocab_size, self.dtype)
