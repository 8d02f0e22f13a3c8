"""Nemotron-H decoder (``model_type: nemotron_h``) — the fourth LM family: a
stack whose every layer is ONE mixer behind one norm.

``x <- x + Mixer_i(RMSNorm(x))``, the mixer of layer ``i`` chosen by the
character ``hybrid_override_pattern[i]``: ``M`` a Mamba-2 state-space layer
(:class:`..ops.mamba2.Mamba2Mixer`; ``mamba_*``, ``n_groups``,
``ssm_state_size``, ``conv_kernel``, ``chunk_size``), ``E`` latent experts
(:class:`..ops.moe.DroplessMoE` with sigmoid scores chosen under a
correction bias, ``relu2`` experts in a latent of ``moe_latent_size`` and a
shared expert of ``moe_shared_expert_intermediate_size``), ``*`` grouped-query
softmax attention with no gate and no position term
(:class:`..ops.attention.GroupedQueryAttention`).  There is no (attention,
feed-forward) pair: a feed-forward part is a layer of its own, with its own
norm and residual.  No bias but the convolution's; final RMSNorm and an
untied head.  The fields are the published ``config.json`` keys under their
published names plus ``experts_held``, so a ``model:`` section is the model
card's config with ``name: NemotronH`` in front; only the first
``num_hidden_layers`` characters of the pattern are built.

NOT built: the multi-token-prediction module (``num_nextn_predict_layers``,
``mtp_hybrid_override_pattern``).  The config does not fix its equations,
plain serving never evaluates it, and as the model's own draft it needs what
a model that carries a state is refused today: a state snapshot to roll a
rejected draft back.  The two keys are carried and nothing is made of them.
A ``-`` layer (a dense ``relu2`` MLP of ``intermediate_size``) does not occur
in the published pattern and is refused.

Serving only, as :mod:`.solar_open2` (whose norm, head, expert layer and
expert counts it shares through :mod:`.lm_parts`): parameters are created
and kept in ``dtype``, ``clone(decode=True, paged=True, kv_block_size=,
kv_num_blocks=, state_slots=)`` is the contract of
``serving/decode.py::build_paged_fns``.  What the serving programs learn from
the class: ``is_language_model``, ``takes_logit_cols``, ``moe_shape`` and
``state_shape`` — a second kind of state a sequence (the Mamba layers'),
in ``[slots, ...]`` leaves beside the pool's rows, addressed by
``state_rows [B]`` in every paged call.
"""
from __future__ import annotations

import collections
from typing import Any, Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn

from ..ops.attention import GroupedQueryAttention
from ..ops.mamba2 import Mamba2Mixer
from .lm_parts import (
    RMSNorm, add_moe_counts, expert_ffn, final_logits, sow_moe_stats,
)

__all__ = ["NemotronHLM"]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

_LAYER_FIELDS = (
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "use_conv_bias", "time_step_min",
    "time_step_max", "time_step_floor", "layer_norm_epsilon", "dtype",
    "decode", "paged", "kv_block_size", "kv_num_blocks", "state_slots",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
    "experts_held", "moe_form",
)
# the LM's fields a layer reads, as one hashable value (a flax module cannot
# hold its parent as a field)
LayerConfig = collections.namedtuple("NemotronLayerConfig", _LAYER_FIELDS)


class DecoderLayer(nn.Module):
    """One layer: the norm, ONE mixer of ``kind``, the residual.  Returns
    ``(x', group_sizes)``, the sizes ``None`` where the layer has no
    experts."""

    config: "LayerConfig"
    kind: str

    @nn.compact
    def __call__(self, x, positions, block_tables, state_rows, token_mask,
                 rows_are_slots=False):
        c = self.config
        b, s, dim = x.shape
        y = RMSNorm(c.layer_norm_epsilon, c.dtype, name="norm")(x)
        if self.kind == MAMBA:
            return x + Mamba2Mixer(
                num_heads=c.mamba_num_heads,
                head_dim=c.mamba_head_dim,
                n_groups=c.n_groups,
                state_size=c.ssm_state_size,
                conv_size=c.conv_kernel,
                chunk_size=c.chunk_size,
                conv_bias=c.use_conv_bias,
                rms_norm_eps=c.layer_norm_epsilon,
                dt_init=(c.time_step_min, c.time_step_max, c.time_step_floor),
                dtype=c.dtype,
                decode=c.decode,
                state_slots=c.state_slots,
                name="mamba",
            )(y, positions, state_rows, rows_are_slots), None
        if self.kind == EXPERTS:
            out, sizes = expert_ffn(c, y.reshape(b * s, dim), token_mask)
            return x + out.reshape(b, s, dim), sizes
        return x + GroupedQueryAttention(
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads,
            head_dim=c.head_dim,
            gate=False,
            dtype=c.dtype,
            decode=c.decode,
            paged=c.paged,
            kv_block_size=c.kv_block_size,
            kv_num_blocks=c.kv_num_blocks,
            # a model that carries a state is prefilled a whole prompt a call
            # (``ContinuousScheduler._refuse_a_piece``)
            whole_prompts=bool(c.state_slots),
            name="attn",
        )(y, positions, block_tables), None


class NemotronHLM(nn.Module):
    """Causal LM over integer tokens ``[B, S] -> logits [B, S, V]`` (or
    ``[B, 1, V]`` with ``logit_cols``)."""

    is_language_model = True
    takes_logit_cols = True
    training_unsupported = (
        "NemotronH is served, not trained, by this repository: the dropless "
        "expert layer (ops/moe.py::DroplessMoE) and the chunked state-space "
        "scan (ops/mamba2.py) have no backward pass here; train with "
        "TransformerLM or serve it through python -m "
        "pytorch_distributed_training_tpu.serving"
    )

    vocab_size: int
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    # Mamba-2 layers
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    mamba_hidden_act: str = "silu"
    mamba_proj_bias: bool = False
    use_conv_bias: bool = True
    use_mamba_kernels: bool = True   # an implementation's hint: nothing here
    time_step_min: float = 0.001     # the three: dt_bias's initialisation
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    sliding_window: Optional[int] = None
    rope_theta: float = 10000.0      # carried: the attention applies no position term
    partial_rotary_factor: float = 1.0
    # expert layers
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    moe_shared_expert_overlap: bool = False  # a scheduling hint: nothing here
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    intermediate_size: int = 2688    # a dense ("-") layer's width: the pattern has none
    # the stack
    layer_norm_epsilon: float = 1e-5
    norm_eps: float = 1e-5
    residual_in_fp32: bool = False
    rescale_prenorm_residual: bool = True  # an initialisation's rule: nothing here
    use_bias: bool = False
    tie_word_embeddings: bool = False
    num_logits_to_keep: int = 1
    max_position_embeddings: int = 262144
    model_type: str = "nemotron_h"
    # the multi-token-prediction module: carried, NOT built (module docstring)
    num_nextn_predict_layers: int = 1
    mtp_hybrid_override_pattern: str = "*E"
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
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        super().__post_init__()

    @property
    def pattern(self) -> str:
        """The mixers that are built: the first ``num_hidden_layers``
        characters of ``hybrid_override_pattern``."""
        return self.hybrid_override_pattern[: self.num_hidden_layers]

    @property
    def max_len(self) -> int:
        """The most positions the config declares: a limit, not a table."""
        return self.max_position_embeddings

    @property
    def moe_form(self) -> tuple:
        """What this family's experts are beside softmax-routed SwiGLU of the
        model's width (``lm_parts.expert_ffn``), as hashable items."""
        return (
            ("scoring", "sigmoid"), ("activation", self.mlp_hidden_act),
            ("latent", self.moe_latent_size),
            ("shared_hidden",
             self.n_shared_experts * self.moe_shared_expert_intermediate_size),
            # 22 pairs a token: a piece of 4,096 tokens is 90,112 pairs (the
            # default 8,192 x 6 or 8 picks of the other two families: 49-66k)
            ("token_chunk", 4096),
        )

    @property
    def moe_shape(self) -> Optional[Tuple[int, int, int]]:
        """``(expert layers, experts a token, experts held)``; None for a
        pattern without an ``E``."""
        layers = self.pattern.count(EXPERTS)
        held = (self.experts_held or (0, self.n_routed_experts))[1]
        return (layers, self.num_experts_per_tok, held) if layers else None

    @property
    def state_shape(self) -> Optional[Tuple[int, int, int, int]]:
        """``(layers that carry a state, heads, head channels, state size)``
        of the float32 state a sequence; None for a pattern without an
        ``M``.  A model that states it takes ``state_rows`` in every paged
        call (serving/decode.py), and the serving layers that assume a cache
        of token rows alone refuse it."""
        layers = self.pattern.count(MAMBA)
        if not layers:
            return None
        return (layers, self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size)

    def _check(self):
        unsupported = {
            "mamba_hidden_act": (self.mamba_hidden_act, "silu"),
            "mlp_hidden_act": (self.mlp_hidden_act, "relu2"),
            "mamba_proj_bias": (self.mamba_proj_bias, False),
            "attention_bias": (self.attention_bias, False),
            "mlp_bias": (self.mlp_bias, False),
            "use_bias": (self.use_bias, False),
            # GroupedQueryAttention has a ``window`` since the Laguna family;
            # this family's config carries null and no layer is given one
            "sliding_window": (self.sliding_window, None),
            "n_group": (self.n_group, 1),
            "topk_group": (self.topk_group, 1),
            "residual_in_fp32": (self.residual_in_fp32, False),
            "tie_word_embeddings": (self.tie_word_embeddings, False),
            "model_type": (self.model_type, "nemotron_h"),
            "norm_eps": (self.norm_eps, self.layer_norm_epsilon),
            "expand": (self.expand * self.hidden_size,
                       self.mamba_num_heads * self.mamba_head_dim),
        }
        for key, (got, want) in unsupported.items():
            if got != want:
                raise ValueError(
                    f"NemotronH: model.{key} = {got!r} is not written "
                    f"(only {want!r})"
                )
        if len(self.pattern) != self.num_hidden_layers:
            raise ValueError(
                f"NemotronH: hybrid_override_pattern names "
                f"{len(self.hybrid_override_pattern)} layers, "
                f"num_hidden_layers asks for {self.num_hidden_layers}")
        unknown = set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}
        if unknown:
            raise ValueError(
                f"NemotronH: the layers {sorted(unknown)} of "
                f"hybrid_override_pattern are not written (only M, E and *; "
                f"'-', a dense MLP, does not occur in the published pattern)")

    @nn.compact
    def __call__(self, tokens, decode_pos=None, block_tables=None,
                 adapter_ids=None, logit_cols=None, state_rows=None,
                 rows_are_slots=False):
        self._check()
        if adapter_ids is not None:
            raise ValueError("NemotronH has no LoRA factors")
        if decode_pos is not None and not self.decode:
            raise ValueError("decode_pos given but model was not cloned with decode=True")
        if self.decode and not self.paged:
            raise ValueError(
                "NemotronH carries a state a sequence and has no contiguous "
                "cache: decode mode is the paged scheduler's (paged=True)")
        emb = self.param(
            "tok_embedding", nn.initializers.normal(stddev=0.02),
            (self.vocab_size, self.hidden_size), self.dtype,
        )
        x = jnp.take(emb, tokens, axis=0).astype(self.dtype)
        token_mask = None if decode_pos is None else (decode_pos >= 0).reshape(-1)
        counts = add_moe_counts(None, None)
        config = LayerConfig(*(getattr(self, f) for f in _LAYER_FIELDS))
        for i, kind in enumerate(self.pattern):
            x, sizes = DecoderLayer(config=config, kind=kind, name=f"layer{i}")(
                x, decode_pos, block_tables, state_rows, token_mask, rows_are_slots)
            counts = add_moe_counts(counts, sizes)
        if self.moe_shape:
            sow_moe_stats(self, counts)
        return final_logits(
            x, logit_cols, self.layer_norm_epsilon, self.vocab_size, self.dtype)
