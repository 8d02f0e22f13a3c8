"""Laguna decoder (``model_type: laguna``) — the sixth LM family: window and
global softmax attention mixed, with head counts that differ by layer, over
dropless experts.

Every layer is ``h = x + Attn_l(RMSNorm(x)); x' = h + FFN_l(RMSNorm(h))``, no
bias.  Layer ``l``'s attention (:class:`..ops.attention.GroupedQueryAttention`)
has ``num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` K/V heads of ``head_dim``, ONE sigmoid gate a head on
its output (``gating``) and a rotary term by its kind, ``layer_types[l]``:

- ``full_attention``: every key ``j <= i``; rows in the paged pool; the
  rotary term of ``rope_parameters.full_attention`` (here YaRN over the first
  ``partial_rotary_factor`` of a head's lanes, cos and sin times
  ``attention_factor``).
- ``sliding_attention``: the keys ``i - sliding_window < j <= i`` and nothing
  older: rows in a ring a slot beside the pool
  (:func:`..ops.attention.window_attention`); the rotary term of
  ``rope_parameters.sliding_attention`` (here the default one over all lanes).

``mlp_layer_types[l]`` chooses the FFN: ``dense``, a SwiGLU of
``intermediate_size`` (:class:`.lm_parts.GatedMLP`); ``sparse``,
``num_experts`` dropless SwiGLU experts of ``moe_intermediate_size``, the
``num_experts_per_tok`` largest softmax scores renormalised over the chosen
and times ``moe_routed_scaling_factor`` on the experts' output, beside one
shared expert of ``shared_expert_intermediate_size``
(:class:`..ops.moe.DroplessMoE` through :func:`.lm_parts.expert_ffn`).  Final
RMSNorm and an untied head.  The fields are the published ``config.json``
keys under their published names plus ``experts_held``, so a ``model:``
section is the model card's config with ``name: Laguna`` in front; only the
first ``num_hidden_layers`` entries of the three per-layer lists are built.

Serving only, as :mod:`.solar_open2`: parameters are created and kept in
``dtype``, ``clone(decode=True, paged=True, kv_block_size=, kv_num_blocks=,
state_slots=)`` is the contract of ``serving/decode.py::build_paged_fns``.
What the serving programs learn from the class: ``is_language_model``,
``takes_logit_cols``, ``moe_shape``, ``window_shape`` and ``state_shape`` —
not None where a layer has a window: the cache tree then holds the rings'
``[slots * window, ...]`` leaves beside the pool's, every paged call names
each row's slot in ``state_rows [B]``, and what assumes a cache of token rows
alone (prefix cache, speculative fork, KV transfer) refuses the model as it
refuses a recurrent state.
"""
from __future__ import annotations

import collections
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.attention import GroupedQueryAttention
from ..ops.rotary import yarn_inv_freq
from .lm_parts import (
    GatedMLP, RMSNorm, add_moe_counts, expert_ffn, final_logits, sow_moe_stats,
)

__all__ = ["LagunaLM"]

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# query rows of one batch row a full layer's prefill scores at once: 48 heads
# against a row's whole block table (8,704 positions in
# config/serve-laguna-xs2.yml) are 428 MB of float32 scores at 256 rows; the
# shared layer's 512 made a prefill of 8,192 positions 2.55 GB of temporaries
# beside 12.0 GB resident
QUERY_BLOCK = 256

# the LM's fields a layer reads under their own names ...
_OWN_FIELDS = (
    "num_key_value_heads", "head_dim", "sliding_window", "intermediate_size",
    "rms_norm_eps", "dtype", "decode", "paged", "kv_block_size",
    "kv_num_blocks", "state_slots", "num_experts_per_tok",
    "moe_intermediate_size", "experts_held",
)
# ... and the expert layer's under the names lm_parts.expert_ffn reads
_EXPERT_FIELDS = (
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "routed_scaling_factor",
)
# as one hashable value (a flax module cannot hold its parent as a field)
LayerConfig = collections.namedtuple(
    "LagunaLayerConfig", _OWN_FIELDS + _EXPERT_FIELDS)


def _frozen(value):
    """A config dict as flax can hash it: items, sorted, nested alike."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


def rotary_term(rope: dict, head_dim: int) -> Tuple[int, Tuple[float, ...], float]:
    """``(rotary_dim, frequencies, amplitude)`` of one entry of
    ``rope_parameters``: ``default`` is ``theta^(-2i / rotary_dim)`` at
    amplitude 1; ``yarn`` blends each frequency with its ``factor``-th by
    the ramp between the two correction dimensions
    (:func:`..ops.rotary.yarn_inv_freq`) and multiplies cos and sin by
    ``attention_factor`` (``0.1 ln(factor) + 1`` where the config leaves it
    out)."""
    kind = rope.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(
            f"Laguna: model.rope_parameters rope_type {kind!r} is not written "
            "(only 'default' and 'yarn')")
    rotary_dim = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    scaling, amplitude = None, 1.0
    if kind == "yarn":
        scaling = {key: rope[key] for key in (
            "factor", "original_max_position_embeddings", "beta_fast", "beta_slow")}
        amplitude = rope.get("attention_factor")
        if amplitude is None:
            amplitude = 0.1 * math.log(rope["factor"]) + 1.0
    freq = yarn_inv_freq(rotary_dim, float(rope["rope_theta"]), scaling)
    return rotary_dim, tuple(float(f) for f in freq), float(amplitude)


class DecoderLayer(nn.Module):
    """One pre-norm layer: ``kind`` (an entry of ``layer_types``) and
    ``heads`` choose the attention, ``ffn`` (of ``mlp_layer_types``) the FFN.
    Returns ``(x', group_sizes)`` as :class:`.deepseek_v2.DecoderLayer`
    does (``None`` in a dense layer)."""

    config: "LayerConfig"
    kind: str
    heads: int
    ffn: str
    rotary: Tuple[int, Tuple[float, ...], float]

    @nn.compact
    def __call__(self, x, positions, block_tables, state_rows, token_mask):
        c = self.config
        b, s, dim = x.shape
        rotary_dim, inv_freq, amplitude = self.rotary
        y = RMSNorm(c.rms_norm_eps, c.dtype, name="attn_norm")(x)
        x = x + GroupedQueryAttention(
            num_heads=self.heads,
            num_kv_heads=c.num_key_value_heads,
            head_dim=c.head_dim,
            gate="head",
            rotary_dim=rotary_dim,
            rotary_inv_freq=inv_freq,
            rotary_amp=amplitude,
            window=c.sliding_window if self.kind == SLIDING else 0,
            query_block=QUERY_BLOCK,
            dtype=c.dtype,
            decode=c.decode,
            paged=c.paged,
            kv_block_size=c.kv_block_size,
            kv_num_blocks=c.kv_num_blocks,
            state_slots=c.state_slots,
            # a model with window layers is prefilled a whole prompt a call
            # (no prefix cache, no piece: its rings are addressed by slot;
            # ``ContinuousScheduler._refuse_a_piece`` refuses any other
            # call), so a full layer may score the call's own keys
            whole_prompts=bool(c.state_slots),
            name="attn",
        )(y, positions, block_tables, state_rows)
        flat = RMSNorm(c.rms_norm_eps, c.dtype, name="ffn_norm")(x).reshape(b * s, dim)
        if self.ffn == DENSE:
            with jax.named_scope("mlp"):
                out = GatedMLP(c.intermediate_size, c.dtype, name="mlp")(flat)
            return x + out.reshape(b, s, dim), None
        out, sizes = expert_ffn(c, flat, token_mask)
        return x + out.reshape(b, s, dim), sizes


class LagunaLM(nn.Module):
    """Causal LM over integer tokens ``[B, S] -> logits [B, S, V]`` (or
    ``[B, 1, V]`` with ``logit_cols``)."""

    is_language_model = True
    takes_logit_cols = True
    training_unsupported = (
        "Laguna is served, not trained, by this repository: the dropless "
        "expert layer (ops/moe.py::DroplessMoE) has no backward pass here and "
        "the flash kernels (ops/flash_attention.py) have no window; train with "
        "TransformerLM or serve it through python -m "
        "pytorch_distributed_training_tpu.serving"
    )

    vocab_size: int
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: Any = True  # true, or the sibling configs' "per-head"
    sliding_window: int = 512
    rope_parameters: Optional[Any] = None  # the config's dict (or its items)
    layer_types: Optional[Tuple[str, ...]] = None  # None: (full, sliding x 3) repeated
    mlp_layer_types: Optional[Tuple[str, ...]] = None  # None: dense, then sparse
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    moe_apply_router_weight_on_input: bool = False
    partial_rotary_factor: float = 0.5
    moe_routed_scaling_factor: float = 2.5
    model_type: str = "laguna"
    # which experts this chip holds, ``(first, count)``; None = all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.float32
    decode: bool = False
    paged: bool = False
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    # slots of the window layers' rings (the scheduler's slots)
    state_slots: int = 0

    def __post_init__(self):
        # flax hashes a module's fields: dicts as their items, lists as tuples
        n = self.num_hidden_layers
        kinds = self.layer_types
        if kinds is None:
            kinds = ((FULL,) + (SLIDING,) * 3) * (-(-n // 4))
        object.__setattr__(self, "layer_types", tuple(kinds))
        ffns = self.mlp_layer_types
        if ffns is None:
            ffns = (DENSE,) + (SPARSE,) * max(n - 1, 0)
        object.__setattr__(self, "mlp_layer_types", tuple(ffns))
        heads = self.num_attention_heads_per_layer
        if heads is None:
            heads = (self.num_attention_heads,) * n
        object.__setattr__(
            self, "num_attention_heads_per_layer", tuple(int(h) for h in heads))
        rope = self.rope_parameters
        if rope is None:
            rope = {kind: {"rope_type": "default", "rope_theta": 10000.0}
                    for kind in (FULL, SLIDING)}
        object.__setattr__(self, "rope_parameters", _frozen(rope))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", tuple(self.experts_held))
        super().__post_init__()

    @property
    def max_len(self) -> int:
        """The most positions the config declares: a limit, not a table."""
        return self.max_position_embeddings

    def _layers(self):
        """``(kind, query heads, ffn)`` of each layer that is built."""
        n = self.num_hidden_layers
        return tuple(zip(self.layer_types[:n],
                         self.num_attention_heads_per_layer[:n],
                         self.mlp_layer_types[:n]))

    @property
    def moe_shape(self) -> Optional[Tuple[int, int, int]]:
        """``(expert layers, experts a token, experts held)``
        (:attr:`.deepseek_v2.DeepseekV2LM.moe_shape`)."""
        layers = sum(ffn == SPARSE for _, _, ffn in self._layers())
        held = (self.experts_held or (0, self.num_experts))[1]
        return (layers, self.num_experts_per_tok, held) if layers else None

    @property
    def window_shape(self) -> Optional[Tuple[int, int, int]]:
        """``(window layers, window, full layers)``; None for a model none of
        whose layers has a window.  A step's live rows read ``min(L,
        window)`` positions in each window layer and ``L`` in each full one:
        the scheduler files both sums in its ``decode_step`` spans."""
        kinds = [kind for kind, _, _ in self._layers()]
        windows = kinds.count(SLIDING)
        return (windows, self.sliding_window, len(kinds) - windows) if windows else None

    @property
    def state_shape(self) -> Optional[Tuple[int, int, int, int]]:
        """``(window layers, K/V heads, window, head_dim)`` of the ring a
        slot, in ``dtype``, K and V each; None for a model with no window
        layer.  Stated like :attr:`.solar_open2.SolarOpen2LM.state_shape`: a
        model that states it takes ``state_rows`` in every paged call
        (serving/decode.py), and the serving layers that assume a cache of
        token rows alone refuse it."""
        shape = self.window_shape
        if shape is None:
            return None
        return (shape[0], self.num_key_value_heads, shape[1], self.head_dim)

    def _check(self):
        unsupported = {
            "attention_bias": (self.attention_bias, False),
            "tie_word_embeddings": (self.tie_word_embeddings, False),
            "model_type": (self.model_type, "laguna"),
            "moe_apply_router_weight_on_input": (
                self.moe_apply_router_weight_on_input, False),
        }
        for key, (got, want) in unsupported.items():
            if got != want:
                raise ValueError(
                    f"Laguna: model.{key} = {got!r} is not written "
                    f"(only {want!r})"
                )
        if self.gating not in (True, "per-head"):
            raise ValueError(
                f"Laguna: model.gating = {self.gating!r} is not written (only "
                "true and 'per-head': one sigmoid gate a head)")
        n = self.num_hidden_layers
        layers = self._layers()
        if len(layers) < n or {kind for kind, _, _ in layers} - {FULL, SLIDING} \
                or {ffn for _, _, ffn in layers} - {DENSE, SPARSE}:
            raise ValueError(
                f"Laguna: model.layer_types, mlp_layer_types and "
                f"num_attention_heads_per_layer must each name {n} layers "
                f"({FULL!r} / {SLIDING!r}, {DENSE!r} / {SPARSE!r}); got "
                f"{layers!r}")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError(
                "Laguna: model.shared_expert_intermediate_size = "
                f"{self.shared_expert_intermediate_size} is not written (only "
                f"a multiple of moe_intermediate_size {self.moe_intermediate_size})")

    @nn.compact
    def __call__(self, tokens, decode_pos=None, block_tables=None,
                 adapter_ids=None, logit_cols=None, state_rows=None,
                 rows_are_slots=False):
        # ``rows_are_slots`` (the decode step's statement that row i is slot
        # i) buys a ring nothing: it is addressed by ``state_rows`` whatever
        # the call's width, a table of the slot's blocks or a scatter
        del rows_are_slots
        self._check()
        if adapter_ids is not None:
            raise ValueError("Laguna has no LoRA factors")
        if decode_pos is not None and not self.decode:
            raise ValueError("decode_pos given but model was not cloned with decode=True")
        if self.decode and not self.paged:
            raise ValueError(
                "Laguna keeps its window layers' rows in a ring a slot and has "
                "no contiguous cache: decode mode is the paged scheduler's "
                "(paged=True)")
        rope = dict(self.rope_parameters)
        rotary = {
            kind: rotary_term(dict(rope[kind]), self.head_dim)
            for kind in (FULL, SLIDING) if kind in rope
        }
        emb = self.param(
            "tok_embedding", nn.initializers.normal(stddev=0.02),
            (self.vocab_size, self.hidden_size), self.dtype,
        )
        x = jnp.take(emb, tokens, axis=0).astype(self.dtype)
        token_mask = None if decode_pos is None else (decode_pos >= 0).reshape(-1)
        counts = add_moe_counts(None, None)
        config = LayerConfig(
            *(getattr(self, f) for f in _OWN_FIELDS),
            n_routed_experts=self.num_experts,
            n_shared_experts=(
                self.shared_expert_intermediate_size // self.moe_intermediate_size),
            # assumed, as the sibling config states it: the chosen gates sum to 1
            norm_topk_prob=True,
            routed_scaling_factor=self.moe_routed_scaling_factor,
        )
        for i, (kind, heads, ffn) in enumerate(self._layers()):
            if kind not in rotary:
                raise ValueError(
                    f"Laguna: model.rope_parameters has no entry {kind!r}")
            x, sizes = DecoderLayer(
                config=config, kind=kind, heads=heads, ffn=ffn,
                rotary=rotary[kind], name=f"layer{i}",
            )(x, decode_pos, block_tables, state_rows, token_mask)
            counts = add_moe_counts(counts, sizes)
        if self.moe_shape:
            sow_moe_stats(self, counts)
        return final_logits(
            x, logit_cols, self.rms_norm_eps, self.vocab_size, self.dtype)
