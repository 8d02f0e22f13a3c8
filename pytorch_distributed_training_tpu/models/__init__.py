"""Model zoo.

Re-provides ``dl_lib.classification.models.get_model`` (reference import at
train_distributed.py:25, call at :183-186): ``get_model(model_name,
num_classes) -> model``.  Case-insensitive on the name; the reference configs
use ``ResNet50`` (config/ResNet50.yml:31).

Families: the reference's ResNet-18/34/50/101/152 (README.md:7-13) plus a
ViT family (ViT-Ti16/S16/B16) and six language-model families added beyond
the reference — the config surface only pins ``model.name``, so new names
slot straight in.  ``_LM_FAMILIES`` below lists them once, each with its
module and what sets it apart; ``TransformerLM`` trains and serves, the other
five are served, not trained, and share their norm, head, dense MLP and
expert layer through :mod:`.lm_parts`.

What a model IS is stated by its class, not compared by name:
``is_language_model`` (tokens in, logits out; the ``num_classes`` slot is
the vocabulary), ``training_unsupported`` (a message where the training
path must refuse it), ``moe_shape`` (expert layers) and ``state_shape`` (a
fixed-size state a sequence beside the paged pool: the serving layers that
assume a cache of token rows alone refuse such a model).  :func:`model_class` gives the class for a name.
"""
from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp

from .deepseek_v2 import DeepseekV2LM
from .laguna import LagunaLM
from .nemotron_h import NemotronHLM
from .olmo_hybrid import OlmoHybridLM
from .resnet import RESNET_CONFIGS, BasicBlock, Bottleneck, ResNet
from .solar_open2 import SolarOpen2LM
from .transformer_lm import TransformerLM
from .vit import VIT_CONFIGS, ViT

__all__ = [
    "get_model",
    "list_models",
    "model_class",
    "DeepseekV2LM",
    "LagunaLM",
    "NemotronHLM",
    "OlmoHybridLM",
    "SolarOpen2LM",
    "ResNet",
    "BasicBlock",
    "Bottleneck",
    "ViT",
    "TransformerLM",
]

_CANONICAL = {name.lower(): name for name in RESNET_CONFIGS}
_CANONICAL.update({name.lower(): name for name in VIT_CONFIGS})
# the language-model families: name -> class (the class takes
# ``vocab_size=num_classes`` and the ``model:`` section's keys verbatim)
_LM_FAMILIES = {
    # the decoder-only long-context / sequence-parallel model; trained too
    "TransformerLM": TransformerLM,
    # latent attention, dropless experts
    "DeepseekV2": DeepseekV2LM,
    # KDA linear layers that carry a state a sequence, one grouped-query
    # layer without positions in four, dropless experts in every layer
    "SolarOpen2": SolarOpen2LM,
    # ONE mixer a layer by a pattern: Mamba-2 layers that carry a second
    # kind of state, latent experts, grouped-query attention of 2 K/V heads
    "NemotronH": NemotronHLM,
    # dense, post-norm: Gated DeltaNet layers (one decay a head, a
    # rectangular state: the third kind) three to one with full attention
    # under a QK-norm over the whole projection
    "OlmoHybrid": OlmoHybridLM,
    # window and global softmax layers three to one, each with its own head
    # count and rotary term and a gate a head: the window layers' K/V rows
    # in a ring a slot beside the pool (the fourth slot-addressed kind),
    # dropless experts beside a shared one after a dense first layer
    "Laguna": LagunaLM,
}
_CANONICAL.update({name.lower(): name for name in _LM_FAMILIES})


def list_models():
    return sorted(RESNET_CONFIGS) + sorted(VIT_CONFIGS) + sorted(_LM_FAMILIES)


def model_class(model_name: str):
    """The module class behind a zoo name (case-insensitive)."""
    key = model_name.lower()
    if key not in _CANONICAL:
        raise KeyError(f"unknown model '{model_name}' (have: {list_models()})")
    name = _CANONICAL[key]
    if name in _LM_FAMILIES:
        return _LM_FAMILIES[name]
    return ResNet if name in RESNET_CONFIGS else ViT


def get_model(
    model_name: str,
    num_classes: int,
    axis_name: Optional[str] = None,
    dtype: Any = jnp.float32,
    **kwargs,
):
    """Build a model by zoo name (reference: train_distributed.py:183-186).

    Extra TPU-native knobs beyond the reference signature (keyword-only in
    spirit; the engine wires them from config):
      axis_name: mesh axis for SyncBN (``sync_bn: True`` => the data axis;
        models without batch statistics accept and ignore it).
      dtype: compute dtype (bf16 mixed precision).
      **kwargs: architecture hyperparameters forwarded verbatim to the
        module — the engine passes any extra keys of the ``model:`` config
        section here (e.g. ``embed_dim/depth/num_heads/max_len/seq_axis``
        for ``TransformerLM``).

    For a language model (a name in ``_LM_FAMILIES``) the reference's
    ``num_classes`` slot is the vocabulary size (``dataset.n_classes`` in the
    config).
    """
    key = model_name.lower()
    if key not in _CANONICAL:
        raise KeyError(f"unknown model '{model_name}' (have: {list_models()})")
    name = _CANONICAL[key]
    if name in _LM_FAMILIES:
        return _LM_FAMILIES[name](vocab_size=num_classes, dtype=dtype, **kwargs)
    if name in RESNET_CONFIGS:
        block_cls, stage_sizes = RESNET_CONFIGS[name]
        return ResNet(
            stage_sizes=stage_sizes,
            block_cls=block_cls,
            num_classes=num_classes,
            axis_name=axis_name,
            dtype=dtype,
            **kwargs,
        )
    patch, embed, depth, heads = VIT_CONFIGS[name]
    return ViT(
        num_classes=num_classes,
        patch_size=patch,
        embed_dim=embed,
        depth=depth,
        num_heads=heads,
        axis_name=axis_name,
        dtype=dtype,
        **kwargs,
    )
