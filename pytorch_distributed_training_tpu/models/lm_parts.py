"""What the served decoder families have in common, in one place:
:class:`RMSNorm`, the untied :class:`Head`, the dense SwiGLU
:class:`GatedMLP`, the expert layer built from a
model's published keys (:func:`expert_ffn`), the step's expert counts
(:func:`add_moe_counts`, :func:`sow_moe_stats`) and the last norm and
projection over one column a row (:func:`final_logits`).  The served
families import them from here; the functions are called inside a model's
compact ``__call__`` and name their submodules there, so a family's
parameter tree does not know they exist.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.mla import rms_norm
from ..ops.moe import DroplessMoE, in_token_chunks, swiglu

__all__ = [
    "GatedMLP", "Head", "RMSNorm", "add_moe_counts", "expert_ffn", "final_logits",
    "sow_moe_stats",
]


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.dtype)
        return rms_norm(x, scale, self.eps)


class Head(nn.Module):
    """Untied, bias-free output projection: operands in ``dtype``, logits
    accumulated and returned in float32."""

    vocab_size: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.vocab_size), self.dtype,
        )
        return jnp.dot(x, kernel, preferred_element_type=jnp.float32)


class GatedMLP(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``, gate and up side by side in one
    tensor (the gate first); long calls run in pieces of ``token_chunk``."""

    hidden: int
    dtype: Any = jnp.float32
    token_chunk: int = 8192

    @nn.compact
    def __call__(self, x):
        dim = x.shape[-1]
        init = nn.initializers.lecun_normal()
        gate_up = self.param("gate_up", init, (dim, 2 * self.hidden), self.dtype)
        down = self.param("down", init, (self.hidden, dim), self.dtype)
        n = x.shape[0]
        if n <= self.token_chunk:
            return swiglu(x, gate_up, down)
        out = in_token_chunks(
            lambda piece: swiglu(piece, gate_up, down), self.token_chunk, x)
        return out.reshape(-1, dim)[:n]


def expert_ffn(c, flat, token_mask):
    """The dropless expert layer of a model whose fields ``c`` carries under
    their published names (``n_routed_experts``, ``num_experts_per_tok``,
    ``moe_intermediate_size``, ``n_shared_experts``, ``norm_topk_prob``,
    ``routed_scaling_factor``, ``experts_held``, ``dtype``), over tokens
    ``flat [N, dim]``, under the scope ``moe`` and the name ``moe``.  A
    family whose experts differ from softmax-routed SwiGLU of the model's
    width says so in ``c.moe_form``: a dict of the :class:`DroplessMoE`
    options it sets (``scoring``, ``activation``, ``latent``,
    ``shared_hidden``, ``token_chunk``).
    Returns ``(y [N, dim], group_sizes [held])``."""
    options = {
        "shared_hidden": c.n_shared_experts * c.moe_intermediate_size,
        **dict(getattr(c, "moe_form", ())),
    }
    with jax.named_scope("moe"):
        return DroplessMoE(
            dim=flat.shape[-1],
            num_experts=c.n_routed_experts,
            top_k=c.num_experts_per_tok,
            hidden=c.moe_intermediate_size,
            norm_topk_prob=c.norm_topk_prob,
            routed_scaling_factor=c.routed_scaling_factor,
            experts_held=c.experts_held,
            dtype=c.dtype,
            name="moe",
            **options,
        )(flat, token_mask)


def add_moe_counts(counts, sizes):
    """``counts = (experts that got a token, largest count at one expert)``,
    each summed over the expert layers so far (start from ``None``), plus
    one layer's ``group_sizes`` (``None`` for a dense layer: no change)."""
    if counts is None:
        counts = (jnp.zeros((), jnp.int32),) * 2
    if sizes is None:
        return counts
    return (
        counts[0] + jnp.sum(sizes > 0).astype(jnp.int32),
        counts[1] + jnp.max(sizes).astype(jnp.int32),
    )


def sow_moe_stats(model, counts):
    """Sow a call's :func:`add_moe_counts` into ``moe_stats``:
    ``serving/decode.py`` returns them from the decode program of a model
    that states ``moe_shape``."""
    model.sow("moe_stats", "experts_hit", counts[0])
    model.sow("moe_stats", "expert_load_max", counts[1])


def final_logits(x, logit_cols, eps, vocab_size, dtype):
    """``Head(RMSNorm(x))`` under the scope ``loss_head`` (submodules
    ``norm`` and ``head`` of the calling model), over every column of ``x
    [B, S, dim]`` or, with ``logit_cols [B]``, over that one column a row: a
    prefill needs no more, and ``[B, S, V]`` in float32 need not fit."""
    if logit_cols is not None:
        x = jnp.take_along_axis(x, logit_cols[:, None, None], axis=1)
    with jax.named_scope("loss_head"):
        x = RMSNorm(eps, dtype, name="norm")(x)
        return Head(vocab_size, dtype, name="head")(x)
