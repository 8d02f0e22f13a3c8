"""The rotary position term, where both attention families read it:
:class:`.mla.MLAttention` (its ``qk_rope_head_dim`` lanes, interleaved
pairs) and :class:`.attention.GroupedQueryAttention` (the first
``rotary_dim`` lanes of a head, pairs of halves).  Frequencies and amplitude
are numbers of the configuration, computed once on the host.
"""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
import numpy as np

__all__ = ["rotate_halves", "yarn_inv_freq", "yarn_mscale"]


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]) -> np.ndarray:
    """Rotary frequencies ``[dim / 2]``; with a YaRN ``rope_scaling`` the
    published ones divided by ``factor`` where a dimension turns fewer than
    ``beta_slow`` times over the original context, kept where it turns more
    than ``beta_fast`` times, a linear ramp between."""
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return freq.astype(np.float32)
    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (freq / scaling["factor"] * ramp + freq * (1 - ramp)).astype(np.float32)


def rotate_halves(x, positions, inv_freq, amplitude: float = 1.0):
    """``x [B, S, H, hd]`` with the first ``2 * len(inv_freq)`` lanes of
    every head rotated by ``positions [B, S]`` and the rest passed through:
    lane ``i`` pairs with lane ``i + len(inv_freq)`` (the ``rotate_half``
    layout), the angle is ``position * inv_freq[i]`` in float32, and cos and
    sin are multiplied by ``amplitude`` (YaRN's attention factor) on the
    rotated lanes only."""
    half = len(inv_freq)
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)  # [B, S, half]
    cos = (jnp.cos(angles) * amplitude)[:, :, None, :]
    sin = (jnp.sin(angles) * amplitude)[:, :, None, :]
    first = x[..., :half].astype(jnp.float32)
    second = x[..., half:2 * half].astype(jnp.float32)
    rotated = jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    ).astype(x.dtype)
    return jnp.concatenate([rotated, x[..., 2 * half:]], axis=-1)
