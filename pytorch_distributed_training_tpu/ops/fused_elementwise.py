"""Pallas TPU kernels: the elementwise tails around the transformer matmuls.

Round-5 traces show XLA leaves two elementwise chains unfused at the
boundaries of the Pallas attention/CE islands (a pallas_call is opaque to
the fusion pass, so producers/consumers on either side cannot merge into
it): the residual-add -> LayerNorm pair between attention and the MLP, and
the bias-add -> GELU pair inside the MLP.  Each chain re-reads its [B, S, E]
(or [B, S, 4E]) operand from HBM once per unfused op; at the flagship LM
shape that is pure memory-bound VPU time.  These kernels collapse each
chain into one single-pass VMEM-resident kernel:

- :func:`fused_add_layernorm`: ``s = x + delta; y = LN(s)`` emitting BOTH
  the residual stream ``s`` and the normalized ``y`` in one read of the
  operands (the plain pair reads the sum twice: once to store it, once for
  the LN statistics).
- :func:`fused_bias_gelu`: ``y = gelu(u + bias)`` for the MLP's first
  projection, exact-erf GELU matching ``nn.gelu(approximate=False)``.

Numerics replicate the flax modules they substitute bit-for-bit in spirit:
LN statistics in float32 with the fast-variance form
``max(0, E[s^2] - E[s]^2)`` and ``eps`` inside the rsqrt (flax
``_compute_stats``/``_normalize`` with ``use_fast_variance=True``,
``epsilon=1e-6``); the residual sum is rounded to the stream dtype BEFORE
the statistics read it, exactly as the unfused ``x + delta`` would be.
Backward passes are ``jax.custom_vjp`` with plain-XLA math (standard LN
backward, exact GELU derivative): the backward of these tails fuses into
the surrounding backward matmuls anyway, so only the forward needs the
hand-written kernel; keeping the bwd in XLA also keeps it differentiable
under remat without a second kernel family.

The module wrappers (:class:`FusedResidualLayerNorm`,
:class:`FusedDenseGelu`) declare parameters with the SAME names, shapes,
dtypes, and initializers as the ``nn.LayerNorm``/``nn.Dense`` they replace,
so checkpoints are interchangeable and ``model.fused_tails`` can be toggled
on an existing run.

Kernels run on real TPU, or in Pallas interpreter mode everywhere else;
``PDT_DISABLE_PALLAS=1`` falls back to the plain XLA composition (same
escape hatch as ops/losses.py).
"""
from __future__ import annotations

import functools
import os
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl

from .fused_ce import _out_struct

__all__ = [
    "fused_add_layernorm",
    "fused_bias_gelu",
    "FusedResidualLayerNorm",
    "FusedDenseGelu",
]

_TILE_ROWS = 256  # rows per kernel instance; lane dim carries features
_TILE_BYTES = 2 * 1024 * 1024  # same VMEM budget rationale as fused_ce

_INV_SQRT2 = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327


def _tile(rows: int, feat: int) -> int:
    budget_rows = max(1, _TILE_BYTES // (4 * feat))
    tile = 1
    while tile * 2 <= min(_TILE_ROWS, budget_rows):
        tile *= 2
    return min(tile, rows)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pallas_disabled() -> bool:
    return bool(os.environ.get("PDT_DISABLE_PALLAS"))


# ---------------------------------------------------------------------------
# residual-add + LayerNorm


def _add_ln_kernel(x_ref, d_ref, scale_ref, bias_ref, s_ref, y_ref, *, eps):
    s = (x_ref[...].astype(jnp.float32) + d_ref[...].astype(jnp.float32)).astype(
        s_ref.dtype
    )
    s_ref[...] = s
    # statistics read the ROUNDED sum (what the unfused LN would see)
    s32 = s.astype(jnp.float32)
    mu = jnp.mean(s32, axis=-1, keepdims=True)
    var = jnp.maximum(0.0, jnp.mean(s32 * s32, axis=-1, keepdims=True) - mu * mu)
    xhat = (s32 - mu) * jax.lax.rsqrt(var + eps)
    y = xhat * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_add_ln(interpret: bool, eps: float):
    def _forward(x, delta, scale, bias, out_dtype):
        rows, feat = x.shape
        tile = _tile(rows, feat)
        s, y = pl.pallas_call(
            functools.partial(_add_ln_kernel, eps=eps),
            grid=(pl.cdiv(rows, tile),),
            in_specs=[
                pl.BlockSpec((tile, feat), lambda i: (i, 0)),
                pl.BlockSpec((tile, feat), lambda i: (i, 0)),
                pl.BlockSpec((1, feat), lambda i: (0, 0)),
                pl.BlockSpec((1, feat), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((tile, feat), lambda i: (i, 0)),
                pl.BlockSpec((tile, feat), lambda i: (i, 0)),
            ],
            out_shape=[
                _out_struct((rows, feat), x.dtype, x),
                _out_struct((rows, feat), out_dtype, x),
            ],
            interpret=interpret,
            name="fused_add_ln",
        )(x, delta, scale.reshape(1, feat), bias.reshape(1, feat))
        return s, y

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def add_ln(x, delta, scale, bias, out_dtype):
        return _forward(x, delta, scale, bias, out_dtype)

    def add_ln_fwd(x, delta, scale, bias, out_dtype):
        s, y = _forward(x, delta, scale, bias, out_dtype)
        return (s, y), (s, scale)

    def add_ln_bwd(out_dtype, res, cts):
        s, scale = res
        ds_up, dy = cts
        s32 = s.astype(jnp.float32)
        mu = jnp.mean(s32, axis=-1, keepdims=True)
        var = jnp.maximum(
            0.0, jnp.mean(s32 * s32, axis=-1, keepdims=True) - mu * mu
        )
        r = jax.lax.rsqrt(var + eps)
        xhat = (s32 - mu) * r
        dy32 = dy.astype(jnp.float32)
        dscale = jnp.sum(dy32 * xhat, axis=0)
        dbias = jnp.sum(dy32, axis=0)
        dxhat = dy32 * scale.astype(jnp.float32)
        ds_ln = r * (
            dxhat
            - jnp.mean(dxhat, axis=-1, keepdims=True)
            - xhat * jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
        )
        ds = ds_up.astype(jnp.float32) + ds_ln
        return (
            ds.astype(s.dtype),
            ds.astype(s.dtype),
            dscale.astype(scale.dtype),
            dbias.astype(scale.dtype),
        )

    add_ln.defvjp(add_ln_fwd, add_ln_bwd)
    return add_ln


def fused_add_layernorm(x, delta, scale, bias, *, eps: float = 1e-6):
    """``s = x + delta; y = layernorm(s) * scale + bias`` in ONE kernel.

    ``x``/``delta``: [..., E] same shape/dtype (residual stream + branch
    output).  ``scale``/``bias``: [E] LN parameters.  Returns ``(s, y)``
    where ``s`` keeps the input dtype and ``y`` follows flax LN's result
    dtype (promotion of inputs and params).

    With ``PDT_DISABLE_PALLAS=1`` computes the plain XLA composition
    (identical math, two fusion roots).
    """
    lead = x.shape[:-1]
    feat = x.shape[-1]
    out_dtype = jnp.result_type(x.dtype, scale.dtype, bias.dtype)
    if _pallas_disabled():
        s = x + delta
        s32 = s.astype(jnp.float32)
        mu = jnp.mean(s32, axis=-1, keepdims=True)
        var = jnp.maximum(
            0.0, jnp.mean(s32 * s32, axis=-1, keepdims=True) - mu * mu
        )
        y = (s32 - mu) * jax.lax.rsqrt(var + eps) * scale.astype(
            jnp.float32
        ) + bias.astype(jnp.float32)
        return s, y.astype(out_dtype)
    fn = _make_add_ln(_use_interpret(), float(eps))
    s, y = fn(
        x.reshape(-1, feat), delta.reshape(-1, feat), scale, bias, out_dtype
    )
    return s.reshape(*lead, feat), y.reshape(*lead, feat)


# ---------------------------------------------------------------------------
# bias-add + exact-erf GELU


# erf(x) ~= x * P(x^2) / Q(x^2) on the clamped range: the float32 rational
# approximation XLA and Eigen use for their own erf.  Mosaic has no lowering
# for ``lax.erf`` ("Unimplemented primitive in Pallas TPU lowering ... erf"),
# and this form needs only multiplies, adds and one divide.  Max abs error
# against math.erf over [-6, 6] is 3.0e-7 (XLA's CPU erf: 2.4e-7).
_ERF_CLAMP = 3.832506856900711
_ERF_ALPHA = (
    2.29050653e-04, 3.40829096e-03, 5.09556941e-02, 1.85208321e-01,
    1.12837911e+00,
)
_ERF_BETA = (
    -1.17916031e-07, 2.35479656e-05, 1.01796259e-03, 1.40704699e-02,
    1.10985048e-01, 4.97469246e-01, 1.0,
)


def _erf_f32(x):
    x = jnp.clip(x, -_ERF_CLAMP, _ERF_CLAMP)
    x2 = x * x
    p, q = _ERF_ALPHA[0], _ERF_BETA[0]  # Horner, in x^2
    for a in _ERF_ALPHA[1:]:
        p = p * x2 + a
    for b in _ERF_BETA[1:]:
        q = q * x2 + b
    return x * p / q


def _bias_gelu_kernel(u_ref, bias_ref, y_ref):
    t = u_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
    y = 0.5 * t * (1.0 + _erf_f32(t * _INV_SQRT2))
    y_ref[...] = y.astype(y_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_bias_gelu(interpret: bool):
    def _forward(u, bias):
        rows, feat = u.shape
        tile = _tile(rows, feat)
        return pl.pallas_call(
            _bias_gelu_kernel,
            grid=(pl.cdiv(rows, tile),),
            in_specs=[
                pl.BlockSpec((tile, feat), lambda i: (i, 0)),
                pl.BlockSpec((1, feat), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, feat), lambda i: (i, 0)),
            out_shape=_out_struct((rows, feat), u.dtype, u),
            interpret=interpret,
            name="fused_bias_gelu",
        )(u, bias.reshape(1, feat))

    @jax.custom_vjp
    def bias_gelu(u, bias):
        return _forward(u, bias)

    def bias_gelu_fwd(u, bias):
        return _forward(u, bias), (u, bias)

    def bias_gelu_bwd(res, dy):
        u, bias = res
        t = u.astype(jnp.float32) + bias.astype(jnp.float32)
        cdf = 0.5 * (1.0 + jax.lax.erf(t * _INV_SQRT2))
        pdf = jnp.exp(-0.5 * t * t) * _INV_SQRT_2PI
        du = dy.astype(jnp.float32) * (cdf + t * pdf)
        return du.astype(u.dtype), jnp.sum(du, axis=0).astype(bias.dtype)

    bias_gelu.defvjp(bias_gelu_fwd, bias_gelu_bwd)
    return bias_gelu


def fused_bias_gelu(u, bias):
    """``gelu(u + bias, approximate=False)`` in ONE kernel.

    ``u``: [..., H] pre-bias matmul output; ``bias``: [H].  Output keeps
    ``u``'s dtype (matching ``nn.Dense`` + ``nn.gelu`` composed in the
    module compute dtype).  ``PDT_DISABLE_PALLAS=1`` falls back to plain
    XLA ops.
    """
    lead = u.shape[:-1]
    feat = u.shape[-1]
    if _pallas_disabled():
        t = u.astype(jnp.float32) + bias.astype(jnp.float32)
        y = 0.5 * t * (1.0 + jax.lax.erf(t * _INV_SQRT2))
        return y.astype(u.dtype)
    y = _make_bias_gelu(_use_interpret())(u.reshape(-1, feat), bias)
    return y.reshape(*lead, feat)


# ---------------------------------------------------------------------------
# param-compatible linen wrappers


class FusedResidualLayerNorm(nn.Module):
    """Drop-in for ``x + delta`` followed by ``nn.LayerNorm(name=...)``.

    Declares the SAME parameters as ``nn.LayerNorm`` ("scale" ones,
    "bias" zeros, float32, shape [E]) so a checkpoint trained either way
    loads in the other.  Returns ``(s, y)``: the new residual stream and
    its normalization.
    """

    dtype: Any = jnp.float32
    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x, delta):
        feat = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (feat,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (feat,), jnp.float32)
        s, y = fused_add_layernorm(x, delta, scale, bias, eps=self.epsilon)
        return s, y.astype(self.dtype)


class FusedDenseGelu(nn.Module):
    """Drop-in for ``nn.Dense(hidden, name=...)`` + exact-erf ``nn.gelu``.

    Declares the SAME parameters as ``nn.Dense`` ("kernel" lecun_normal,
    "bias" zeros, float32 param dtype).  The matmul stays a plain XLA dot
    (that's MXU work the partitioner handles); only the bias+gelu tail is
    the fused kernel.
    """

    hidden: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        feat = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (feat, self.hidden),
            jnp.float32,
        )
        bias = self.param("bias", nn.initializers.zeros, (self.hidden,), jnp.float32)
        u = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype))
        return fused_bias_gelu(u, bias.astype(self.dtype))
