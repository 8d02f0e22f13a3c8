"""Pallas TPU kernel: fused softmax cross-entropy (forward + backward).

The reference computes CE with ``torch.nn.CrossEntropyLoss`` (cuDNN/CUDA
softmax + NLL kernels, train_distributed.py:202, :275).  Here the whole
row-wise pipeline — max, exp, sum, log, label gather — runs in one VMEM-
resident Pallas kernel per batch tile, and the backward pass
``dlogits = (softmax - onehot) * g/N`` is a second fused kernel wired up via
``jax.custom_vjp``.  Both kernels read the logits from HBM exactly once
(the VPU work is memory-bound at (B, 1000) shapes, so single-pass is the
whole game); neither materializes the softmax in the forward pass — the
backward recomputes it from the saved per-row logsumexp.

Numerics: compute is float32 regardless of input dtype (bf16 logits are
upcast on load), matching the fp32 loss convention of ``ops.losses``.

The kernels run on real TPU or, for the 8-virtual-device CPU test mesh, in
Pallas interpreter mode (``interpret=True``) — same code path the fake-
backend distributed tests use for collectives (SURVEY.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..utils.vma import mark_varying

__all__ = ["fused_cross_entropy"]

_TILE_B = 128  # max batch rows per kernel instance; lane dim carries classes
_TILE_BYTES = 2 * 1024 * 1024  # f32 logits-tile budget: scoped VMEM is
# ~16MB and the backward pipelines double-buffered input AND output tiles
# (4 tile-sized buffers) plus temporaries, so cap the tile at ~2MB and
# shrink the row count for large class counts (LM vocabularies) instead of
# overflowing VMEM


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct inheriting ``like``'s varying-mesh-axes (vma) type.

    Inside ``shard_map`` (where the train step calls this) JAX requires
    pallas outputs to declare which mesh axes they vary over; the outputs
    vary exactly like the logits they are computed from.
    """
    try:
        vma = jax.typeof(like).vma
        if vma:
            return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    except (AttributeError, TypeError):
        pass
    return jax.ShapeDtypeStruct(shape, dtype)


def _fwd_kernel(logits_ref, labels_ref, nll_ref, lse_ref, *, vma_axes=()):
    x = logits_ref[...].astype(jnp.float32)
    lbl = labels_ref[...]  # (tile_b, 1) int32
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))
    # the iota constant is mesh-invariant; in interpreter mode (where the
    # kernel jaxpr runs under shard_map's vma typing) it must be promoted
    # to match the varying labels — Mosaic-compiled kernels pass () here
    col = mark_varying(jax.lax.broadcasted_iota(jnp.int32, x.shape, 1), vma_axes)
    true_logit = jnp.sum(jnp.where(col == lbl, x, 0.0), axis=-1, keepdims=True)
    nll_ref[...] = lse - true_logit
    lse_ref[...] = lse


def _bwd_kernel(logits_ref, labels_ref, lse_ref, scale_ref, dlogits_ref, *, vma_axes=()):
    x = logits_ref[...].astype(jnp.float32)
    lbl = labels_ref[...]
    lse = lse_ref[...]
    p = jnp.exp(x - lse)  # softmax, recomputed from the saved logsumexp
    # the iota constant is mesh-invariant; in interpreter mode (where the
    # kernel jaxpr runs under shard_map's vma typing) it must be promoted
    # to match the varying labels — Mosaic-compiled kernels pass () here
    col = mark_varying(jax.lax.broadcasted_iota(jnp.int32, x.shape, 1), vma_axes)
    onehot = jnp.where(col == lbl, 1.0, 0.0)
    dlogits_ref[...] = ((p - onehot) * scale_ref[0]).astype(dlogits_ref.dtype)


def _tile(b: int, c: int) -> int:
    budget_rows = max(1, _TILE_BYTES // (4 * c))
    tile = 1
    while tile * 2 <= min(_TILE_B, budget_rows):
        tile *= 2
    return min(tile, b)


@functools.lru_cache(maxsize=None)
def _make(interpret: bool):
    """Build the custom-VJP'd fused CE for a static interpret mode."""

    def _kernel_vma(x):
        """Axes the kernel must mark constants with (interpret mode only)."""
        if not interpret:
            return ()
        try:
            return tuple(sorted(jax.typeof(x).vma))
        except (AttributeError, TypeError):
            return ()

    def _forward(logits, labels):
        b, c = logits.shape
        tile = _tile(b, c)
        labels2 = labels.astype(jnp.int32).reshape(b, 1)
        nll, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, vma_axes=_kernel_vma(logits)),
            grid=(pl.cdiv(b, tile),),
            in_specs=[
                pl.BlockSpec((tile, c), lambda i: (i, 0)),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                _out_struct((b, 1), jnp.float32, logits),
                _out_struct((b, 1), jnp.float32, logits),
            ],
            interpret=interpret,
            name="fused_ce_fwd",
        )(logits, labels2)
        return nll, lse

    @jax.custom_vjp
    def ce(logits, labels):
        nll, _ = _forward(logits, labels)
        return jnp.mean(nll)

    def ce_fwd(logits, labels):
        nll, lse = _forward(logits, labels)
        return jnp.mean(nll), (logits, labels, lse)

    def ce_bwd(res, g):
        logits, labels, lse = res
        b, c = logits.shape
        tile = _tile(b, c)
        labels2 = labels.astype(jnp.int32).reshape(b, 1)
        # fold the mean's 1/B into the upstream cotangent once, on the host side
        scale = (g / b).astype(jnp.float32).reshape(1)
        dlogits = pl.pallas_call(
            functools.partial(_bwd_kernel, vma_axes=_kernel_vma(logits)),
            grid=(pl.cdiv(b, tile),),
            in_specs=[
                pl.BlockSpec((tile, c), lambda i: (i, 0)),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
                pl.BlockSpec((1,), lambda i: (0,)),
            ],
            out_specs=pl.BlockSpec((tile, c), lambda i: (i, 0)),
            out_shape=_out_struct((b, c), logits.dtype, logits),
            interpret=interpret,
            name="fused_ce_bwd",
        )(logits, labels2, lse, scale)
        return dlogits, None

    ce.defvjp(ce_fwd, ce_bwd)
    return ce


def fused_cross_entropy(logits, labels, *, interpret: bool = False):
    """Mean softmax CE with integer labels — Pallas-fused fwd/bwd.

    Drop-in for :func:`..ops.losses.cross_entropy_loss` (same semantics:
    mean reduction, fp32 compute, ``torch.nn.CrossEntropyLoss`` defaults).

    Precondition: every label must lie in ``[0, C)``.  An out-of-range label
    makes the where-based gather contribute ``true_logit = 0`` — a finite but
    wrong loss — whereas ``torch.nn.CrossEntropyLoss`` raises and the XLA
    ``take_along_axis`` path clamps; validate labels at the data boundary
    (the ``ImageFolderDataset``/token pipelines only emit in-range labels).

    Args:
      interpret: run the kernels in Pallas interpreter mode (for CPU test
        meshes); on TPU leave False.
    """
    return _make(bool(interpret))(logits, labels)
