"""Compiled SPMD train / eval steps.

The TPU-native re-design of the reference's hot loop (SURVEY.md §3.2-3.3):
``train_iter``'s zero_grad -> H2D -> forward -> CE -> backward (DDP bucketed
allreduce) -> SGD step sequence (train_distributed.py:267-299) becomes ONE
XLA program: forward, loss, backward, gradient ``pmean`` over the ICI data
axis, BN-stats ``pmean`` (SyncBN), LR-schedule evaluation, and the SGD update
are all traced together under ``jit`` + ``shard_map``, so XLA fuses the
elementwise work into the matmuls and overlaps the gradient all-reduce with
remaining backward compute — the scheduling DDP's C++ reducer does by hand.

The per-step loss is ``pmean``-reduced in-graph (the reference's explicit
``dist.all_reduce(loss)/world_size``, :281-284) and returned as a device
scalar; the host only syncs on it at ``print_interval`` (:280), so steady-state
iterations never block on device->host transfers.

Eval mirrors :301-321: loss + top-1/top-5 computed on-device and
``pmean``-reduced (the reference's three per-batch ``all_reduce`` calls
collapse into the compiled step).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..metrics import accuracy
from ..ops import cross_entropy_loss
from ..parallel.mesh import DATA_AXIS
from ..telemetry.retrace import register_compiled

__all__ = [
    "TrainState",
    "build_train_step",
    "build_eval_step",
    "build_eval_step_exact",
    "init_train_state",
]

# Step-family label for the static collective-order oracle (see
# analysis/collectives.py and PERF.md): all collectives emitted by the
# builders in this module belong to the data-parallel family.
PDT_COLLECTIVE_FAMILY = "dp"


class TrainState(struct.PyTreeNode):
    """Replicated training state: params + BN running stats + optimizer state.

    The reference's equivalents: module params/buffers on each replica (DDP
    keeps them in sync via grad allreduce + buffer broadcast) and
    ``optimizer.state`` (momentum buffers, train_distributed.py:207).  The
    iteration counter lives in ``opt_state.step``.

    ``ema``: exponential moving average of params (config ``training.ema``;
    empty dict when disabled, so the pytree stays checkpoint- and
    shard_map-friendly without structural branching).
    """

    params: Any
    batch_stats: Any
    opt_state: Any
    ema: Any = struct.field(default_factory=dict)

    @property
    def step(self):
        return self.opt_state.step


def init_train_state(model, optimizer, rng, sample_input) -> TrainState:
    """Same-seed replicated init — the DDP param broadcast (reference :198)
    is redundant when every replica initializes from the same PRNGKey
    (the reference already seeds all ranks identically, :141-142)."""
    variables = model.init(rng, sample_input, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(
        params=params,
        batch_stats=batch_stats,
        opt_state=optimizer.init(params),
    )


def _input_normalizer(input_norm) -> Callable:
    """Build the in-graph ``(x/255 - mean)/std`` affine for uint8 batches.

    ``input_norm`` is ``(mean, std)`` per channel.  Uses the same
    ``x*scale + bias`` form (f32) as the native host kernel
    (native/__init__.py: scale=1/(255*std), bias=-mean/std) so device-side
    normalization matches the host path to float rounding.  Identity when
    ``input_norm`` is None (host-normalized float32 input — reference
    parity).
    """
    if input_norm is None:
        return lambda img: img
    import numpy as np

    mean, std = (np.asarray(x, np.float32) for x in input_norm)
    scale = jnp.asarray(1.0 / (255.0 * std), jnp.float32)
    bias = jnp.asarray(-mean / std, jnp.float32)

    def normalize(img):
        return img.astype(jnp.float32) * scale + bias

    return normalize


def build_train_step(
    model,
    optimizer,
    lr_fn: Callable,
    mesh: Mesh,
    sync_bn: bool,
    donate: bool = True,
    input_norm=None,
    grad_accum: int = 1,
    label_smoothing: float = 0.0,
    ema_decay: Optional[float] = None,
    anomaly_factor: Optional[float] = None,
):
    """Compile the full training iteration as one SPMD program.

    Gradient reduction lives in one place, the step's own differentiation:
    the objective is the GLOBAL-batch mean (``pmean`` inside the
    differentiated function), and ``shard_map``'s AD transpose ``psum``s the
    cotangent of the replicated parameters.  No explicit gradient collective
    exists in the ``shard_map`` step families; sharded optimizer state is
    ``training.zero`` on the GSPMD family (:mod:`.tp_steps`).

    Args:
      model: a linen module whose ``apply`` takes ``(variables, img, train=...)``
        and mutates ``batch_stats`` in train mode.  When ``sync_bn``, the model
        must carry ``axis_name=DATA_AXIS`` so its BN layers ``pmean`` their
        statistics (the reference's SyncBatchNorm conversion, :196-197).
      optimizer: functional optimizer (``init``/``update``) from
        :mod:`..optimizers`.
      lr_fn: pure schedule ``lr(step)`` evaluated on-device (see
        :mod:`..schedulers`).
      sync_bn: whether BN stats are cross-replica (config ``training.sync_bn``).
      input_norm: optional ``(mean, std)`` — the batch arrives as raw uint8
        and is normalized in-graph (4x less host->device traffic; config
        ``training.device_normalize``).
      grad_accum: micro-batch count (config ``training.grad_accumulation``).
        The per-device batch is processed as ``grad_accum`` sequential
        micro-batches under ``lax.scan`` — activation memory shrinks by the
        factor while the update stays the mean over the full batch (equal
        micro sizes => mean of micro means == full mean).  BN running stats
        update once per micro-batch with per-micro statistics, matching
        torch's behavior when accumulating under DDP.
      label_smoothing: torch-convention smoothing factor (config
        ``training.label_smoothing``; 0 = reference parity).  Deliberately
        applied to the TRAINING objective only — the eval step reports
        unsmoothed CE so validation losses stay comparable across smoothing
        settings (the perplexity convention).
      ema_decay: when set, maintain ``state.ema`` as the exponential moving
        average of the updated params, ``ema <- d*ema + (1-d)*params``
        (config ``training.ema.decay``; the Runner evaluates with the EMA
        params when enabled).
      anomaly_factor: when set, arm the anomaly-step guard (config
        ``training.fault_tolerance.anomaly``).  The step additionally takes
        a host-fed ``gnorm_ref`` scalar (trailing-median grad norm; a
        python float, so feeding a new value never retraces) and computes
        the global grad norm on-device.  A step whose loss/grad-norm is
        non-finite — or whose grad norm exceeds ``anomaly_factor *
        gnorm_ref`` when both are positive (``anomaly_factor == 0`` means
        non-finite-only) — is SKIPPED: params, BN stats, optimizer state
        and EMA are ``jnp.where``-gated back to their inputs, so nothing
        anomalous ever leaves the compiled step and the state stays
        bitwise-identical.  The step then returns ``(state, loss, gnorm,
        applied)`` instead of ``(state, loss)``; ``None`` (the default)
        compiles the exact ungated program.
    """
    normalize = _input_normalizer(input_norm)

    def micro_loss(params, batch_stats, img, label):
        # normalize PER MICRO-BATCH: converting uint8 -> f32 up front would
        # pin a 4x-size buffer across the whole accumulation scan, defeating
        # the memory savings grad_accum exists for
        img = normalize(img)

        def loss_fn(p):
            with jax.named_scope("forward"):
                out, mutated = model.apply(
                    {"params": p, "batch_stats": batch_stats},
                    img,
                    train=True,
                    mutable=["batch_stats"],
                )
            with jax.named_scope("loss_head"):
                loss = cross_entropy_loss(out, label, label_smoothing)
            # The OBJECTIVE is the global-batch mean (each replica's CE is
            # the mean over its local shard).  Differentiating it is the
            # DDP-reducer equivalent: shard_map's AD transpose psums the
            # cotangent of the replicated params across the mesh, so `grads`
            # below is exactly the DDP-averaged gradient.  This is the only
            # gradient reduction: an explicit post-grad collective would
            # count it twice (world_size x too large; regression-tested in
            # tests/test_engine.py::test_dp_step_matches_single_device).
            # XLA overlaps the underlying all-reduce with independent
            # backward compute, like DDP's bucketed reducer (reference :198).
            loss = jax.lax.pmean(loss, DATA_AXIS)
            # models without batch statistics (e.g. ViT) mutate nothing
            return loss, mutated.get("batch_stats", {})

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    # When the optimizer is fused AND EMA is on, fold the EMA decay into the
    # same fused update pass (one kernel per dtype group for update+EMA
    # combined) instead of paying a separate one-kernel-per-leaf tree.map
    # after the shard_map.  Identical math either way (regression-tested in
    # tests/test_profiling.py); the fold only exists for the kernel count.
    fold_ema = ema_decay is not None and getattr(optimizer, "fused", False)
    guard = anomaly_factor is not None

    def body(params, batch_stats, opt_state, img, label, ema, *guard_args):
        if grad_accum > 1:
            b = img.shape[0]
            if b % grad_accum != 0:
                raise ValueError(
                    f"per-device batch {b} not divisible by "
                    f"grad_accumulation {grad_accum}"
                )
            micro = b // grad_accum
            img = img.reshape(grad_accum, micro, *img.shape[1:])
            label = label.reshape(grad_accum, micro)
            zero_grads = jax.tree.map(jnp.zeros_like, params)

            def scan_step(carry, xy):
                bs, acc, loss_acc = carry
                (loss, new_bs), grads = micro_loss(params, bs, *xy)
                acc = jax.tree.map(
                    lambda a, g: a + g / grad_accum, acc, grads
                )
                return (new_bs, acc, loss_acc + loss / grad_accum), None

            (new_bs, grads, loss), _ = jax.lax.scan(
                scan_step, (batch_stats, zero_grads, jnp.float32(0.0)),
                (img, label),
            )
        else:
            (loss, new_bs), grads = micro_loss(params, batch_stats, img, label)
        if not sync_bn:
            # Local BN stats diverge per replica; average them so the state
            # stays replicated (the reference's DDP broadcast_buffers keeps
            # replicas in sync by broadcasting rank-0 — an averaging variant
            # with the same fixed point; deviation documented in SURVEY §2.3).
            new_bs = jax.lax.pmean(new_bs, DATA_AXIS)
        lr = lr_fn(opt_state.step)
        with jax.named_scope("optimizer"):
            if fold_ema:
                new_params, new_opt, new_ema = optimizer.update_with_ema(
                    grads, opt_state, params, lr, ema, float(ema_decay)
                )
            else:
                new_params, new_opt = optimizer.update(
                    grads, opt_state, params, lr
                )
                new_ema = ema
        if not guard:
            return new_params, new_bs, new_opt, loss, new_ema
        (gnorm_ref,) = guard_args
        # grads are already the psum-reduced (replicated) global gradient —
        # the norm is identical on every replica, no extra collective
        gnorm = jnp.sqrt(
            sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)
            )
        )
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        if anomaly_factor > 0:
            # spike check only once a trailing median exists (ref > 0) —
            # the first steps of a run have no baseline to spike against
            ok = ok & (
                (gnorm_ref <= 0.0) | (gnorm <= anomaly_factor * gnorm_ref)
            )

        def sel(new, old):
            return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)

        return (
            sel(new_params, params), sel(new_bs, batch_stats),
            sel(new_opt, opt_state), loss, sel(new_ema, ema), gnorm, ok,
        )

    rep = P()
    img_spec = P(DATA_AXIS, None, None, None)
    label_spec = P(DATA_AXIS)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rep, rep, rep, img_spec, label_spec, rep)
        + ((rep,) if guard else ()),
        out_specs=(rep, rep, rep, rep, rep) + ((rep, rep) if guard else ()),
    )

    @jax.named_scope("optimizer")
    def _ema_outside(ok, old_ema, new_params):
        # replicated elementwise update — no collective needed, so it
        # lives outside the shard_map
        d = float(ema_decay)
        if ok is None:
            return jax.tree.map(
                lambda e, p: d * e + (1.0 - d) * p, old_ema, new_params
            )
        # gated: new_params is already the OLD params on a skipped step, so
        # an unguarded decay would still drift the EMA toward them
        return jax.tree.map(
            lambda e, p: jnp.where(ok, d * e + (1.0 - d) * p, e),
            old_ema, new_params,
        )

    if guard:

        @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
        def train_step(state: TrainState, img, label, gnorm_ref):
            new_params, new_bs, new_opt, loss, new_ema, gnorm, ok = sharded(
                state.params, state.batch_stats, state.opt_state, img, label,
                state.ema, gnorm_ref,
            )
            if ema_decay is not None and not fold_ema:
                new_ema = _ema_outside(ok, state.ema, new_params)
            return (
                TrainState(
                    params=new_params, batch_stats=new_bs, opt_state=new_opt,
                    ema=new_ema,
                ),
                loss,
                gnorm,
                ok.astype(jnp.float32),
            )

        return register_compiled("train_step/gspmd_guarded", train_step)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def train_step(state: TrainState, img, label):
        new_params, new_bs, new_opt, loss, new_ema = sharded(
            state.params, state.batch_stats, state.opt_state, img, label,
            state.ema,
        )
        if ema_decay is not None and not fold_ema:
            new_ema = _ema_outside(None, state.ema, new_params)
        return (
            TrainState(
                params=new_params, batch_stats=new_bs, opt_state=new_opt,
                ema=new_ema,
            ),
            loss,
        )

    return register_compiled("train_step/gspmd", train_step)


def build_eval_step(model, mesh: Mesh, input_norm=None):
    """Compile the distributed validation step (reference :309-321)."""
    normalize = _input_normalizer(input_norm)

    def body(params, batch_stats, img, label):
        img = normalize(img)
        out = model.apply(
            {"params": params, "batch_stats": batch_stats}, img, train=False
        )
        loss = cross_entropy_loss(out, label)
        acc1, acc5 = accuracy(out, label, topk=(1, 5))
        # reference: all_reduce(SUM) then / world_size  ==  pmean
        return jax.lax.pmean((loss, acc1, acc5), DATA_AXIS)

    rep = P()
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rep, rep, P(DATA_AXIS, None, None, None), P(DATA_AXIS)),
        out_specs=(rep, rep, rep),
    )

    @jax.jit
    def eval_step(state: TrainState, img, label):
        return sharded(state.params, state.batch_stats, img, label)

    return eval_step


def build_eval_step_exact(model, mesh: Mesh, input_norm=None):
    """Exact-count distributed validation (``validation.exact: true``).

    The parity eval (:func:`build_eval_step` + per-batch ``AverageMeter``)
    inherits two reference biases on non-divisible val sets: the
    ``DistributedSampler`` wrap-padded tail double-counts samples (torch
    semantics, reference train_distributed.py:219-222) and the unweighted
    per-batch meter over-weights a smaller final batch.  This step returns
    GLOBAL SUMS ``(ce_sum, top1_sum, top5_sum, n)`` with a per-sample
    validity mask folded in before the ``psum`` — masked samples (sampler
    wrap-pads, runner batch-padding) contribute nothing, so
    ``sums / n`` is exact for any val-set size.  Default remains the
    parity eval (Runner.validate)."""
    normalize = _input_normalizer(input_norm)

    def body(params, batch_stats, img, label, mask):
        img = normalize(img)
        out = model.apply(
            {"params": params, "batch_stats": batch_stats}, img, train=False
        )
        logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]
        # k clamped like metrics.accuracy's argsort form: < 5 classes must
        # not turn the exact flag into a trace-time crash
        topk = jax.lax.top_k(out, min(5, out.shape[-1]))[1]
        c1 = (topk[:, 0] == label).astype(jnp.float32)
        c5 = jnp.any(topk == label[:, None], axis=-1).astype(jnp.float32)
        m = mask.astype(jnp.float32)
        return jax.lax.psum(
            (jnp.sum(ce * m), jnp.sum(c1 * m), jnp.sum(c5 * m), jnp.sum(m)),
            DATA_AXIS,
        )

    rep = P()
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            rep, rep, P(DATA_AXIS, None, None, None), P(DATA_AXIS),
            P(DATA_AXIS),
        ),
        out_specs=(rep, rep, rep, rep),
    )

    @jax.jit
    def eval_step(state: TrainState, img, label, mask):
        return sharded(state.params, state.batch_stats, img, label, mask)

    return eval_step
