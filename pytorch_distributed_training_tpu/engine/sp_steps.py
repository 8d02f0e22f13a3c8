"""Compiled sequence-parallel (long-context) LM training step.

The DP step in :mod:`.steps` shards the *batch*; this step additionally
shards the *sequence* over a second mesh axis, the TPU-native analog of
ring-attention context parallelism: one compiled SPMD program in which
attention streams K/V blocks around the sequence ring (``ppermute`` over
ICI) while every other component stays per-token local.

Gradient math (why this is exact): the objective is the per-token CE summed
locally, normalized by the GLOBAL token count, and ``psum``-reduced over
(data, sequence) *inside the differentiated function* — i.e. the true
global mean loss as a replicated scalar.  Differentiating it gives the
exact global gradient with no post-grad collective: every local
contribution is a partial sum (token embeddings and position slices touch
disjoint rows, transformer weights accumulate only local-token terms, and
attention K/V cotangents ride the ring back to their owners), and
shard_map's AD transpose psums the replicated params' cotangent across the
mesh.  No special-casing per parameter, unlike pooled classifiers where
post-reduction params would behave differently.

Batch layout: ``tokens``/``labels`` are ``[global_batch, global_seq]``
sharded ``P(data, sequence)``.  Labels are the host-shifted next tokens
(the shift crosses shard boundaries, so it must happen before sharding).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import cross_entropy_loss
from ..parallel.mesh import DATA_AXIS
from ..parallel.sequence import SEQUENCE_AXIS
from ..telemetry.retrace import register_compiled
from .steps import TrainState

__all__ = ["build_lm_train_step", "build_lm_eval_step", "lm_loss_local"]

# Step-family label for the static collective-order oracle (see
# analysis/collectives.py and PERF.md).
PDT_COLLECTIVE_FAMILY = "sp"


def lm_loss_local(logits, labels, global_tokens: int, label_smoothing: float = 0.0):
    """Local partial loss: sum of per-token CE / global token count (fp32).

    Routes through :func:`..ops.cross_entropy_loss` (token-flattened), so the
    [B*S, V] softmax-CE — the largest CE in the framework — hits the Pallas
    fused kernel on TPU; the local mean is rescaled to the global-sum
    normalization the SP gradient math needs.
    """
    vocab = logits.shape[-1]
    with jax.named_scope("loss_head"):
        local_mean = cross_entropy_loss(
            logits.reshape(-1, vocab), labels.reshape(-1), label_smoothing
        )
        return local_mean * (labels.size / global_tokens)


def build_lm_train_step(
    model,
    optimizer,
    lr_fn: Callable,
    mesh: Mesh,
    data_axis: str = DATA_AXIS,
    seq_axis: str = SEQUENCE_AXIS,
    donate: bool = True,
    grad_accum: int = 1,
    label_smoothing: float = 0.0,
    anomaly_factor=None,
):
    """Compile one DP x SP training iteration for a :class:`TransformerLM`.

    ``model.seq_axis`` must equal ``seq_axis`` (the module runs its ring
    attention over that mesh axis); ``mesh`` must carry both axes.

    ``grad_accum``: process the local batch as N sequential micro-batches
    under ``lax.scan`` (activation memory / N).  Each micro loss is already
    a partial sum normalized by the GLOBAL token count, so accumulating
    grad/loss *sums* over micros reproduces the full-batch objective
    exactly.

    ``anomaly_factor``: arm the anomaly-step guard — same contract as
    :func:`..engine.steps.build_train_step`: the step takes an extra
    host-fed ``gnorm_ref`` scalar and returns ``(state, loss, gnorm,
    applied)``, with params/opt-state ``jnp.where``-gated back to their
    inputs on a non-finite or spiking step.

    Gradient reduction is the differentiation's own (module docstring): no
    explicit gradient collective exists in the ``shard_map`` step families,
    and sharded optimizer state is ``training.zero`` on the GSPMD family
    (:mod:`.tp_steps`).
    """
    axes = (data_axis, seq_axis)
    n_data = mesh.shape[data_axis]
    n_seq = mesh.shape[seq_axis]
    guard = anomaly_factor is not None

    def body(params, opt_state, tokens, labels, *guard_args):
        b_local, s_local = tokens.shape
        global_tokens = b_local * s_local * n_data * n_seq

        def loss_fn(p, tok, lab):
            with jax.named_scope("forward"):
                logits = model.apply({"params": p}, tok)
            # objective = GLOBAL mean CE per token: psum of the local partial
            # sums (each already /global_tokens).  Differentiating this
            # replicated scalar yields the exact global gradient directly —
            # shard_map's AD transpose psums the replicated params' cotangent
            # across both mesh axes (an explicit post-grad psum would
            # double-count; regression-tested in tests/test_transformer_lm.py).
            local = lm_loss_local(logits, lab, global_tokens, label_smoothing)
            return jax.lax.psum(local, axes)

        if grad_accum > 1:
            if b_local % grad_accum != 0:
                raise ValueError(
                    f"per-shard batch {b_local} not divisible by "
                    f"grad_accumulation {grad_accum}"
                )
            micro = b_local // grad_accum
            tok = tokens.reshape(grad_accum, micro, s_local)
            lab = labels.reshape(grad_accum, micro, s_local)
            zero = jax.tree.map(jnp.zeros_like, params)

            def scan_step(carry, xy):
                acc, loss_acc = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, *xy)
                return (
                    jax.tree.map(jnp.add, acc, grads),
                    loss_acc + loss,
                ), None

            (grads, loss), _ = jax.lax.scan(
                scan_step, (zero, jnp.float32(0.0)), (tok, lab)
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        lr = lr_fn(opt_state.step)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(
                grads, opt_state, params, lr
            )
        if not guard:
            return new_params, new_opt, loss
        (gnorm_ref,) = guard_args
        # grads are the exact replicated global gradient (psum'd objective)
        # — the norm matches on every shard, no extra collective
        gnorm = jnp.sqrt(
            sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)
            )
        )
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        if anomaly_factor > 0:
            ok = ok & (
                (gnorm_ref <= 0.0) | (gnorm <= anomaly_factor * gnorm_ref)
            )

        def sel(new, old):
            return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, old)

        return sel(new_params, params), sel(new_opt, opt_state), loss, gnorm, ok

    rep = P()
    tok_spec = P(data_axis, seq_axis)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rep, rep, tok_spec, tok_spec) + ((rep,) if guard else ()),
        out_specs=(rep, rep, rep) + ((rep, rep) if guard else ()),
    )

    if guard:

        @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
        def train_step(state: TrainState, tokens, labels, gnorm_ref):
            new_params, new_opt, loss, gnorm, ok = sharded(
                state.params, state.opt_state, tokens, labels, gnorm_ref
            )
            return (
                TrainState(
                    params=new_params, batch_stats=state.batch_stats,
                    opt_state=new_opt, ema=state.ema,
                ),
                loss,
                gnorm,
                ok.astype(jnp.float32),
            )

        return register_compiled("lm_train_step/sp_guarded", train_step)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def train_step(state: TrainState, tokens, labels):
        new_params, new_opt, loss = sharded(
            state.params, state.opt_state, tokens, labels
        )
        return (
            TrainState(
                params=new_params, batch_stats=state.batch_stats,
                opt_state=new_opt, ema=state.ema,
            ),
            loss,
        )

    return register_compiled("lm_train_step/sp", train_step)


def build_lm_eval_step(
    model,
    mesh: Mesh,
    data_axis: str = DATA_AXIS,
    seq_axis: str = SEQUENCE_AXIS,
):
    """Compile the distributed LM validation step.

    Mirrors the classifier eval contract (engine/steps.py, reference
    :309-321): returns replicated ``(loss, acc1, acc5)`` — mean CE per token
    and next-token top-1/top-5 accuracy, ``psum``-weighted over the (data,
    sequence) axes so every shard's tokens count once.  Same signature as
    the classifier eval step, so ``Runner.validate`` drives either.
    """
    from ..metrics import accuracy

    axes = (data_axis, seq_axis)
    n_shards = mesh.shape[data_axis] * mesh.shape[seq_axis]

    def body(params, tokens, labels):
        logits = model.apply({"params": params}, tokens)
        vocab = logits.shape[-1]
        flat_logits = logits.reshape(-1, vocab)
        flat_labels = labels.reshape(-1)
        global_tokens = flat_labels.size * n_shards
        loss = jax.lax.psum(
            lm_loss_local(logits, labels, global_tokens), axes
        )
        acc1, acc5 = accuracy(flat_logits, flat_labels, topk=(1, 5))
        # equal local token counts -> psum/n == the global token mean
        acc1, acc5 = jax.lax.pmean((acc1, acc5), axes)
        return loss, acc1, acc5

    rep = P()
    tok_spec = P(data_axis, seq_axis)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rep, tok_spec, tok_spec),
        out_specs=(rep, rep, rep),
    )

    @jax.jit
    def eval_step(state: TrainState, tokens, labels):
        return sharded(state.params, tokens, labels)

    return eval_step
