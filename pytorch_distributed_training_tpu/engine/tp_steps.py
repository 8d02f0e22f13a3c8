"""Compiled tensor-parallel (DP x TP) LM training step via GSPMD.

Unlike the shard_map-based DP/SP steps (steps.py / sp_steps.py), this step
is written as straight-line single-device math and parallelized entirely by
sharding annotations: params carry the Megatron-style ``model``-axis specs
from :mod:`..parallel.tensor`, the batch is sharded over ``data``, and the
XLA SPMD partitioner inserts every collective (gradient all-reduce over
data, partial-sum all-reduce after the row-parallel matmuls, resharding at
boundaries).  This is the scaling-book recipe verbatim: pick a mesh,
annotate, let XLA do the communication scheduling.

The same :class:`TransformerLM` module (seq_axis=None) is used — TP here
composes with DP, and — on a 3-D ``(data, sequence, model)`` mesh
(``parallel.make_3d_mesh``) — with GSPMD sequence parallelism too: token
inputs shard over BOTH the data and sequence axes and the partitioner
inserts the sequence resharding around attention (DeepSpeed-Ulysses-style
all-to-alls fall out of the sharding propagation).  The shard_map-based
ring-attention path (``sp_steps``) remains the memory-optimal choice for
SP-only long-context runs; this GSPMD path is what composes all three
axes in one program.
"""
from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import cross_entropy_loss
from ..parallel.mesh import DATA_AXIS
from ..parallel.sequence import SEQUENCE_AXIS
from ..parallel.tensor import tp_state_shardings
from ..telemetry.retrace import register_compiled
from .steps import TrainState


def _token_spec(mesh: Mesh) -> P:
    """Tokens shard over data (+ sequence, when the mesh carries that axis)."""
    if SEQUENCE_AXIS in mesh.axis_names:
        return P(DATA_AXIS, SEQUENCE_AXIS)
    return P(DATA_AXIS, None)


def _token_ce(logits, labels, mesh: Mesh, label_smoothing: float = 0.0):
    """Mean token CE of ``[B, S, V]`` logits as a shard_map island.

    On TPU ``cross_entropy_loss`` is a Pallas kernel, and a Mosaic call has
    no GSPMD partitioning rule (the v5e compiler: "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map") — the
    same reason attention runs in an island (ops/attention.py).  Each
    device takes the CE of its own ``[B/dp, S/sp]`` tokens; the shards are
    equal-sized, so the pmean of the local means is the global mean.
    """
    spec = _token_spec(mesh)
    axes = tuple(a for a in spec if a is not None)

    def local(lg, lb):
        loss = cross_entropy_loss(
            lg.reshape(-1, lg.shape[-1]), lb.reshape(-1), label_smoothing
        )
        return jax.lax.pmean(loss, axes)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(*spec, None), spec), out_specs=P()
    )(logits, labels)

__all__ = ["build_tp_lm_train_step", "build_tp_lm_eval_step"]

# Step-family label for the static collective-order oracle (see
# analysis/collectives.py and PERF.md).  The TP path is GSPMD-compiled:
# collectives are inserted by the partitioner, so the static extraction
# legitimately reports zero explicit collectives for this family.
PDT_COLLECTIVE_FAMILY = "tp"


def build_tp_lm_train_step(
    model,
    optimizer,
    lr_fn: Callable,
    mesh: Mesh,
    donate: bool = True,
    label_smoothing: float = 0.0,
    zero: int = 0,
    grad_accum: int = 1,
):
    """Compile one DP x TP LM iteration (GSPMD-partitioned).

    ``model`` must be a :class:`TransformerLM` with ``seq_axis=None`` (the
    partitioner, not the module, distributes the math).  Use
    :func:`..parallel.tensor.tp_state_shardings` to place the state before
    the first call; in/out shardings are pinned so XLA keeps params resident
    in their TP layout across steps.

    ``grad_accum``: process the batch as N sequential micro-batches under
    ``lax.scan`` (activation memory / N).  Equal micro sizes make the mean
    of per-micro mean losses the exact full-batch objective; for MoE the
    aux loss (and routing capacity) is likewise per-micro — the average of
    per-micro aux terms, the standard accumulation semantics.

    ``zero``: 0/False = mirrored optimizer state; 1/True = ZeRO-1 (moments
    sharded over ``data``; the partitioner reduce-scatters grads into the
    sharded update and all-gathers fresh params); 2 = ZeRO-2 — additionally
    pins GRADIENT buffers to the same sharded layout via
    ``with_sharding_constraint``, so each device holds only its 1/N grad
    slice (and, under ``grad_accum``, a 1/N accumulator carried across
    micro-batches) instead of a replicated full-gradient tree.  The update
    math is identical in all three modes.
    """
    import jax.numpy as jnp

    from ..parallel.tensor import zero_grad_shardings

    zero = int(zero)
    # Hand the model the mesh so attention runs the Pallas flash kernel in
    # a shard_map island (ops/attention.py) — a bare pallas_call has no
    # GSPMD partitioning rule, so without this every TP/ZeRO/FSDP/MoE step
    # paid O(S^2) einsum attention (VERDICT r4 weak #3).  clone() changes
    # static config only; param shapes are untouched.
    if hasattr(model, "flash_mesh") and model.flash_mesh is None:
        model = model.clone(flash_mesh=mesh)

    def shard_grads(grads):
        """ZeRO-2: reduce-scatter gradients into their 1/N home slices."""
        return jax.lax.with_sharding_constraint(
            grads, zero_grad_shardings(grads, mesh)
        )

    def loss_fn(p, tokens, labels):
        # mutable="intermediates" collects sown auxiliary objectives —
        # today the MoE load-balancing loss (ops/moe.py sows the
        # already-weighted value under ``moe_aux``); dense models sow
        # nothing.  Only ``moe_aux`` entries join the objective: other
        # sown intermediates (telemetry, debugging) must NOT leak into
        # the loss (r2 code-review finding).  Validation stays pure CE.
        with jax.named_scope("forward"):
            logits, inter = model.apply(
                {"params": p}, tokens, mutable="intermediates"
            )
        with jax.named_scope("loss_head"):
            loss = _token_ce(logits, labels, mesh, label_smoothing)
        for path, leaf in jax.tree_util.tree_flatten_with_path(inter)[0]:
            if any(
                str(getattr(key, "key", key)) == "moe_aux" for key in path
            ):
                loss = loss + leaf
        return loss

    def step(state: TrainState, tokens, labels):
        if grad_accum > 1:
            b, seq = tokens.shape
            if b % grad_accum != 0:
                raise ValueError(
                    f"global batch {b} not divisible by grad_accumulation "
                    f"{grad_accum}"
                )
            micro = b // grad_accum
            # keep each micro-batch sharded exactly like the full batch
            # (data [+ sequence] on the row dim) — without the constraint
            # the partitioner may shard the scan axis instead, serializing
            # the data parallelism
            micro_spec = P(None, *_token_spec(mesh))
            tok = jax.lax.with_sharding_constraint(
                tokens.reshape(grad_accum, micro, seq),
                NamedSharding(mesh, micro_spec),
            )
            lab = jax.lax.with_sharding_constraint(
                labels.reshape(grad_accum, micro, seq),
                NamedSharding(mesh, micro_spec),
            )
            zero_g = jax.tree.map(jnp.zeros_like, state.params)
            if zero >= 2:
                zero_g = shard_grads(zero_g)

            def scan_step(carry, xy):
                acc, loss_acc = carry
                loss, grads = jax.value_and_grad(loss_fn)(state.params, *xy)
                if zero >= 2:
                    # each micro's grads land in their 1/N slices BEFORE the
                    # add, keeping the carried accumulator sharded
                    grads = shard_grads(grads)
                return (jax.tree.map(jnp.add, acc, grads), loss_acc + loss), None

            (grads, loss_sum), _ = jax.lax.scan(
                scan_step, (zero_g, jnp.float32(0.0)), (tok, lab)
            )
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss_sum / grad_accum
        else:
            loss, grads = jax.value_and_grad(loss_fn)(
                state.params, tokens, labels
            )
            if zero >= 2:
                grads = shard_grads(grads)
        lr = lr_fn(state.opt_state.step)
        # A `fused=True` optimizer composes with ZeRO here unchanged: this is
        # GSPMD (not shard_map), so the concatenated flat update buffers are
        # ordinary ops on sharded arrays and XLA's sharding propagation
        # chooses the layout — the cross-replica sharded weight update of
        # arXiv:2004.13336 expressed declaratively.  Bitwise parity with the
        # per-leaf path is pinned in tests/test_profiling.py (incl. a ZeRO-1
        # GSPMD case); whether concat beats per-leaf under ZeRO is a chip
        # measurement (the ``optimizer`` scope in a trace), not an assumption.
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(
                grads, state.opt_state, state.params, lr
            )
        return (
            TrainState(
                params=new_params, batch_stats=state.batch_stats,
                opt_state=new_opt, ema=state.ema,
            ),
            loss,
        )

    def compile_for(state: TrainState):
        """jit with shardings derived from this state's structure."""
        state_sh = tp_state_shardings(state, mesh, zero=zero)
        tok_sh = NamedSharding(mesh, _token_spec(mesh))
        rep = NamedSharding(mesh, P())
        return register_compiled(
            "lm_train_step/tp",
            jax.jit(
                step,
                in_shardings=(state_sh, tok_sh, tok_sh),
                out_shardings=(state_sh, rep),
                donate_argnums=(0,) if donate else (),
            ),
        )

    return compile_for


def build_tp_lm_eval_step(model, mesh: Mesh, zero: int = 0):
    """Compile the TP LM validation step (GSPMD-partitioned).

    Same contract as the other eval steps — replicated ``(loss, acc1,
    acc5)``: mean CE per token + next-token top-1/top-5 — so
    ``Runner.validate`` drives it unchanged.  Like the train step, returns a
    ``compile_for(state)`` closure that pins the TP state shardings.
    """
    from ..metrics import accuracy

    # same flash-island mesh hint as the train step
    if hasattr(model, "flash_mesh") and model.flash_mesh is None:
        model = model.clone(flash_mesh=mesh)

    def step(state: TrainState, tokens, labels):
        logits = model.apply({"params": state.params}, tokens)
        vocab = logits.shape[-1]
        flat_logits = logits.reshape(-1, vocab)
        flat_labels = labels.reshape(-1)
        loss = _token_ce(logits, labels, mesh)
        acc1, acc5 = accuracy(flat_logits, flat_labels, topk=(1, 5))
        return loss, acc1, acc5

    def compile_for(state: TrainState):
        state_sh = tp_state_shardings(state, mesh, zero=zero)
        tok_sh = NamedSharding(mesh, _token_spec(mesh))
        rep = NamedSharding(mesh, P())
        return jax.jit(
            step,
            in_shardings=(state_sh, tok_sh, tok_sh),
            out_shardings=(rep, rep, rep),
        )

    return compile_for
