"""Compiled pipeline-parallel (DP x PP) LM training step.

GPipe microbatch schedule as ONE ``shard_map``-ed XLA program on a
``(data, stage)`` mesh — see :mod:`..parallel.pipeline` for the layout and
the exactness argument.  The reference has no pipeline axis at all
(SURVEY.md §2.4); this composes with data parallelism the same way the SP
and TP steps do and plugs into the same ``Runner`` contract.

Design notes (TPU/XLA):
  - the tick loop is a ``lax.scan`` (static trip count ``M + S - 1``), so
    the whole schedule — including the bubble — compiles once; no Python
    per-tick dispatch.
  - inter-stage transfer is a single ``ppermute`` per tick over the
    ``stage`` axis (nearest-neighbor ICI DMA), which XLA overlaps with the
    next tick's compute where the dependence allows.
  - under SPMD every stage runs the same program TEXT, but embedding and
    head math are gated by ``lax.cond`` on the (device-varying) stage
    index, so only stage 0 executes the embed and only the last stage
    executes the head+loss (in the gpipe scan and the eval step the head
    gate additionally folds in tick validity; the 1F1B slots gate on the
    stage index alone and mask the results per slot) — XLA's conditional
    runs just the taken branch at runtime.  The head is NOT negligible at large vocab
    (at the shipped TransformerLM-pp.yml scale it is ~40% of a stage's
    per-tick FLOPs): before round 5 every stage computed embed+head and
    masked the results, putting embed+blocks+head on the lockstep critical
    path; the conds cut that to max(embed+blocks, blocks+head) and
    interior stages run blocks only.  The AD hazard and its resolution
    (shared params pcast to stage-varying so the cotangent stage-psum
    cannot land inside a single-stage branch) are documented at the cond
    sites.  The blocks were never duplicated — each stage applies only
    its own layer shard.
  - tick inputs are index-clipped to real microbatches (never garbage), so
    bubble ticks compute on valid data and masking alone guarantees
    correctness — no NaN-through-``where`` hazards.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

from ..models.transformer_lm import DecoderBlock
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS
from ..parallel.pipeline import STAGE_AXIS, pp_param_specs
from ..parallel.tensor import mirror_opt_fields
from ..telemetry.retrace import register_compiled
from ..utils.vma import mark_varying
from .sp_steps import lm_loss_local
from .steps import TrainState

__all__ = ["build_pp_lm_train_step", "build_pp_lm_eval_step"]

# Step-family label for the static collective-order oracle (see
# analysis/collectives.py and PERF.md).
PDT_COLLECTIVE_FAMILY = "pp"


def _stage_applies(model, seq_axis=None):
    """(embed, blocks, head) closures over a TransformerLM's hyperparams.

    Reuses the model's own flax modules for the shared pieces so the math is
    bit-identical to ``TransformerLM.__call__`` (models/transformer_lm.py).
    With ``seq_axis`` set (PP x SP), each stage's blocks run ring attention
    over that mesh axis and the positional embedding is sliced to the
    sequence shard — the same construction TransformerLM applies when its
    own ``seq_axis`` is set (models/transformer_lm.py:119-130).
    """
    block = DecoderBlock(
        num_heads=model.num_heads,
        mlp_ratio=model.mlp_ratio,
        seq_axis=seq_axis,
        seq_impl=model.seq_impl,
        dtype=model.dtype,
    )
    ln = nn.LayerNorm(dtype=model.dtype)
    head = nn.Dense(model.vocab_size, dtype=jnp.float32)

    def embed(shared, tokens):
        x = jnp.take(shared["tok_embedding"], tokens, axis=0).astype(model.dtype)
        s = tokens.shape[1]
        if seq_axis is None:
            pe = shared["pos_embedding"][:s]
        else:
            off = jax.lax.axis_index(seq_axis) * s
            pe = jax.lax.dynamic_slice_in_dim(
                shared["pos_embedding"], off, s, axis=0
            )
        return x + pe[None].astype(model.dtype)

    @jax.named_scope("forward")
    def apply_blocks(blocks_local, x):
        def layer(x, p):
            return block.apply({"params": p}, x), None

        if model.remat:
            from ..models.transformer_lm import resolve_remat_policy

            f = jax.checkpoint(
                layer, policy=resolve_remat_policy(model.remat_policy)
            )
        else:
            f = layer
        x, _ = jax.lax.scan(f, x, blocks_local)
        return x

    @jax.named_scope("loss_head")
    def apply_head(shared, x):
        h = ln.apply({"params": shared["ln"]}, x)
        return head.apply({"params": shared["head"]}, h)

    return embed, apply_blocks, apply_head


def _sim_1f1b(n_micro: int, n_stages: int):
    """Static 1F1B (PipeDream-Flush) tick schedule, event-simulated.

    Every tick each stage has one F slot and one B slot (the compiled tick
    body always executes both, masked — SPMD lockstep).  A stage runs its
    next forward when the previous stage finished that microbatch at a
    strictly earlier tick AND its in-flight count is under the 1F1B window
    ``n_stages - s`` (the property that caps activation memory at O(S)
    microbatches instead of GPipe's O(M)); it runs its next backward when
    its own forward and the next stage's backward for that microbatch are
    done.  Greedy earliest-tick scheduling of those dependencies IS 1F1B:
    the window forces backwards to interleave as soon as they unblock.

    Returns ``(f_mb, f_on, b_mb, b_on, depth)``: [T, S] int/bool arrays
    (tick t, stage s) plus the ring-buffer depth the activation buffers
    need (max concurrently-live intervals measured on the simulated
    schedule — FIFO per stage, so ``mb % depth`` slots cannot collide).
    """
    M, S = int(n_micro), int(n_stages)
    fwd_done = [[-1] * M for _ in range(S)]
    bwd_done = [[-1] * M for _ in range(S)]
    next_f, next_b = [0] * S, [0] * S
    rows_f, rows_b = [], []
    t = 0
    while any(nb < M for nb in next_b):
        f_row, b_row = [], []
        for s in range(S):
            m = next_f[s]
            can_f = (
                m < M
                and (s == 0 or (0 <= fwd_done[s - 1][m] < t))
                and (next_f[s] - next_b[s]) < (S - s)
            )
            mb = next_b[s]
            can_b = (
                mb < M
                and 0 <= fwd_done[s][mb] < t
                and (s == S - 1 or (0 <= bwd_done[s + 1][mb] < t))
            )
            f_row.append((m if can_f else 0, can_f))
            b_row.append((mb if can_b else 0, can_b))
        for s in range(S):
            m, on = f_row[s]
            if on:
                fwd_done[s][m] = t
                next_f[s] += 1
            m, on = b_row[s]
            if on:
                bwd_done[s][m] = t
                next_b[s] += 1
        rows_f.append(f_row)
        rows_b.append(b_row)
        t += 1
        if t > 4 * (M + S) + 8:
            raise AssertionError("1F1B schedule simulation did not converge")

    T = t

    def max_overlap(intervals):
        """Max number of [a, c] intervals alive at any tick."""
        best = 0
        for tick in range(T + 1):
            best = max(best, sum(1 for a, c in intervals if a <= tick <= c))
        return best

    depth = 1
    for s in range(S):
        # x arrival (prev stage's fwd) .. consumed by this stage's bwd
        arr = [
            ((fwd_done[s - 1][m] if s else fwd_done[s][m]), bwd_done[s][m])
            for m in range(M)
        ]
        # dy arrival (next stage's bwd) .. consumed by this stage's bwd
        dy = (
            [(bwd_done[s + 1][m], bwd_done[s][m]) for m in range(M)]
            if s < S - 1
            else []
        )
        # saved x_in: written at this stage's fwd .. read at its bwd
        sav = [(fwd_done[s][m], bwd_done[s][m]) for m in range(M)]
        depth = max(depth, max_overlap(arr), max_overlap(dy), max_overlap(sav))

    f_mb = np.array([[r[s][0] for s in range(S)] for r in rows_f], np.int32)
    f_on = np.array([[r[s][1] for s in range(S)] for r in rows_f], bool)
    b_mb = np.array([[r[s][0] for s in range(S)] for r in rows_b], np.int32)
    b_on = np.array([[r[s][1] for s in range(S)] for r in rows_b], bool)
    return f_mb, f_on, b_mb, b_on, depth


def _schedule(n_micro: int, n_stages: int):
    """Static GPipe tick schedule: (feed index, feed mask, emit index,
    emit mask).

    Tick ``t``: stage 0 ingests microbatch ``t`` (clipped — the index stays
    in range during drain ticks, but the feed mask goes false there so the
    embed cond is skipped entirely rather than recomputed and discarded),
    the last stage finishes microbatch ``t - (S-1)``; its loss only counts
    once ``t`` has passed the fill bubble.
    """
    ticks = np.arange(n_micro + n_stages - 1)
    feed_idx = np.clip(ticks, 0, n_micro - 1)
    feed_valid = ticks < n_micro
    emit_idx = np.clip(ticks - (n_stages - 1), 0, n_micro - 1)
    emit_valid = ticks >= n_stages - 1
    return (
        jnp.asarray(feed_idx, jnp.int32),
        jnp.asarray(feed_valid),
        jnp.asarray(emit_idx, jnp.int32),
        jnp.asarray(emit_valid),
    )


def build_pp_lm_train_step(
    model,
    optimizer,
    lr_fn: Callable,
    mesh: Mesh,
    num_microbatches: int,
    donate: bool = True,
    label_smoothing: float = 0.0,
    schedule: str = "gpipe",
    seq_axis=None,
    zero: int = 0,
):
    """Compile one DP x PP (optionally x TP) LM iteration.

    ``model``: a :class:`TransformerLM` (``seq_axis=None``); its params must
    be in the pipeline layout (:func:`..parallel.pipeline.pp_stack_params`).
    The optimizer must be elementwise per-leaf (SGD / AdamW — LARS computes
    per-parameter norms, which would span the stacked layer axis and change
    semantics; the Runner rejects that combination).

    ``schedule``:
      - ``"gpipe"``: forward scan differentiated by autodiff (module
        docstring) — activation residuals for all M+S-1 ticks stay live
        through the backward, O(M) microbatch activations per stage.
      - ``"1f1b"``: manual interleaved schedule (:func:`_sim_1f1b`) with a
        hand-written backward: each tick runs one masked forward slot and
        one masked backward slot; the backward slot re-runs its stage's
        forward under ``jax.vjp`` at the saved stage INPUT (recompute —
        only O(S) microbatch inputs are ever buffered, 1F1B's memory
        property) and pulls the activation cotangent backwards along the
        reverse ring.  Same update math as gpipe to float tolerance
        (tests/test_pipeline_parallel.py pins both against the single-chip
        oracle).

    If ``mesh`` also carries a ``model`` axis (size > 1), the step runs
    shard_map-manual over (data, stage) only and leaves ``model`` to the
    GSPMD partitioner — Megatron tensor parallelism INSIDE each pipeline
    stage, from the same sharding rules as the pure-TP path
    (parallel/tensor.py); see :func:`..parallel.pipeline.pp_tp_state_shardings`.

    Returns ``compile_for(state)`` pinning the state's stage shardings,
    mirroring :func:`..engine.tp_steps.build_tp_lm_train_step`.
    """
    n_stages = mesh.shape[STAGE_AXIS]
    n_data = mesh.shape[DATA_AXIS]
    n_seq = mesh.shape[seq_axis] if seq_axis else 1
    loss_axes = (DATA_AXIS, STAGE_AXIS) + ((seq_axis,) if seq_axis else ())
    M = int(num_microbatches)
    if M < 1:
        raise ValueError(f"num_microbatches must be >= 1, got {M}")
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    embed, apply_blocks, apply_head = _stage_applies(model, seq_axis)
    feed_idx, feed_valid, emit_idx, emit_valid = _schedule(M, n_stages)

    def grads_gpipe(params, tokens, labels):
        b_local, seq = tokens.shape
        if b_local % M != 0:
            raise ValueError(
                f"per-shard batch {b_local} not divisible by "
                f"num_microbatches {M}"
            )
        mb = b_local // M
        if seq * n_seq > model.max_len:
            raise ValueError(
                f"global sequence {seq * n_seq} exceeds max_len {model.max_len}"
            )
        global_tokens = b_local * seq * n_data * n_seq
        stage = jax.lax.axis_index(STAGE_AXIS)
        tok = tokens.reshape(M, mb, seq)
        lab = labels.reshape(M, mb, seq)
        perm = [(s, (s + 1) % n_stages) for s in range(n_stages)]

        def loss_fn(p):
            # Shared params are promoted to stage-varying BEFORE the conds
            # below.  Without this, AD would place the stage-psum of their
            # cotangent inside the cond branch (only the predicate-true
            # stage executes it -> the other stages never join the
            # all-reduce: deadlock).  After the pcast, only data/seq
            # reductions remain inside the branches — safe, because every
            # peer along those axes shares the same stage coordinate and
            # takes the same branch — and the stage-psum runs once at the
            # pcast transpose, outside the scan entirely.
            shared = mark_varying(p["shared"], (STAGE_AXIS,))

            def tick(carry, xs):
                x, loss_acc = carry
                f_i, f_valid, e_i, valid = xs
                is_last = stage == n_stages - 1
                # embed only on stage 0's feed ticks, head+loss only on the
                # last stage's valid ticks: lax.cond with a device-varying
                # predicate SKIPS the untaken branch at runtime, so interior
                # stages run blocks only — the per-tick critical path drops
                # from embed+blocks+head on every stage (the round-4 ~40%
                # duplication) to max(embed+blocks, blocks+head).  Folding
                # feed validity in drops the S-1 drain-tick embeds whose
                # output the clipped re-feed previously computed and threw
                # away (their loss contribution was already masked, so
                # gradients are unchanged).
                x_in = jax.lax.cond(
                    (stage == 0) & f_valid,
                    lambda: mark_varying(embed(shared, tok[f_i]), loss_axes),
                    lambda: x,
                )
                y = apply_blocks(p["blocks"], x_in)

                def head_loss():
                    logits = apply_head(shared, y)
                    return mark_varying(
                        lm_loss_local(
                            logits, lab[e_i], global_tokens, label_smoothing
                        ),
                        loss_axes,
                    )

                part = jax.lax.cond(
                    valid & is_last,
                    head_loss,
                    lambda: mark_varying(jnp.float32(0.0), loss_axes),
                )
                loss_acc = loss_acc + part
                x_next = jax.lax.ppermute(y, STAGE_AXIS, perm)
                return (x_next, loss_acc), None

            # the carry is device-varying (each stage holds a different
            # activation), so the constant initial carry must be promoted
            x0, l0 = mark_varying(
                (jnp.zeros((mb, seq, model.embed_dim), model.dtype),
                 jnp.float32(0.0)),
                loss_axes,
            )
            (_, loss_sum), _ = jax.lax.scan(
                tick, (x0, l0), (feed_idx, feed_valid, emit_idx, emit_valid)
            )
            # global mean CE as a replicated scalar: only the last stage
            # holds nonzero partials, the psum both totals them over data
            # (and sequence, under PP x SP) and broadcasts over stage —
            # differentiating THIS is what makes the pipeline backward
            # exact (module docstring)
            return jax.lax.psum(loss_sum, loss_axes)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return grads, loss

    def grads_1f1b(params, tokens, labels):
        b_local, seq = tokens.shape
        if b_local % M != 0:
            raise ValueError(
                f"per-shard batch {b_local} not divisible by "
                f"num_microbatches {M}"
            )
        mb = b_local // M
        if seq * n_seq > model.max_len:
            raise ValueError(
                f"global sequence {seq * n_seq} exceeds max_len {model.max_len}"
            )
        global_tokens = b_local * seq * n_data * n_seq
        stage = jax.lax.axis_index(STAGE_AXIS)
        is_last = stage == n_stages - 1
        tok = tokens.reshape(M, mb, seq)
        lab = labels.reshape(M, mb, seq)
        perm_f = [(s, (s + 1) % n_stages) for s in range(n_stages)]
        perm_b = [(s, (s - 1) % n_stages) for s in range(n_stages)]

        f_mb, f_on, b_mb, b_on, W = _sim_1f1b(M, n_stages)
        # receive-side schedules: what arrives THIS tick is whatever the
        # neighbor's slot ran this tick (the ppermute happens in-tick);
        # stage 0 never receives activations, the last never receives dy
        fr_mb = np.roll(f_mb, 1, axis=1)
        fr_on = np.roll(f_on, 1, axis=1)
        fr_on[:, 0] = False
        br_mb = np.roll(b_mb, -1, axis=1)
        br_on = np.roll(b_on, -1, axis=1)
        br_on[:, -1] = False
        sched = jax.tree.map(
            jnp.asarray, (f_mb, f_on, b_mb, b_on, fr_mb, fr_on, br_mb, br_on)
        )

        def stage_fn(p, tok_mb, lab_mb, x_recv):
            # same cond-gating construction as grads_gpipe (see the comment
            # there): shared params pcast to stage-varying FIRST so the
            # AD-inserted stage-psum of their cotangent runs at the pcast
            # transpose (every tick, all stages — the per-tick vjp below
            # differentiates this whole function) instead of inside a
            # branch only one stage takes
            shared = mark_varying(p["shared"], (STAGE_AXIS,))
            x_in = jax.lax.cond(
                stage == 0,
                lambda: mark_varying(embed(shared, tok_mb), loss_axes),
                lambda: x_recv,
            )
            y = apply_blocks(p["blocks"], x_in)

            def head_loss():
                logits = apply_head(shared, y)
                return mark_varying(
                    lm_loss_local(
                        logits, lab_mb, global_tokens, label_smoothing
                    ),
                    loss_axes,
                )

            part = jax.lax.cond(
                is_last,
                head_loss,
                lambda: mark_varying(jnp.float32(0.0), loss_axes),
            )
            return y, part

        def sel(row):
            return jnp.take(row, stage, axis=0)

        def tick(carry, xs):
            x_buf, dy_buf, x_saved, gacc, loss_acc = carry
            fm, fo, bm, bo, frm, fro, brm, bro = (sel(r) for r in xs)

            # ---- forward slot (masked by fo) ----
            x_in = x_buf[fm % W]
            x_saved = jnp.where(fo, x_saved.at[fm % W].set(x_in), x_saved)
            y, lo = stage_fn(params, tok[fm], lab[fm], x_in)
            loss_acc = loss_acc + jnp.where(fo & is_last, lo, 0.0)
            y_recv = jax.lax.ppermute(y, STAGE_AXIS, perm_f)
            x_buf = jnp.where(fro, x_buf.at[frm % W].set(y_recv), x_buf)

            # ---- backward slot (masked by bo): recompute-vjp at the saved
            # stage input, seed (dy from the next stage, dloss = 1).
            # MASKING GOES INTO THE SEEDS, not onto dp: shard_map AD psums
            # the cotangent of any mesh-invariant primal (shared params are
            # (data, stage)-invariant, block params data-invariant), so dp
            # comes back ALREADY reduced across devices each tick — an
            # after-the-fact `where(bo, dp, 0)` would keep other stages'
            # garbage and re-psumming would double-count.  A zero seed on an
            # inactive stage zeroes its contribution inside the transpose,
            # which is exactly the per-stage mask.
            xs_in = x_saved[bm % W]
            dy_in = jnp.where(
                is_last | ~bo, jnp.zeros_like(xs_in), dy_buf[bm % W]
            )
            _, vjp_fn = jax.vjp(
                lambda p_, xr: stage_fn(p_, tok[bm], lab[bm], xr), params, xs_in
            )
            cts = mark_varying(
                (
                    dy_in.astype(model.dtype),
                    jnp.where(bo, jnp.float32(1.0), jnp.float32(0.0)),
                ),
                loss_axes,
            )
            dp, dx = vjp_fn(cts)
            gacc = jax.tree.map(jnp.add, gacc, dp)
            dx_recv = jax.lax.ppermute(dx, STAGE_AXIS, perm_b)
            dy_buf = jnp.where(bro, dy_buf.at[brm % W].set(dx_recv), dy_buf)
            return (x_buf, dy_buf, x_saved, gacc, loss_acc), None

        act = (W, mb, seq, model.embed_dim)
        # gacc's vma must mirror what the vjp hands back (see seed-masking
        # comment): block grads come back data-psummed (varying over stage
        # only), shared grads fully reduced (invariant) — the activation
        # buffers and the loss are genuinely per-device
        gacc0 = {
            "blocks": mark_varying(
                jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params["blocks"]
                ),
                (STAGE_AXIS,),
            ),
            "shared": jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params["shared"]
            ),
        }
        carry0 = (
            *mark_varying(
                (
                    jnp.zeros(act, model.dtype),
                    jnp.zeros(act, model.dtype),
                    jnp.zeros(act, model.dtype),
                ),
                loss_axes,
            ),
            gacc0,
            mark_varying(jnp.float32(0.0), loss_axes),
        )
        (_, _, _, gacc, loss_sum), _ = jax.lax.scan(tick, carry0, sched)

        # no explicit grad collectives: the per-tick vjp transpose already
        # psummed each cotangent to its primal's invariance (blocks over
        # data, shared over data AND stage — see the seed-masking comment),
        # so gacc IS the fully-reduced gradient after the scan
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), gacc, params)
        loss = jax.lax.psum(loss_sum, loss_axes)
        return grads, loss

    grads_fn = grads_gpipe if schedule == "gpipe" else grads_1f1b

    def step_body(params, opt_state, tokens, labels):
        grads, loss = grads_fn(params, tokens, labels)
        lr = lr_fn(opt_state.step)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state, params, lr)
        return new_params, new_opt, loss

    def compile_for(state: TrainState):
        param_spec = pp_param_specs(state.params)
        opt_spec = _opt_specs(state, param_spec)
        tok_spec = P(DATA_AXIS, seq_axis) if seq_axis else P(DATA_AXIS, None)
        # PP x TP: leave the 'model' axis to the GSPMD partitioner (manual
        # over data/stage only) — Megatron splits inside each stage, from
        # the sharded params' own NamedShardings
        manual = {}
        if MODEL_AXIS in mesh.axis_names and mesh.shape[MODEL_AXIS] > 1:
            manual = dict(axis_names=frozenset({DATA_AXIS, STAGE_AXIS}))
        if zero:
            # ZeRO x PP: only the GRADIENT computation runs in the
            # manual shard_map (data-sharded moments must not enter it —
            # the manual in_specs would gather them, defeating the
            # sharding).  The elementwise update runs outside under GSPMD:
            # the data-sharded moment shardings (pp_state_shardings
            # zero=True) make the partitioner reduce-scatter the grads
            # into the moment update and gather the fresh stage-sharded
            # params — the same construction as the GSPMD TP ZeRO path.
            # Stage 2 additionally pins the grads themselves to the moment
            # layout right at the shard_map boundary, so each device holds
            # a 1/N_data gradient slice instead of the data-replicated
            # stage-sharded tree (the PP analog of tp_steps' shard_grads).
            sharded_grads = jax.shard_map(
                grads_fn,
                mesh=mesh,
                in_specs=(param_spec, tok_spec, tok_spec),
                out_specs=(param_spec, P()),
                **manual,
            )
            param_sh = jax.tree.map(lambda x: x.sharding, state.params)
            moment_sh = None
            if int(zero) >= 2:
                from ..parallel.tensor import param_mirror_fields

                mirrors = param_mirror_fields(state.opt_state, state.params)
                if mirrors:
                    moment_sh = jax.tree.map(
                        lambda x: x.sharding,
                        getattr(state.opt_state, mirrors[0]),
                    )
                    # the grad pin below assumes ONE moment layout; ZeRO
                    # sharding applies uniformly to every params-mirroring
                    # field (parallel/zero.py), so any disagreement means
                    # the opt state was built inconsistently — fail loudly
                    # here rather than pin grads to the wrong layout
                    for m in mirrors[1:]:
                        other = jax.tree.map(
                            lambda x: x.sharding, getattr(state.opt_state, m)
                        )
                        if other != moment_sh:
                            raise ValueError(
                                f"ZeRO-2 x PP: opt-state field {m!r} is laid"
                                f" out differently from {mirrors[0]!r}; all"
                                " params-mirroring moment fields must share"
                                " one ZeRO shard layout"
                            )

            def step(state: TrainState, tokens, labels):
                grads, loss = sharded_grads(state.params, tokens, labels)
                if moment_sh is not None:
                    grads = jax.lax.with_sharding_constraint(grads, moment_sh)
                lr = lr_fn(state.opt_state.step)
                with jax.named_scope("optimizer"):
                    new_params, new_opt = optimizer.update(
                        grads, state.opt_state, state.params, lr
                    )
                new_params = jax.lax.with_sharding_constraint(
                    new_params, param_sh
                )
                return (
                    TrainState(
                        params=new_params, batch_stats=state.batch_stats,
                        opt_state=new_opt, ema=state.ema,
                    ),
                    loss,
                )

            return register_compiled(
                "lm_train_step/pp_gspmd",
                jax.jit(step, donate_argnums=(0,) if donate else ()),
            )

        sharded = jax.shard_map(
            step_body,
            mesh=mesh,
            in_specs=(param_spec, opt_spec, tok_spec, tok_spec),
            out_specs=(param_spec, opt_spec, P()),
            **manual,
        )

        def step(state: TrainState, tokens, labels):
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

        return register_compiled(
            "lm_train_step/pp",
            jax.jit(step, donate_argnums=(0,) if donate else ()),
        )

    return compile_for


def _opt_specs(state: TrainState, param_spec):
    """Spec pytree for the optimizer state: params-shaped moment fields
    mirror the param specs, scalars replicate."""
    return mirror_opt_fields(state.opt_state, state.params, param_spec, P())


def build_pp_lm_eval_step(model, mesh: Mesh, num_microbatches: int, seq_axis=None):
    """Compile the DP x PP LM validation step.

    Same replicated ``(loss, acc1, acc5)`` contract as every other eval step
    (mean CE per token + next-token top-1/top-5), so ``Runner.validate``
    drives it unchanged.  Runs the same microbatch schedule forward-only.
    """
    import math

    n_stages = mesh.shape[STAGE_AXIS]
    n_data = mesh.shape[DATA_AXIS]
    n_seq = mesh.shape[seq_axis] if seq_axis else 1
    red_axes = (DATA_AXIS, STAGE_AXIS) + ((seq_axis,) if seq_axis else ())
    M_cfg = int(num_microbatches)
    embed, apply_blocks, apply_head = _stage_applies(model, seq_axis)

    def body(params, tokens, labels):
        b_local, seq = tokens.shape
        # the val loader keeps its ragged tail batch (drop_last=False,
        # reference :219-222), so unlike the train step this must accept
        # any per-shard batch: fall back to the largest microbatch count
        # that divides it (a tail batch recompiles anyway — new shape)
        M = math.gcd(M_cfg, b_local)
        if M != M_cfg:
            # a tail batch coprime with M_cfg degenerates to M=1 (one
            # whole-batch microbatch: an activation-memory spike and a
            # fully serial pipeline tick pattern) — surface it (trace-time,
            # once per distinct tail shape; round-2 ADVICE)
            import logging

            logging.getLogger(__name__).warning(
                "pp eval: per-shard tail batch %d not divisible by "
                "microbatches %d; falling back to M=%d for this batch",
                b_local, M_cfg, M,
            )
        feed_idx, feed_valid, emit_idx, emit_valid = _schedule(M, n_stages)
        mb = b_local // M
        if seq * n_seq > model.max_len:
            # same guard as the train bodies: beyond the table,
            # dynamic_slice would CLAMP and silently reuse position rows
            raise ValueError(
                f"global sequence {seq * n_seq} exceeds max_len {model.max_len}"
            )
        global_tokens = b_local * seq * n_data * n_seq
        stage = jax.lax.axis_index(STAGE_AXIS)
        tok = tokens.reshape(M, mb, seq)
        lab = labels.reshape(M, mb, seq)
        perm = [(s, (s + 1) % n_stages) for s in range(n_stages)]

        def tick(carry, xs):
            x, loss_acc, c1, c5 = carry
            f_i, f_valid, e_i, valid = xs
            # same stage-gating as the train step (module docstring):
            # forward-only, so no cotangent-psum hazard — plain conds
            x_in = jax.lax.cond(
                (stage == 0) & f_valid,
                lambda: mark_varying(embed(params["shared"], tok[f_i]), red_axes),
                lambda: x,
            )
            y = apply_blocks(params["blocks"], x_in)

            def head_metrics():
                logits = apply_head(params["shared"], y)
                part = lm_loss_local(logits, lab[e_i], global_tokens)
                flat = logits.reshape(-1, logits.shape[-1])
                flab = lab[e_i].reshape(-1)
                top5 = jax.lax.top_k(flat, 5)[1]
                hit1 = jnp.sum(top5[:, 0] == flab)
                hit5 = jnp.sum(jnp.any(top5 == flab[:, None], axis=1))
                return mark_varying((part, hit1, hit5), red_axes)

            emit_mask = valid & (stage == n_stages - 1)
            part, hit1, hit5 = jax.lax.cond(
                emit_mask,
                head_metrics,
                lambda: mark_varying(
                    (jnp.float32(0.0), jnp.int32(0), jnp.int32(0)), red_axes
                ),
            )
            loss_acc = loss_acc + part
            c1 = c1 + hit1
            c5 = c5 + hit5
            x_next = jax.lax.ppermute(y, STAGE_AXIS, perm)
            return (x_next, loss_acc, c1, c5), None

        carry0 = mark_varying(
            (jnp.zeros((mb, seq, model.embed_dim), model.dtype),
             jnp.float32(0.0), jnp.int32(0), jnp.int32(0)),
            red_axes,
        )
        (_, loss_sum, c1, c5), _ = jax.lax.scan(
            tick, carry0, (feed_idx, feed_valid, emit_idx, emit_valid)
        )
        axes = red_axes
        loss = jax.lax.psum(loss_sum, axes)
        total = jnp.float32(global_tokens)
        acc1 = jax.lax.psum(c1, axes).astype(jnp.float32) / total * 100.0
        acc5 = jax.lax.psum(c5, axes).astype(jnp.float32) / total * 100.0
        return loss, acc1, acc5

    def compile_for(state: TrainState):
        param_spec = pp_param_specs(state.params)
        tok_spec = P(DATA_AXIS, seq_axis) if seq_axis else P(DATA_AXIS, None)
        manual = {}
        if MODEL_AXIS in mesh.axis_names and mesh.shape[MODEL_AXIS] > 1:
            manual = dict(axis_names=frozenset({DATA_AXIS, STAGE_AXIS}))
        sharded = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(param_spec, tok_spec, tok_spec),
            out_specs=(P(), P(), P()),
            **manual,
        )

        @jax.jit
        def eval_step(state: TrainState, tokens, labels):
            return sharded(state.params, tokens, labels)

        return eval_step

    return compile_for
