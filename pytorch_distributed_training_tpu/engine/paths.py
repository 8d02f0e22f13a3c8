"""Execution-path strategy table: predicate -> mesh/state/step builders.

Extracted from ``Runner.worker``'s four-way if-ladder (round-3 VERDICT
weak #5).  Each path is DATA — a ``PathSpec(name, predicate, build)`` row —
selected by the first matching predicate, so adding a path is one row
plus one builder, not another elif with cross-constraints.

Every builder sets on the Runner: ``mesh``, ``state`` (device_put with the
path's shardings), ``train_step``, ``eval_step``, ``_img_sharding``,
``_label_sharding``.  The config validation feeding the predicates lives in
:mod:`.topology`; behavior and error messages are unchanged from the
pre-extraction Runner (pinned by tests/test_composition_matrix.py).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import (
    DATA_AXIS,
    batch_sharding,
    make_mesh,
    make_sp_mesh,
    replicated_sharding,
)
from ..parallel.sequence import SEQUENCE_AXIS
from .sp_steps import build_lm_eval_step, build_lm_train_step
from .steps import TrainState, build_eval_step, build_train_step, init_train_state

__all__ = ["PathSpec", "PATHS", "select_path"]


class PathSpec(NamedTuple):
    name: str
    predicate: Callable  # Runner -> bool
    build: Callable  # (Runner, seed, train_dataset) -> None


def _anomaly_factor(r):
    """The ``anomaly_factor`` to hand a step builder: the configured factor
    when the guard is on, ``None`` (exact ungated program) otherwise."""
    return r.anomaly_factor if getattr(r, "anomaly_enabled", False) else None


def _reject_anomaly(r, path: str):
    if getattr(r, "anomaly_enabled", False):
        raise ValueError(
            "training.fault_tolerance.anomaly is not wired for the "
            f"{path} execution path (supported: image-dp, ring-sp)"
        )


def _token_shardings(r, mesh, seq_axis):
    """Tokens/targets are [batch, seq]: data axis on rows, the path's
    sequence axis (or None) on columns — same for inputs and labels."""
    tok = NamedSharding(mesh, P(DATA_AXIS, seq_axis))
    r._img_sharding = tok
    r._label_sharding = tok


def _build_pipeline(r, seed, train_dataset):
    # (data, stage) mesh, microbatch schedule as one shard_map program
    # (parallel/pipeline.py, engine/pp_steps.py): decoder blocks stack into
    # a leading layer axis sharded over stage, activations rotate
    # stage-to-stage via ppermute each tick.
    from ..optimizers import LARS
    from ..parallel import make_pp_mesh, pp_stack_params, pp_state_shardings
    from .pp_steps import build_pp_lm_eval_step, build_pp_lm_train_step

    _reject_anomaly(r, "pipeline")
    if r.model.depth % r.pipe_par != 0:
        raise ValueError(
            f"model.depth ({r.model.depth}) must be divisible by "
            f"training.pipeline_parallelism ({r.pipe_par})"
        )
    if isinstance(r.optimizer, LARS):
        # LARS takes per-parameter norms; on the stacked layer axis
        # those would span a whole stage's layers — different math
        raise ValueError(
            "optimizer LARS is not supported with pipeline_parallelism "
            "(per-parameter trust ratios do not survive the stacked-layer "
            "param layout)"
        )
    if r.tensor_par > 1 and r.model.num_heads % r.tensor_par:
        # same whole-head Megatron split constraint as the TP path
        raise ValueError(
            f"model.num_heads ({r.model.num_heads}) must be divisible by "
            f"training.tensor_parallelism ({r.tensor_par})"
        )
    r.mesh = make_pp_mesh(r.pipe_par, r.tensor_par, r.seq_par)
    pp_seq_axis = SEQUENCE_AXIS if r.seq_par > 1 else None
    sample = jnp.zeros((1, r.seq_len), jnp.int32)
    params = r.model.init(jax.random.PRNGKey(seed), sample)["params"]
    if r.pretrained:
        params = r._apply_pretrained_lm(params)
    pp_params = pp_stack_params(params, r.model.depth)
    state = TrainState(
        params=pp_params, batch_stats={}, opt_state=r.optimizer.init(pp_params)
    )
    r.state = jax.device_put(
        state, pp_state_shardings(state, r.mesh, zero=r.zero)
    )
    r.train_step = build_pp_lm_train_step(
        r.model, r.optimizer, r.scheduler.lr_fn, r.mesh,
        num_microbatches=r.microbatches,
        label_smoothing=r.label_smoothing,
        schedule=r.pp_schedule,
        seq_axis=pp_seq_axis,
        zero=r.zero,
    )(r.state)
    r.eval_step = build_pp_lm_eval_step(
        r.model, r.mesh, r.microbatches, seq_axis=pp_seq_axis
    )(r.state)
    _token_shardings(r, r.mesh, pp_seq_axis)


def _build_gspmd(r, seed, train_dataset):
    # (data, sequence, model) mesh, GSPMD Megatron sharding
    # (parallel/tensor): params live sharded over the model axis; XLA
    # inserts the row-parallel all-reduces, the gradient all-reduce, and —
    # when sequence_parallelism > 1 — the sequence resharding around
    # attention.  ``training.zero`` shards optimizer moments over the data
    # axis (stage >= 1) and gradient buffers (stage 2), and selects this
    # GSPMD path even at tensor_par == 1.  MoE models (``model.moe_experts``)
    # also land here: expert weights shard over the model axis (expert
    # parallelism) and the train step folds the sown aux loss into the
    # objective.
    from ..parallel import make_3d_mesh
    from ..parallel.tensor import tp_state_shardings
    from .tp_steps import build_tp_lm_eval_step, build_tp_lm_train_step

    _reject_anomaly(r, "gspmd")
    if r.model.num_heads % r.tensor_par != 0:
        # the Megatron column split lands on whole-head boundaries
        raise ValueError(
            f"model.num_heads ({r.model.num_heads}) must be divisible by "
            f"training.tensor_parallelism ({r.tensor_par})"
        )
    r.mesh = make_3d_mesh(r.seq_par, r.tensor_par)
    sample = jnp.zeros((1, r.seq_len), jnp.int32)
    params = r.model.init(jax.random.PRNGKey(seed), sample)["params"]
    if r.pretrained:
        params = r._apply_pretrained_lm(params)
    state = TrainState(
        params=params, batch_stats={}, opt_state=r.optimizer.init(params)
    )
    r.state = jax.device_put(
        state, tp_state_shardings(state, r.mesh, zero=r.zero)
    )
    r.train_step = build_tp_lm_train_step(
        r.model, r.optimizer, r.scheduler.lr_fn, r.mesh,
        label_smoothing=r.label_smoothing, zero=r.zero,
        grad_accum=r.grad_accum,
    )(r.state)
    r.eval_step = build_tp_lm_eval_step(r.model, r.mesh, zero=r.zero)(r.state)
    _token_shardings(r, r.mesh, SEQUENCE_AXIS)


def _build_ring_sp(r, seed, train_dataset):
    # (data, sequence) mesh; with sequence_parallelism == 1 the sequence
    # axis is trivial and this is plain DP over tokens.  seq_par > 1 runs
    # shard_map ring attention (memory-optimal for long context).
    r.mesh = make_sp_mesh(r.seq_par)
    sample = jnp.zeros((1, r.seq_len), jnp.int32)
    params = r.model.init(jax.random.PRNGKey(seed), sample)["params"]
    if r.pretrained:
        params = r._apply_pretrained_lm(params)
    state = TrainState(
        params=params, batch_stats={}, opt_state=r.optimizer.init(params)
    )
    r.state = jax.device_put(state, replicated_sharding(r.mesh))
    r.train_step = build_lm_train_step(
        r.model, r.optimizer, r.scheduler.lr_fn, r.mesh,
        grad_accum=r.grad_accum,
        label_smoothing=r.label_smoothing,
        anomaly_factor=_anomaly_factor(r),
    )
    r.eval_step = build_lm_eval_step(r.model, r.mesh)
    _token_shardings(r, r.mesh, SEQUENCE_AXIS)


def _build_image_dp(r, seed, train_dataset):
    # 1-D batch mesh, the whole reference iteration as one jitted shard_map
    # program (engine/steps.py): forward, CE, backward, grad psum, SyncBN
    # stats pmean, SGD update.
    r.mesh = make_mesh()
    sample_img, _ = train_dataset[0]
    sample = jnp.zeros((1,) + tuple(sample_img.shape), jnp.float32)
    state = init_train_state(
        r.model, r.optimizer, jax.random.PRNGKey(seed), sample
    )
    if r.pretrained:
        # before the EMA copy below, so the average starts from the
        # pretrained weights too
        state = r._apply_pretrained_image(state)
    if r.ema_decay is not None:
        # EMA starts at the initial weights (standard convention).
        # jnp.copy: ema must NOT alias the params buffers — the donated
        # train step would otherwise donate them twice
        state = state.replace(ema=jax.tree.map(jnp.copy, state.params))
    r.state = jax.device_put(state, replicated_sharding(r.mesh))
    r.train_step = build_train_step(
        r.model, r.optimizer, r.scheduler.lr_fn, r.mesh,
        sync_bn=r.sync_bn,
        input_norm=r._input_norm,
        grad_accum=r.grad_accum,
        label_smoothing=r.label_smoothing,
        ema_decay=r.ema_decay,
        anomaly_factor=_anomaly_factor(r),
    )
    r.eval_step = build_eval_step(r.model, r.mesh, input_norm=r._input_norm)
    r._img_sharding = batch_sharding(r.mesh, ndim=4)
    r._label_sharding = batch_sharding(r.mesh, ndim=1)


PATHS = (
    PathSpec("pipeline", lambda r: r.is_lm and r.pipe_par > 1, _build_pipeline),
    PathSpec(
        "gspmd",
        lambda r: r.is_lm and (r.tensor_par > 1 or r.zero or r.is_moe),
        _build_gspmd,
    ),
    PathSpec("ring-sp", lambda r: r.is_lm, _build_ring_sp),
    PathSpec("image-dp", lambda r: True, _build_image_dp),
)


def select_path(r) -> PathSpec:
    """First matching row of :data:`PATHS` (the last row always matches)."""
    return next(spec for spec in PATHS if spec.predicate(r))
