"""The decode step of a layer whose cache is a ``[slots, ...]`` STATE leaf
(:mod:`.kda`, :mod:`.mamba2`; ``STATE_LEAVES`` of :mod:`.attention`): the
live rows only, each updated where it lies.

The scheduler's decode step is as wide as the slots, and below saturation
few of them hold a sequence (2.6 of 32 in ``solar-open2-250b.serve.long32``,
PERF.md).  A one-row recurrence applied to the whole leaf and then
``where(live, new, old)`` is one pass over every slot's state whatever
lives: 134 MB read and 134 MB written a layer at the published widths.
:func:`step_live_rows` walks the live rows instead, one trip of a
``while`` a row, and a row that is not live is neither read nor written.

The walk is serial, 24-27 us a 4.19 MB row on a TPU v5e where the pass over
all 32 slots takes 0.42-0.60 ms (PERF.md section 5, PR 42: the two cross at
17 and 22 live rows of 32), so past ``WALK_SHARE`` of the slots live the
step takes the dense pass: the program adapts on the one thing it can see,
the count of live rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["step_live_rows"]

# the largest share of the slots live at which the rows are walked
WALK_SHARE = 0.5


def step_live_rows(step_fn, state, live, old, row_inputs):
    """``step_fn(*row_inputs, state) -> (out, state)``, a one-position
    recurrence over rows (:func:`.kda.delta_rule_step`,
    :func:`.mamba2.ssd_step`), applied to the rows of ``state [slots, ...]``
    that ``live [slots]`` names.  Each of ``row_inputs`` is ``[slots, ...]``;
    a live row where ``old [slots]`` is false starts from a zero state.
    Returns ``(out [slots, ...], state)``: a row that is not live keeps its
    state bit for bit and its output is zeros.

    Both arms are loops whose trip count is a value of the program (the
    number of live rows; zero or one), so each lowers to a ``while`` that
    carries the leaf and updates it in place: a caller that donates
    ``state`` gets it back in the same buffer.  A ``lax.cond`` between the
    two arms does not: the TPU compiler (jax 0.9.0) then copies the leaf,
    and inside the walk's body at that."""
    slots = state.shape[0]
    n_live = jnp.sum(live, dtype=jnp.int32)
    walk = n_live <= int(slots * WALK_SHARE)
    # the live rows' indices first; what follows them is never visited
    (rows,) = jnp.nonzero(live, size=slots, fill_value=0)

    def one(a, row):
        return lax.dynamic_slice_in_dim(a, row, 1, axis=0)

    def over(a, mask):  # a mask of rows against an array of rows
        return mask.reshape(mask.shape + (1,) * (a.ndim - 1))

    def step_row(row, state):
        start = jnp.where(over(state, one(old, row)), one(state, row), 0)
        return step_fn(*(one(a, row) for a in row_inputs), start)

    def one_row(i, carried):
        state, out = carried
        row = rows[i]
        out_row, state_row = step_row(row, state)
        return (lax.dynamic_update_slice_in_dim(state, state_row, row, axis=0),
                lax.dynamic_update_slice_in_dim(out, out_row, row, axis=0))

    def all_rows(_, carried):
        state, _ = carried
        out, new = step_fn(*row_inputs, jnp.where(over(state, old), state, 0))
        return (jnp.where(over(state, live), new, state),
                jnp.where(over(out, live), out, 0))

    out_row, _ = jax.eval_shape(step_row, rows[0], state)
    out = jnp.zeros((slots,) + out_row.shape[1:], out_row.dtype)
    state, out = lax.fori_loop(
        0, jnp.where(walk, n_live, 0), one_row, (state, out))
    state, out = lax.fori_loop(
        0, jnp.where(walk, 0, 1), all_rows, (state, out))
    return out, state
