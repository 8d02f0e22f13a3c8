"""``ops/state_rows.py::step_live_rows`` against the dense arm it took the
place of in the aligned decode step of ``ops/kda.py`` and ``ops/mamba2.py``:
the one-row recurrence over EVERY slot's state, then ``where(live, new,
old)``.  Toy widths, float32 on the CPU: the two run the same arithmetic a
row, so live rows agree to rounding (1e-6 read, 1e-5 asked) and a row that
is not live is not touched at all.  Up to ``WALK_SHARE`` of the slots live
the rows are walked, past it the step is the dense pass again (a loop of
one trip): the masks below take both arms, and one case walks all rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.ops.kda import delta_rule_step
from pytorch_distributed_training_tpu.ops.mamba2 import ssd_step
from pytorch_distributed_training_tpu.ops import state_rows
from pytorch_distributed_training_tpu.ops.state_rows import step_live_rows

SLOTS = 8
MASKS = {
    "none": [],
    "one": [5],
    "scattered_half": [0, 3, 4, 7],
    "most": [0, 1, 2, 4, 6, 7],  # past WALK_SHARE: the dense arm
    "all": list(range(SLOTS)),
}


def kda_inputs(rng):
    h, d = 4, 16
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    k = normal(SLOTS, h, d)
    rows = (normal(SLOTS, h, d) * d ** -0.5,
            k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            normal(SLOTS, h, d),
            -0.3 * jax.nn.softplus(normal(SLOTS, h, d)),
            2 * jax.nn.sigmoid(normal(SLOTS, h)))
    return normal(SLOTS, h, d, d), rows


def mamba_inputs(rng):
    h, p, g, n = 8, 8, 2, 16
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(normal(SLOTS, h) - 2.0)
    rows = (normal(SLOTS, h, p), normal(SLOTS, g, n), normal(SLOTS, g, n),
            dt, -jnp.exp(normal(h)) * dt)
    return normal(SLOTS, h, p, n), rows


FAMILIES = {"kda": (delta_rule_step, kda_inputs), "mamba2": (ssd_step, mamba_inputs)}


def dense(step, state, live, old, rows):
    """The arm the helper replaced (``_layer`` of both layers before PR 42)."""
    state0 = jnp.where(old[:, None, None, None], state, 0.0)
    out, state1 = step(*rows, state0)
    return out, jnp.where(live[:, None, None, None], state1, state)


def masks(which, fresh=()):
    live = np.zeros(SLOTS, bool)
    live[MASKS[which]] = True
    old = np.ones(SLOTS, bool)
    old[list(fresh)] = False
    return live, old


@pytest.mark.parametrize("share", [state_rows.WALK_SHARE, 1.0])
@pytest.mark.parametrize("which", list(MASKS))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_step_is_the_dense_step_on_the_live_rows_and_touches_no_other(
        family, which, share, monkeypatch):
    monkeypatch.setattr(state_rows, "WALK_SHARE", share)  # 1.0: every mask walks
    step, make = FAMILIES[family]
    state, rows = make(np.random.default_rng(3))
    live, old = masks(which)
    want_out, want_state = dense(step, state, live, old, rows)
    out, after = jax.jit(step_live_rows, static_argnums=0)(step, state, live, old, rows)
    out, after = np.asarray(out), np.asarray(after)
    np.testing.assert_allclose(after[live], np.asarray(want_state)[live], atol=1e-5)
    np.testing.assert_allclose(out[live], np.asarray(want_out)[live], atol=1e-5)
    # a dead row: its state bit for bit, its output zeros (finite: the
    # serving programs' output guard sees nothing new)
    np.testing.assert_array_equal(after[~live], np.asarray(state)[~live])
    assert out.shape == want_out.shape and out.dtype == want_out.dtype
    assert (out[~live] == 0).all()


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_live_row_that_starts_a_sequence_reads_zeros_whatever_its_slot_held(family):
    step, make = FAMILIES[family]
    state, rows = make(np.random.default_rng(4))
    live, old = masks("scattered_half", fresh=[3, 5])  # 3 lives, 5 does not
    out, after = step_live_rows(step, state, live, old, rows)
    want_out, want_state = dense(step, state, live, old, rows)
    np.testing.assert_allclose(
        np.asarray(after)[live], np.asarray(want_state)[live], atol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want_out)[live], atol=1e-5)
    cleared = step_live_rows(step, state.at[3].set(0.0), live, old, rows)
    np.testing.assert_array_equal(np.asarray(after)[3], np.asarray(cleared[1])[3])
    np.testing.assert_array_equal(np.asarray(out)[3], np.asarray(cleared[0])[3])
    np.testing.assert_array_equal(np.asarray(after)[5], np.asarray(state)[5])


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_donated_leaf_is_updated_in_its_own_buffer(family):
    """What the engine's warm-up checks for ``pool_aliased_bytes``: the
    compiled step, its leaf donated, aliases the leaf's bytes (the
    ``while``'s carried leaf is written where it lies, no copy)."""
    step, make = FAMILIES[family]
    state, rows = make(np.random.default_rng(5))
    live, old = masks("one")
    compiled = jax.jit(
        lambda state, live, old, rows: step_live_rows(step, state, live, old, rows),
        donate_argnums=0).lower(state, live, old, rows).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= state.nbytes
