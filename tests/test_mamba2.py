"""The Mamba-2 mixer (ops/mamba2.py) against the benchmark's plain reference
(benchmark/reference/nemotron_h.py::mamba_layer, whose recurrence runs ONE
POSITION AT A TIME and shares no code with the program) at the toy size of
tests/nemotron_toy.py: 16 heads x 8 in 4 groups, a state of 16, chunks of 8.

TOLERANCE 1e-4 on outputs of magnitude about 1: both sides are float32 on
the CPU and differ in the order of their sums (a chunk's products against a
position at a time): 2e-6 was read, 1e-4 leaves fifty times that and is an
order and more below what a state kept in bfloat16 reads (5e-3), which a
test below holds it to.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from nemotron_toy import CONFIG, load_reference

from pytorch_distributed_training_tpu.ops.attention import MAMBA_CONV, MAMBA_STATE
from pytorch_distributed_training_tpu.ops.mamba2 import (
    Mamba2Mixer, ssd_chunked, ssd_step,
)

TOLERANCE = 1e-4
H, P, G, N, TAPS, CHUNK, DIM = 16, 8, 4, 16, 4, 8, 64
CH = H * P + 2 * G * N
SLOTS = 3


@pytest.fixture(scope="module")
def ref():
    return load_reference()


@pytest.fixture(scope="module")
def weights(ref):
    """(arch, the reference's layer 0 — an ``M`` —, the program's tree of it)."""
    params = jax.device_get(ref.make_params(7, ref.sizes_of(CONFIG)))
    tree = jax.tree.map(
        lambda a: jnp.asarray(a).astype(jnp.float32),
        ref.to_checkpoint_tree(params)["layer0"]["mamba"])
    return ref.arch_of(params), jax.tree.map(jnp.asarray, params["layers"][0]), tree


def mixer(**more):
    return Mamba2Mixer(num_heads=H, head_dim=P, n_groups=G, state_size=N,
                       conv_size=TAPS, chunk_size=CHUNK, **more)


def inputs(rows, length, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, length, DIM), jnp.float32)


def padded(lens, bucket):
    positions = np.full((len(lens), bucket), -1, np.int32)
    for i, n in enumerate(lens):
        positions[i, :n] = np.arange(n)
    return positions


def cache_of(variables):
    cache = variables["cache"]
    return np.asarray(cache[MAMBA_STATE]), np.asarray(cache[MAMBA_CONV])


def random_cache(seed=5):
    rng = np.random.default_rng(seed)
    return {MAMBA_STATE: jnp.asarray(rng.standard_normal((SLOTS, H, P, N)), jnp.float32),
            MAMBA_CONV: jnp.asarray(rng.standard_normal((SLOTS, TAPS - 1, CH)), jnp.float32)}


@pytest.mark.parametrize("length", [1, 8, 64, 150])
def test_chunked_scan_is_the_step_by_step_recurrence(length):
    """With a state carried IN: the chunked form over ``length`` positions
    equals ``length`` one-position updates, outputs and final state."""
    keys = jax.random.split(jax.random.PRNGKey(length), 6)
    b = 2
    x = jax.random.normal(keys[0], (b, length, H, P))
    b_in = jax.random.normal(keys[1], (b, length, G, N))
    c_out = jax.random.normal(keys[2], (b, length, G, N))
    dt = jax.nn.softplus(jax.random.normal(keys[3], (b, length, H)) - 2.0)
    log_decay = -jnp.exp(jax.random.normal(keys[4], (H,))) * dt
    state = jax.random.normal(keys[5], (b, H, P, N))
    outs, carried = [], state
    for t in range(length):
        out, carried = ssd_step(
            x[:, t], b_in[:, t], c_out[:, t], dt[:, t], log_decay[:, t], carried)
        outs.append(out)
    got, final = ssd_chunked(x, b_in, c_out, dt, log_decay, state, chunk=CHUNK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(outs, 1)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(final), np.asarray(carried), atol=2e-5)


@pytest.mark.parametrize("length", [5, 64, 150])
def test_the_layer_is_the_reference_s_position_at_a_time_recurrence(ref, weights, length):
    arch, layer, tree = weights
    x = inputs(1, length, seed=length)
    got = mixer().apply({"params": tree}, x)[0]
    want = ref.mamba_layer(x[0], layer, arch=arch)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOLERANCE)


def test_the_tolerance_fails_a_state_kept_in_bfloat16(ref, weights):
    arch, layer, _ = weights
    x = inputs(1, 150, seed=150)[0]
    sound = np.asarray(ref.mamba_layer(x, layer, arch=arch))
    rounded = np.asarray(ref.mamba_layer(x, layer, arch=arch, mode="bf16_state"))
    assert np.abs(rounded - sound).max() > 10 * TOLERANCE


def test_prefill_then_steps_through_the_slots_is_one_full_forward(ref, weights):
    """Two rows of unequal lengths, neither a multiple of the chunk, in ONE
    padded call into slots 2 and 0 whose leaves hold another sequence's
    leftovers; then six decode steps over all slots (row i is slot i, slot 1
    is padding) and a seventh that slot 2 takes alone: every output row is
    the reference's over the same inputs."""
    arch, layer, tree = weights
    lens, slots, bucket, steps = [37, 18], [2, 0], 40, 6
    rows = [inputs(1, n + steps + 1, seed=n)[0] for n in lens]
    want = [np.asarray(ref.mamba_layer(r, layer, arch=arch)) for r in rows]
    x = jnp.stack([jnp.pad(r[:n], ((0, bucket - n), (0, 0))) for r, n in zip(rows, lens)])
    layer_fn = mixer(decode=True, state_slots=SLOTS)
    variables = {"params": tree, "cache": random_cache()}
    out, changed = layer_fn.apply(
        variables, x, padded(lens, bucket), np.asarray(slots, np.int32), mutable=["cache"])
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(out[i, :n]), want[i][:n], atol=TOLERANCE)
    row_of_slot = {2: 0, 0: 1}
    for k in range(steps):
        x = jnp.zeros((SLOTS, 1, DIM))
        pos = np.full((SLOTS, 1), -1, np.int32)
        for slot, i in row_of_slot.items():
            x = x.at[slot, 0].set(rows[i][lens[i] + k])
            pos[slot, 0] = lens[i] + k
        state_rows = np.where(pos[:, 0] >= 0, np.arange(SLOTS), -1).astype(np.int32)
        out, changed = layer_fn.apply(
            {"params": tree, "cache": changed["cache"]}, x, pos, state_rows,
            rows_are_slots=True, mutable=["cache"])
        for slot, i in row_of_slot.items():
            np.testing.assert_allclose(
                np.asarray(out[slot, 0]), want[i][lens[i] + k], atol=TOLERANCE)
    # one step more with most rows dead: slot 2 alone lives, and what the
    # two slots that sit it out hold is what it was, bit for bit
    x = jnp.zeros((SLOTS, 1, DIM)).at[2, 0].set(rows[0][lens[0] + steps])
    pos = np.asarray([[-1], [-1], [lens[0] + steps]], np.int32)
    before = cache_of(changed)
    out, changed = layer_fn.apply(
        {"params": tree, "cache": changed["cache"]}, x, pos,
        np.asarray([-1, -1, 2], np.int32), rows_are_slots=True, mutable=["cache"])
    np.testing.assert_allclose(
        np.asarray(out[2, 0]), want[0][lens[0] + steps], atol=TOLERANCE)
    assert np.isfinite(np.asarray(out)).all()
    for old, new in zip(before, cache_of(changed)):
        np.testing.assert_array_equal(old[:2], new[:2])
        assert (old[2] != new[2]).any()


def test_rows_of_unequal_length_end_each_at_its_own_last_position(weights):
    """One padded call of three rows (a burst's prefill) leaves in each
    row's slot the state and the convolution rows that the row ALONE, at its
    own length, leaves: the padding behind a row changes neither."""
    _, _, tree = weights
    lens, bucket = [23, 8, 2], 24  # 2 < the convolution's three rows
    rows = [inputs(1, n, seed=10 + n)[0] for n in lens]
    layer_fn = mixer(decode=True, state_slots=SLOTS)
    x = jnp.stack([jnp.pad(r, ((0, bucket - n), (0, 0))) for r, n in zip(rows, lens)])
    _, together = layer_fn.apply(
        {"params": tree, "cache": random_cache()}, x, padded(lens, bucket),
        np.asarray([1, 2, 0], np.int32), mutable=["cache"])
    state, conv = cache_of(together)
    for row, n, slot in zip(rows, lens, [1, 2, 0]):
        _, alone = layer_fn.apply(
            {"params": tree, "cache": random_cache(seed=9)}, row[None],
            padded([n], n), np.asarray([slot], np.int32), mutable=["cache"])
        state_alone, conv_alone = cache_of(alone)
        np.testing.assert_allclose(state[slot], state_alone[slot], atol=1e-5)
        np.testing.assert_allclose(conv[slot], conv_alone[slot], atol=1e-6)


def test_a_reused_slot_starts_from_zero(weights):
    """A slot is never cleared: a row whose first position is 0 reads zeros
    whatever the slot held."""
    _, _, tree = weights
    x = inputs(1, 12, seed=3)
    layer_fn = mixer(decode=True, state_slots=SLOTS)
    call = lambda cache: layer_fn.apply(  # noqa: E731
        {"params": tree, "cache": cache}, x, padded([12], 12),
        np.asarray([1], np.int32), mutable=["cache"])
    used, after_used = call(random_cache())
    fresh, after_fresh = call(jax.tree.map(jnp.zeros_like, random_cache()))
    np.testing.assert_array_equal(np.asarray(used), np.asarray(fresh))
    for got, want in zip(cache_of(after_used), cache_of(after_fresh)):
        np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("call", ["decode_step", "prefill"])
def test_padding_rows_change_neither_state_nor_convolution_rows(weights, call):
    _, _, tree = weights
    layer_fn = mixer(decode=True, state_slots=SLOTS)
    before = random_cache()
    pad = np.full((SLOTS,), -1, np.int32)
    if call == "decode_step":
        _, after = layer_fn.apply(
            {"params": tree, "cache": before}, inputs(SLOTS, 1), pad[:, None], pad,
            rows_are_slots=True, mutable=["cache"])
    else:
        _, after = layer_fn.apply(
            {"params": tree, "cache": before}, inputs(SLOTS, 16),
            np.full((SLOTS, 16), -1, np.int32), pad, mutable=["cache"])
    for got, want in zip(cache_of(after), cache_of({"cache": before})):
        np.testing.assert_array_equal(got, want)


def test_the_fixed_width_step_is_stated_and_a_crossed_row_is_answered_with_nan(weights):
    _, _, tree = weights
    layer_fn = mixer(decode=True, state_slots=SLOTS)
    variables = {"params": tree, "cache": random_cache()}
    with pytest.raises(ValueError, match="rows_are_slots is the decode step"):
        layer_fn.apply(variables, inputs(1, 1), np.zeros((1, 1), np.int32),
                       np.zeros((1,), np.int32), rows_are_slots=True, mutable=["cache"])
    pos = np.asarray([[5], [5], [-1]], np.int32)
    crossed = np.asarray([0, 2, -1], np.int32)  # row 1 names slot 2
    out, _ = layer_fn.apply(variables, inputs(SLOTS, 1), pos, crossed,
                            rows_are_slots=True, mutable=["cache"])
    finite = np.isfinite(np.asarray(out)).all(axis=(1, 2))
    assert list(finite) == [True, False, True]


def test_a_long_call_in_groups_of_rows_is_the_call_at_once(weights, monkeypatch):
    """Past ``TOKEN_BUDGET`` tokens the rows go through in groups, each
    group's outputs written over its inputs; three rows in groups of two
    (one padding row behind them) give what the three give at once."""
    from pytorch_distributed_training_tpu.ops import mamba2

    _, _, tree = weights
    lens, bucket = [23, 8, 16], 24
    x = inputs(3, bucket, seed=8)
    layer_fn = mixer(decode=True, state_slots=SLOTS)
    call = lambda: layer_fn.apply(  # noqa: E731
        {"params": tree, "cache": random_cache()}, x, padded(lens, bucket),
        np.asarray([1, 2, 0], np.int32), mutable=["cache"])
    at_once, cache_at_once = call()
    monkeypatch.setattr(mamba2, "TOKEN_BUDGET", 2 * bucket)
    grouped, cache_grouped = call()
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(at_once), atol=1e-5)
    for got, want in zip(cache_of(cache_grouped), cache_of(cache_at_once)):
        np.testing.assert_allclose(got, want, atol=1e-5)
