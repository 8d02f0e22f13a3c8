"""A whole-prompt prefill's full-attention layers: the flash arm of
``ops/attention.py::paged_attention`` (the causal flash forward over the
call's own K/V, chosen by the static ``whole_prompts`` and the call's shape)
against the gather arm it stands in for.

The kernel runs in the Pallas interpreter here; the routing asks
``flash_enabled()``, which is the CPU's answer, so the tests that want the
flash arm steer it (``_on``).  What Mosaic makes of the kernel at the served
widths is asked in ``tests/test_chip_compile.py``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from pytorch_distributed_training_tpu.ops import attention
from pytorch_distributed_training_tpu.ops import flash_attention as gate

BS, NB, T = 16, 40, 12  # blocks of 16 positions, tables of 192
S, HD = 128, 16  # the shortest call the kernel takes


class _Arm(nn.Module):
    """``paged_attention`` as ``GroupedQueryAttention`` calls it."""
    whole_prompts: bool

    @nn.compact
    def __call__(self, q, k, v, positions, tables):
        return attention.paged_attention(
            self, q, k, v, positions, tables, block_size=BS, num_blocks=NB,
            dtype=k.dtype, as_stored=True, query_block=64,
            whole_prompts=self.whole_prompts)


def _on(monkeypatch):
    """The flash arm as a TPU would choose it, its kernel interpreted."""
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_INTERPRET", True)


def _call(rng, lengths, heads, kv_heads, dtype=np.float32, first=0):
    """One prefill call: row ``i`` holds positions ``first .. first +
    lengths[i] - 1`` in its first columns and -1 after them, its table the
    blocks a shuffled pool gave it (block 0 kept out: a dead entry is 0)."""
    b = len(lengths)
    q, k, v = (jnp.asarray(rng.standard_normal((b, S, h, HD)), dtype)
               for h in (heads, kv_heads, kv_heads))
    positions = np.full((b, S), -1, np.int32)
    tables = np.zeros((b, T), np.int32)
    free = list(rng.permutation(NB - 1) + 1)
    for i, n in enumerate(lengths):
        positions[i, :n] = first + np.arange(n)
        for t in range(-(-(first + n) // BS)):
            tables[i, t] = free.pop()
    return q, k, v, positions, tables


def _run(arm, call, pool=None):
    b, kv_heads = call[1].shape[0], call[1].shape[2]
    stored = attention._stored_heads(kv_heads)
    if pool is None:  # what an evicted request left there
        rng = np.random.default_rng(99)
        pool = {name: jnp.asarray(
            rng.standard_normal((NB * BS, stored, HD)), call[1].dtype)
            for name in (attention.KEY_POOL, attention.VALUE_POOL)}
    out, state = arm.apply(
        {"cache": jax.tree.map(jnp.asarray, pool)}, *call, mutable=["cache"])
    return np.asarray(out, np.float32), jax.device_get(state["cache"])


# 30 K/V heads a head each, stored as 32 (olmo-hybrid); 48 over 8 (laguna's
# full layers); 64 over 8 (solar-open2); 32 over 2 (nemotron-3)
@pytest.mark.parametrize("heads,kv_heads", [(30, 30), (12, 2), (16, 2), (32, 2)],
                         ids=["G1_30_stored_as_32", "G6", "G8", "G16"])
def test_flash_arm_matches_the_gather_arm_on_ragged_rows(heads, kv_heads, monkeypatch):
    """Rows of a whole bucket, of a ragged length, of one position and of
    padding alone: equal outputs at every real column (a padding column's
    output is read by nobody) and the SAME pool, bit for bit."""
    rng = np.random.default_rng(heads)
    lengths = [S, 77, 1, 0]
    call = _call(rng, lengths, heads, kv_heads)
    want, pool_want = _run(_Arm(whole_prompts=False), call)
    _on(monkeypatch)
    got, pool_got = _run(_Arm(whole_prompts=True), call)
    real = call[3] >= 0
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[real], want[real], rtol=2e-5, atol=2e-6)
    for name, leaf in pool_want.items():
        np.testing.assert_array_equal(np.asarray(pool_got[name]), np.asarray(leaf))
    # ... and the call wrote its rows: the comparison is not of two no-ops
    first_row = call[4][0, 0] * BS
    np.testing.assert_array_equal(
        np.asarray(pool_got[attention.KEY_POOL])[first_row, :kv_heads],
        np.asarray(call[1])[0, 0])


def test_bfloat16_flash_arm_is_within_a_rounding_of_the_gather_arm(monkeypatch):
    """As served: bfloat16 operands and pool, float32 scores, softmax and
    accumulation in both arms, the probabilities rounded to bfloat16 before
    the second product (the gather arm rounds them normalised, the kernel
    before it divides by their sum)."""
    rng = np.random.default_rng(3)
    call = _call(rng, [S, 50], 12, 2, dtype=jnp.bfloat16)
    want, pool_want = _run(_Arm(whole_prompts=False), call)
    _on(monkeypatch)
    got, pool_got = _run(_Arm(whole_prompts=True), call)
    real = call[3] >= 0
    np.testing.assert_allclose(got[real], want[real], rtol=2 ** -6, atol=2 ** -7)
    for name, leaf in pool_want.items():
        np.testing.assert_array_equal(
            np.asarray(pool_got[name], np.float32), np.asarray(leaf, np.float32))


def test_a_nan_in_the_pool_s_dead_rows_never_reaches_the_flash_arm(monkeypatch):
    """The flash arm reads no pool row at all: what a recycled block holds
    cannot reach a row's output."""
    rng = np.random.default_rng(4)
    call = _call(rng, [S, 9], 12, 2)
    _on(monkeypatch)
    clean, _ = _run(_Arm(whole_prompts=True), call)
    dirty = {name: jnp.full((NB * BS, 2, HD), jnp.nan, jnp.float32)
             for name in (attention.KEY_POOL, attention.VALUE_POOL)}
    got, _ = _run(_Arm(whole_prompts=True), call, dirty)
    np.testing.assert_array_equal(got, clean)


def _lowered_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("whole_prompts,kernels", [(True, 1), (False, 0)])
def test_the_static_fact_alone_chooses_the_arm(whole_prompts, kernels, monkeypatch):
    """On a TPU, at a shape the kernel takes: a caller that states whole
    prompts gets the kernel and gathers no table; one that does not (its
    call may start past position 0) keeps the gather arm."""
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    call = _call(np.random.default_rng(0), [S], 12, 2)
    arm = _Arm(whole_prompts=whole_prompts)
    pool = jax.eval_shape(
        lambda: arm.init(jax.random.PRNGKey(0), *call))["cache"]
    pool = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pool)
    text = _lowered_for_tpu(
        lambda pool, *call: arm.apply({"cache": pool}, *call, mutable=["cache"]),
        pool, *call)
    assert len(re.findall(r'kernel_name = "flash_fwd"', text)) == kernels
    # K's and V's blocks by the table, [1, T, BS, Hkv, hd]
    gathered = re.findall(rf'"stablehlo\.gather".*-> tensor<1x{T}x{BS}x2x{HD}x', text)
    assert len(gathered) == (0 if whole_prompts else 2)


def test_a_call_that_starts_past_position_zero_takes_the_gather_arm(monkeypatch):
    """A prefix hit's suffix (positions 32 .. 32 + n - 1 over a table whose
    first two blocks another call filled): the caller states no whole
    prompts, so even where the kernel would run the table is gathered, and
    the suffix reads the prefix."""
    rng = np.random.default_rng(8)
    prefix = 2 * BS
    whole = _call(rng, [prefix + 40], 12, 2)
    want, _ = _run(_Arm(whole_prompts=False), whole)
    # the same request as a prefix call and a suffix call over its pool
    q, k, v, positions, tables = whole
    head = (q, k, v, np.where(positions < prefix, positions, -1), tables)
    _, pool = _run(_Arm(whole_prompts=False), head)
    shift = lambda a: jnp.roll(a, -prefix, axis=1)  # noqa: E731
    tail_pos = np.full_like(positions, -1)
    tail_pos[0, :40] = prefix + np.arange(40)
    tail = (shift(q), shift(k), shift(v), tail_pos, tables)
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    assert not attention.whole_prompt_flash(False, S, HD)
    assert attention.whole_prompt_flash(True, S, HD)
    got, _ = _run(_Arm(whole_prompts=False), tail, pool)
    np.testing.assert_allclose(
        got[0, :40], want[0, prefix:prefix + 40], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("s,enabled,whole,want", [
    (128, True, True, True), (4096, True, True, True), (1, True, True, False),
    (64, True, True, False), (192, True, True, False), (128, False, True, False),
    (128, True, False, False)])
def test_the_rule_is_the_fact_the_backend_and_the_shape(s, enabled, whole, want, monkeypatch):
    monkeypatch.setattr(gate, "flash_enabled", lambda: enabled)
    assert attention.whole_prompt_flash(whole, s, 128) is want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_streamed_forward_is_the_resident_one(dtype, monkeypatch):
    """A prefill's largest bucket (8,192 x 128, the edge of the resident
    budget) goes through the streamed kernels: grouped and without a
    logsumexp output they give what the resident ones give, and what
    ``flash_attention`` gives over K and V repeated a group."""
    rng = np.random.default_rng(6)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 256, h, HD)), dtype)
               for h in (12, 2, 2))
    resident = np.asarray(gate.flash_prefill(q, k, v, interpret=True), np.float32)
    repeated = np.asarray(gate.flash_attention(
        q, jnp.repeat(k, 6, axis=2), jnp.repeat(v, 6, axis=2), causal=True,
        interpret=True), np.float32)
    np.testing.assert_array_equal(resident, repeated)
    monkeypatch.setenv("PDT_FLASH_FORCE_STREAM", "1")
    streamed = np.asarray(gate.flash_prefill(q, k, v, interpret=True), np.float32)
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == jnp.float32 else dict(rtol=2 ** -7, atol=2 ** -8)
    np.testing.assert_allclose(streamed, resident, **tol)


def test_the_resident_budget_ends_under_its_edge():
    assert gate._resident_ok(6144, 128) and gate._resident_ok(8192, 64)
    assert not gate._resident_ok(8192, 128)
