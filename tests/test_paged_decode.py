"""The single-position kernel of the paged pool (``ops/paged_decode.py``)
against the gather arm of ``ops/attention.py::paged_attention``.

The kernel runs in Pallas interpreter mode here (the CPU); the gather arm is
what ``paged_attention`` itself takes on any backend but a TPU, so each case
calls it through a one-line module and hands the pool IT scattered into to
the kernel.  What Mosaic makes of the kernel at the served widths is asked in
``tests/test_chip_compile.py``.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from pytorch_distributed_training_tpu.ops import attention, paged_decode

BS, NB, T = 4, 48, 12  # 48 positions a row at most, three loop steps of 16
STEP = 16
HD = 16


@pytest.fixture(autouse=True)
def short_steps(monkeypatch):
    """Loop steps of 16 positions, so that a table of 48 is walked in
    three: the kernel's own 256 would make every toy row a single step."""
    monkeypatch.setattr(paged_decode, "_STEP_POSITIONS", STEP)


class _Arm(nn.Module):
    """``paged_attention`` as the two served families call it."""
    as_stored: bool

    @nn.compact
    def __call__(self, q, k, v, positions, tables):
        return attention.paged_attention(
            self, q, k, v, positions, tables, block_size=BS, num_blocks=NB,
            dtype=q.dtype, as_stored=self.as_stored)


def _pool(rng, kv_heads):
    shape = (NB * BS, kv_heads, HD)
    return {attention.KEY_POOL: rng.standard_normal(shape).astype(np.float32),
            attention.VALUE_POOL: rng.standard_normal(shape).astype(np.float32)}


def _tables(rng, lengths):
    """Each row's live blocks from a shuffled pool, block 0 kept out of
    them: every dead entry is 0 and so aliases block 0."""
    tables = np.zeros((len(lengths), T), np.int32)
    free = list(rng.permutation(NB - 1) + 1)
    for b, n in enumerate(lengths):
        for t in range(-(-n // BS)):
            tables[b, t] = free.pop()
    return tables


def _dead_rows(tables, lengths):
    """Pool rows no row of the batch may read: whole dead blocks and the
    tail of each row's last live block.  Row 0 of block 0 stays: it is key
    0 of a padding row."""
    dead = np.ones((NB, BS), bool)
    for b, n in enumerate(lengths):
        for t in range(-(-n // BS)):
            dead[tables[b, t], : min(BS, n - t * BS)] = False
    dead[0, 0] = False
    return dead.reshape(-1)


def _both(pool, q, k, v, positions, tables, *, as_stored, group):
    """``(gather arm, kernel)`` outputs ``[B, H, hd]`` of one decode call."""
    b, heads = q.shape[0], q.shape[2]
    pool = {name: jnp.asarray(leaf) for name, leaf in pool.items()}
    want, state = _Arm(as_stored).apply(
        {"cache": pool}, *(jnp.asarray(x) for x in (q, k, v, positions, tables)),
        mutable=["cache"])
    cache = state["cache"]
    kv_heads = heads // group
    got = paged_decode.paged_decode(
        q.reshape(b, kv_heads, group, HD),
        cache[attention.KEY_POOL].reshape(NB, BS, kv_heads, HD),
        cache[attention.VALUE_POOL].reshape(NB, BS, kv_heads, HD),
        tables, jnp.maximum(positions[:, 0], 0) + 1,
        scale=1 / math.sqrt(HD), interpret=True)
    return np.asarray(want[:, 0]), np.asarray(got.reshape(b, heads, HD))


def _call(rng, lengths, kv_heads, group):
    """A decode call's own arrays: the query and the new K/V row of every
    row at position ``length - 1`` (``length`` 0: a padding row)."""
    b, heads = len(lengths), kv_heads * group
    q = rng.standard_normal((b, 1, heads, HD)).astype(np.float32)
    k = rng.standard_normal((b, 1, kv_heads, HD)).astype(np.float32)
    v = rng.standard_normal((b, 1, kv_heads, HD)).astype(np.float32)
    positions = (np.asarray(lengths, np.int32) - 1)[:, None]
    return q, k, v, positions


# one position, a whole block, a block plus one, a loop step less one, a whole
# step, a step plus one, two steps and a block, the whole table; 0: padding
RAGGED = [1, BS, BS + 1, STEP - 1, STEP, STEP + 1, 2 * STEP + BS, T * BS, 0]


@pytest.mark.parametrize("group,as_stored", [(1, False), (8, True), (2, False), (16, True)],
                         ids=["G1_lm", "G8_gqa", "G2", "G16_2kv_nemotron"])
def test_kernel_matches_the_gather_arm_on_ragged_rows(group, as_stored):
    rng = np.random.default_rng(group)
    kv_heads = 2  # G16: 32 query heads over 2 K/V heads, nemotron_h's attention
    lengths = RAGGED
    tables = _tables(rng, lengths)
    want, got = _both(_pool(rng, kv_heads), *_call(rng, lengths, kv_heads, group),
                      tables, as_stored=as_stored, group=group)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("group", [1, 8])
def test_bfloat16_pool_keeps_float32_probabilities(group):
    """The pool's rows as stored (bfloat16 is exact in float32), everything
    after the scores in float32: the kernel is as close to the float32 arm
    as one rounding of the OUTPUT to bfloat16, which rounding the
    probabilities to bfloat16 would not be."""
    rng = np.random.default_rng(5)
    kv_heads, lengths = 2, [T * BS, 2 * STEP + 3, 1]
    tables = _tables(rng, lengths)
    pool = _pool(rng, kv_heads)
    call = _call(rng, lengths, kv_heads, group)
    bf16 = lambda x: jnp.asarray(x, jnp.bfloat16)
    pool16 = {name: bf16(leaf) for name, leaf in pool.items()}
    call16 = tuple(bf16(x) for x in call[:3]) + (call[3],)
    exact = {name: np.asarray(leaf, np.float32) for name, leaf in pool16.items()}
    call_exact = tuple(np.asarray(x, np.float32) for x in call16[:3]) + (call[3],)
    want, _ = _both(exact, *call_exact, tables, as_stored=False, group=group)
    _, got = _both(pool16, *call16, tables, as_stored=False, group=group)
    # half a bfloat16 step of the output, and the accumulation's float32 noise
    np.testing.assert_allclose(got, want, rtol=2 ** -8, atol=1e-5)


@pytest.mark.parametrize("where", ["dead_blocks", "dead_tail", "both"])
@pytest.mark.parametrize("group", [1, 8])
def test_nan_in_dead_rows_stays_out_of_every_row(group, where):
    """A recycled block keeps an evicted request's rows and a padded table
    entry aliases block 0: neither a dead block nor the dead tail of a live
    block may reach a row's output, as weight or as ``0 * NaN``."""
    rng = np.random.default_rng(11)
    kv_heads, lengths = 2, RAGGED
    tables = _tables(rng, lengths)
    pool = _pool(rng, kv_heads)
    call = _call(rng, lengths, kv_heads, group)
    want, clean = _both(pool, *call, tables, as_stored=False, group=group)
    dead = _dead_rows(tables, lengths)
    in_live_block = np.isin(np.arange(NB * BS) // BS, tables[tables > 0])
    chosen = {"dead_blocks": dead & ~in_live_block,
              "dead_tail": dead & in_live_block, "both": dead}[where]
    assert chosen.any()
    dirty = {name: np.where(chosen[:, None, None], np.nan, leaf)
             for name, leaf in pool.items()}
    _, got = _both(dirty, *call, tables, as_stored=False, group=group)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_a_recycled_block_reads_as_a_fresh_one():
    """The same call over a pool whose blocks still hold another request's
    (large) rows beyond each row's length and over a zeroed pool."""
    rng = np.random.default_rng(13)
    kv_heads, group, lengths = 2, 1, [BS + 1, STEP + 2, 3]
    tables = _tables(rng, lengths)
    call = _call(rng, lengths, kv_heads, group)
    used = _pool(rng, kv_heads)
    live = ~_dead_rows(tables, lengths)[:, None, None]
    fresh = {name: np.where(live, leaf, 0.0) for name, leaf in used.items()}
    stale = {name: np.where(live, leaf, 1e30) for name, leaf in used.items()}
    _, want = _both(fresh, *call, tables, as_stored=False, group=group)
    _, got = _both(stale, *call, tables, as_stored=False, group=group)
    np.testing.assert_array_equal(got, want)


def test_a_padding_row_keeps_key_zero_live_and_bothers_nobody():
    """Position -1: nothing is scattered, the row reads key 0 of its
    table's first block (block 0) and its softmax stays finite; the rows
    beside it read what they read without it."""
    rng = np.random.default_rng(17)
    kv_heads, group = 2, 8
    pool = _pool(rng, kv_heads)
    lengths = [0, STEP + 1, 0, 5]
    tables = _tables(rng, lengths)
    call = _call(rng, lengths, kv_heads, group)
    want, got = _both(pool, *call, tables, as_stored=True, group=group)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # one live key: the output is that key's value row, for every query head
    v0 = pool[attention.VALUE_POOL][0]  # [Hkv, hd]
    np.testing.assert_allclose(got[0], np.repeat(v0, group, axis=0), rtol=1e-6)
    alone, _ = _both(pool, *(x[1:2] for x in call), tables[1:2],
                     as_stored=True, group=group)
    np.testing.assert_allclose(got[1], alone[0], rtol=2e-5, atol=2e-6)


def test_a_nan_in_a_live_row_stays_in_the_row_that_owns_it():
    """The output guard's contract from the other side: the row whose own
    key is NaN reads NaN, and the row scored next, through the same buffer,
    does not."""
    rng = np.random.default_rng(19)
    kv_heads, group, lengths = 2, 1, [2 * STEP, T * BS, 3, STEP]
    tables = _tables(rng, lengths)
    pool = _pool(rng, kv_heads)
    call = _call(rng, lengths, kv_heads, group)
    want, _ = _both(pool, *call, tables, as_stored=False, group=group)
    owner = 1
    pool[attention.KEY_POOL][tables[owner, 2] * BS + 1] = np.nan
    _, got = _both(pool, *call, tables, as_stored=False, group=group)
    assert np.isnan(got[owner]).all()
    others = [b for b in range(len(lengths)) if b != owner]
    np.testing.assert_allclose(got[others], want[others], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("head_dim,kv_heads,dtype,ok", [
    (128, 8, jnp.bfloat16, True),    # lm271m, solar-open2-250b
    (128, 8, jnp.float32, True),
    (128, 2, jnp.bfloat16, True),    # nemotron-3-super-120b: two heads, one sublane
    (128, 1, jnp.float32, True),
    (128, 1, jnp.bfloat16, False),   # half a 32-bit sublane a row
    (64, 8, jnp.bfloat16, False),    # half a lane tile
    (8, 4, jnp.float32, False),      # the toy models of the CPU tests
])
def test_fits_says_which_pools_the_kernel_reads(head_dim, kv_heads, dtype, ok):
    assert paged_decode.fits(head_dim, kv_heads, dtype) is ok


def test_pools_of_other_heads_are_refused():
    q = jnp.zeros((2, 2, 1, 128))
    pool = jnp.zeros((4, 4, 4, 128))
    with pytest.raises(ValueError, match="does not read pools"):
        paged_decode.paged_decode(
            q, pool, pool, jnp.zeros((2, 3), jnp.int32),
            jnp.ones((2,), jnp.int32), scale=1.0, interpret=True)


# --------------------------------------------------------------------- #
# the program: which calls take the kernel


@pytest.fixture(scope="module")
def toy_lm():
    """A two-layer LM whose heads are 128 wide, the narrowest the kernel
    reads."""
    from pytorch_distributed_training_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(vocab_size=64, max_len=64, embed_dim=256, depth=2,
                          num_heads=2)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture
def toy_fns(toy_lm):
    """The LM's paged programs, built anew (a program traced under one
    backend's routing is cached as traced), the arguments of a decode and
    of a prefill call, and the shape a whole-table gather has."""
    from pytorch_distributed_training_tpu.serving.decode import build_paged_fns

    model, params = toy_lm
    fns = build_paged_fns(model, 8, 16)
    pool = fns.init_pool(params)
    w, t = 4, 6
    keys = jnp.stack([jax.random.PRNGKey(1)] * w)
    i32 = lambda *shape: np.zeros(shape, np.int32)
    decode = (params, pool, i32(w), np.ones(w, bool), i32(w), i32(w), i32(w, t),
              keys, i32(w), i32(w))
    prefill = (params, pool, i32(w, 16), i32(w, 16), i32(w, t), i32(w), keys,
               i32(w), i32(w))
    return fns, decode, prefill, (w, t * 8, 2, 128)


def _lowered_for_tpu(program, args, monkeypatch):
    """StableHLO of ``program`` as a TPU would get it, from here: the
    routing asks ``flash_enabled()``, which is the CPU's answer here."""
    from pytorch_distributed_training_tpu.ops import flash_attention as gate

    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    lowered = program.trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text(debug_info=True)


def _gathers_of(text, shape):
    dims = "x".join(str(d) for d in shape)
    return re.findall(rf'"?stablehlo\.gather"?.*-> tensor<{dims}x', text)


@pytest.mark.parametrize("name", ["decode_step", "decode_step.carried"])
def test_decode_programs_hold_the_kernel_and_no_table_gather(toy_fns, name, monkeypatch):
    """The ONE decode program, as the sync callers hand it its arguments (a
    mask of all rows) and as the ring does (``.carried``: no row fresh)."""
    fns, decode, _, gathered = toy_fns
    args = decode
    if name == "decode_step.carried":
        params, pool, prev, mask, *rest = decode
        args = (params, pool, prev, np.zeros_like(mask), *rest)
    text = _lowered_for_tpu(fns.decode_step, args, monkeypatch)
    # the kernel is lowered ONCE (``paged_decode`` is a jitted function: a
    # layer's call is a call of it, so a program's set-up pays one
    # lowering whatever its depth) and called a layer, inside the scope
    # the layer's readers look under
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(kernels) == 1 and 'kernel_name = "paged_decode"' in kernels[0]
    assert len(re.findall(r"= call @paged_decode\(", text)) == 2
    assert len(re.findall(
        r'loc\("jit\(decode_step\)/TransformerLM/block\d/attn/paged_attention/'
        r'[^"]*jit\(paged_decode\)"', text)) == 2
    assert not _gathers_of(text, gathered)
    # ... and the whole pool is still the program's to update in place
    n_leaves = len(jax.tree_util.tree_leaves(decode[1]))
    assert n_leaves == 4
    assert text.count("tf.aliasing_output") + text.count("jax.buffer_donor") == n_leaves


def test_decode_program_off_the_tpu_keeps_the_gather_arm(toy_fns):
    fns, decode, _, gathered = toy_fns
    text = fns.decode_step.lower(*decode).as_text()
    assert "tpu_custom_call" not in text
    assert len(_gathers_of(text, gathered)) == 4  # K and V, two layers


def test_prefill_program_keeps_the_gather_arm_on_a_tpu(toy_fns, monkeypatch):
    """``s > 1``: whole-prompt and chunked prefill and ``verify`` keep their
    programs, whatever the backend."""
    fns, _, prefill, gathered = toy_fns
    here = fns.prefill.lower(*prefill).as_text()
    there = _lowered_for_tpu(fns.prefill, prefill, monkeypatch)
    assert "kernel_name" not in there and "tpu_custom_call" not in there
    assert len(_gathers_of(there, gathered)) == len(_gathers_of(here, gathered)) == 4


# --------------------------------------------------------------------- #
# the counter


def test_live_block_share_is_observed_a_decode_step():
    """Of ``slots x table_blocks`` entries, those a step's live rows read."""
    from pytorch_distributed_training_tpu.models.transformer_lm import TransformerLM
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )

    model = TransformerLM(vocab_size=64, max_len=32, embed_dim=32, depth=1,
                          num_heads=4)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    sched = ContinuousScheduler(
        model, params, start=False, slots=4, block_size=4, num_blocks=24,
        batch_buckets=[4], seq_buckets=[8], max_new_tokens=4,
        temperature=0.0, eos_id=None)
    assert sched.table_blocks == 3
    # positions 5 and 0 live: 2 blocks and 1 of the 4 x 3 entries
    assert sched._live_block_share(np.asarray([5, -1, 0, -1])) == 3 / 12
    assert "paged_live_block_share_mean" not in sched.metrics.snapshot()
    fut = sched.submit(np.asarray([3, 4, 5, 6, 7], np.int32))
    for _ in range(50):
        if fut.done():
            break
        sched.tick()
    sched.close()
    assert len(fut.result()["tokens"]) == 4
    snap = sched.metrics.snapshot()
    # a prompt of 5 and up to 3 more positions: always 2 of the 12 entries
    assert snap["paged_live_block_share_mean"] == pytest.approx(2 / 12)
    assert snap["paged_live_block_share_p50"] == pytest.approx(2 / 12)
    # the model carries no state: no share of live state rows to report
    assert sched._state_live_row_share(np.asarray([5, -1, 0, -1])) is None
    assert not [k for k in snap if k.startswith("state_live_row_share")]
