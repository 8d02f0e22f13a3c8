"""The single-position kernel of the paged LATENT pool
(``ops/mla_paged_decode.py``) against the gather arm of ``ops/mla.py::MLAttention``.

The kernel runs in Pallas interpreter mode here (the CPU); the gather arm is
what ``MLAttention`` itself takes on any backend but a TPU.  Each case calls
the module twice over the same pool: as it stands (the gather arm), and with
the routing's question answered as a TPU answers it and the kernel
interpreted, so that the module scatters the call's rows and hands the leaf
IT scattered into to the kernel (a spy checks that it is the leaf the gather
arm returns).  What Mosaic makes of the kernel at the served widths is asked
in ``tests/test_chip_compile.py``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.ops import attention, mla_paged_decode
from pytorch_distributed_training_tpu.ops import flash_attention as gate
from pytorch_distributed_training_tpu.ops.mla import MLAttention
from test_paged_decode import _lowered_for_tpu

BS, NB, T = 16, 32, 6  # 96 positions a row at most, three loop steps of 32
STEP = 32
RANK, ROPE, DIM = 128, 16, 64  # the narrowest latent the kernel reads
lanes_up = mla_paged_decode.lanes_up
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# float32: the online softmax reorders float32 sums.  bfloat16: the gather
# arm upcasts its operands on the CPU (its dot has no bfloat16 product), the
# kernel's arm rounds the latent queries, the probabilities and the latent
# output to bfloat16 as the TPU's gather arm does: three roundings of 2^-9
TOLERANCE = {"float32": dict(rtol=2e-5, atol=2e-6),
             "bfloat16": dict(rtol=2 ** -6, atol=2 ** -6)}


@pytest.fixture(autouse=True)
def short_steps(monkeypatch):
    """Loop steps of 32 positions, so that a table of 96 is walked in
    three: the kernel's own 256 would make every toy row a single step."""
    monkeypatch.setattr(mla_paged_decode, "_STEP_POSITIONS", STEP)


WIDTHS = dict(num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=ROPE, v_head_dim=16,
              kv_lora_rank=RANK, decode=True, paged=True, kv_block_size=BS,
              kv_num_blocks=NB)


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


def _call(rng, lengths, dtype, heads=4, rope=ROPE, past=0.0):
    """A decode call: the module's parameters, a pool of random rows (the
    lanes past ``rank + rope`` hold ``past``: zeros, as every write leaves
    them), the hidden row of every slot at position ``length - 1``
    (``length`` 0: a padding row) and the tables."""
    module = MLAttention(**dict(WIDTHS, num_heads=heads, qk_rope_head_dim=rope,
                                dtype=DTYPES[dtype]))
    tables = _tables(rng, lengths)
    x = jnp.asarray(rng.standard_normal((len(lengths), 1, DIM)), DTYPES[dtype])
    positions = (np.asarray(lengths, np.int32) - 1)[:, None]
    params = module.init(jax.random.PRNGKey(0), x, positions, tables)["params"]
    pool = np.full((NB * BS, lanes_up(RANK + rope)), past, np.float32)
    pool[:, :RANK + rope] = rng.standard_normal((NB * BS, RANK + rope))
    return module, params, pool, x, positions, tables


def _both(monkeypatch, module, params, pool, x, positions, tables):
    """``(gather arm, kernel)`` outputs ``[B, dim]`` of one decode call over
    ``pool``, float32."""
    cache = {"cache": {attention.LATENT_POOL: jnp.asarray(pool, module.dtype)}}
    want, state = module.apply(
        {"params": params, **cache}, x, positions, tables, mutable=["cache"])
    handed = []

    def interpreted(q_lat, q_pe, leaf, *rest, **kw):
        handed.append(leaf)
        return kernel(q_lat, q_pe, leaf, *rest, interpret=True, **kw)

    kernel = mla_paged_decode.mla_paged_decode
    with monkeypatch.context() as m:
        m.setattr(gate, "flash_enabled", lambda: True)
        m.setattr(mla_paged_decode, "mla_paged_decode", interpreted)
        got, _ = module.apply(
            {"params": params, **cache}, x, positions, tables, mutable=["cache"])
    # the kernel read the leaf this call's rows were scattered into
    (leaf,) = handed
    np.testing.assert_array_equal(
        np.asarray(leaf, np.float32).reshape(NB * BS, -1),
        np.asarray(state["cache"][attention.LATENT_POOL], np.float32))
    return np.asarray(want[:, 0], np.float32), np.asarray(got[:, 0], np.float32)


# one position, a block less one, a whole block, a block plus one, a loop
# step less one, a whole step, a step plus one, two steps and a block, the
# whole table; 0: padding
RAGGED = [1, BS - 1, BS, BS + 1, STEP - 1, STEP, STEP + 1, 2 * STEP + BS, T * BS, 0]


# rope 16: a row of 144 lanes in a leaf of 256; rope 128: a row that is whole
# lane tiles already, and a leaf of its own width
@pytest.mark.parametrize("dtype,heads,rope", [
    ("float32", 4, ROPE), ("bfloat16", 4, ROPE), ("float32", 16, ROPE),
    ("bfloat16", 16, ROPE), ("float32", 4, 128), ("bfloat16", 4, 128)])
def test_kernel_matches_the_gather_arm_on_ragged_rows(monkeypatch, dtype, heads, rope):
    rng = np.random.default_rng(heads)
    call = _call(rng, RAGGED, dtype, heads, rope)
    assert call[2].shape[1] == {ROPE: 256, 128: RANK + 128}[rope]
    want, got = _both(monkeypatch, *call)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, **TOLERANCE[dtype])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_lanes_past_the_row_are_written_as_zeros_and_never_read(monkeypatch, dtype):
    """A leaf's row is whole lane tiles: past ``rank + rope`` every write
    writes zeros, and what lies there (here a NaN in every row of the pool)
    reaches neither arm's scores nor its values."""
    lengths = [1, BS + 1, 2 * STEP + BS, 0]
    clean = _call(np.random.default_rng(5), lengths, dtype)
    dirty = _call(np.random.default_rng(5), lengths, dtype, past=np.nan)
    np.testing.assert_array_equal(clean[2][:, :RANK + ROPE], dirty[2][:, :RANK + ROPE])
    want = _both(monkeypatch, *clean)
    got = _both(monkeypatch, *dirty)
    assert np.isfinite(got[0]).all() and np.isfinite(got[1]).all()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    module, params, pool, x, positions, tables = dirty
    _, state = module.apply(
        {"params": params, "cache": {attention.LATENT_POOL: jnp.asarray(pool, module.dtype)}},
        x, positions, tables, mutable=["cache"])
    leaf = np.asarray(state["cache"][attention.LATENT_POOL], np.float32)
    written = [tables[b, (n - 1) // BS] * BS + (n - 1) % BS
               for b, n in enumerate(lengths) if n]
    assert (leaf[written, RANK + ROPE:] == 0).all()
    assert np.abs(leaf[written, :RANK + ROPE]).min(axis=1).max() > 0
    others = np.setdiff1d(np.arange(NB * BS), written)
    assert np.isnan(leaf[others, RANK + ROPE:]).all()


@pytest.mark.parametrize("lengths", [RAGGED, [T * BS] * 3, [0, 0, 1, 0], [STEP + 1]],
                         ids=["ragged", "full_tables", "padding", "one_row"])
def test_the_walk_asks_for_live_blocks_only(lengths):
    """The lists a grid step reads: the rows in order, each row's loop steps
    in order, and per operand the row's table entry where it holds live
    positions, else the block the operand read last (which the pipeline
    does not fetch again): no block past a row's length is asked for."""
    rng = np.random.default_rng(3)
    tables = _tables(rng, lengths)
    step_blocks = STEP // BS
    steps, row, step, source = (np.asarray(a) for a in mla_paged_decode._walk(
        jnp.maximum(jnp.asarray(lengths, jnp.int32), 1), len(lengths), T, BS,
        step_blocks))
    blocks = [max(1, -(-n // BS)) for n in lengths]
    want = [(b, s) for b, n in enumerate(blocks) for s in range(-(-n // step_blocks))]
    assert int(steps) == len(want)
    assert list(zip(row[:steps], step[:steps])) == want
    index = np.asarray([
        [mla_paged_decode._block(tables, row, step, source, i, j, step_blocks)
         for j in range(step_blocks)] for i in range(steps)])
    for i, (b, s) in enumerate(want):
        for j in range(step_blocks):
            if s * step_blocks + j < blocks[b]:
                assert index[i, j] == tables[b, s * step_blocks + j]
            elif i:
                assert index[i, j] == index[i - 1, j]
            assert 0 <= index[i, j] < NB


@pytest.mark.parametrize("where", ["dead_blocks", "dead_tail", "block_zero", "all"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_nan_in_dead_rows_stays_out_of_every_row(monkeypatch, dtype, where):
    """A recycled block keeps an evicted request's rows and a padded table
    entry aliases block 0: neither a dead block nor the dead tail of a live
    block may reach a row's output, as weight or as ``0 * NaN``."""
    rng = np.random.default_rng(11)
    module, params, pool, x, positions, tables = _call(rng, RAGGED, dtype)
    want, clean = _both(monkeypatch, module, params, pool, x, positions, tables)
    dead = _dead_rows(tables, RAGGED)
    block = np.arange(NB * BS) // BS
    in_live_block = np.isin(block, tables[tables > 0])
    chosen = {"dead_blocks": dead & ~in_live_block, "dead_tail": dead & in_live_block,
              "block_zero": dead & (block == 0), "all": dead}[where]
    assert chosen.any()
    dirty = np.where(chosen[:, None], np.nan, pool)
    _, got = _both(monkeypatch, module, params, dirty, x, positions, tables)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    np.testing.assert_allclose(got, want, **TOLERANCE[dtype])


def test_a_recycled_block_reads_as_a_fresh_one(monkeypatch):
    """The same call over a pool whose blocks still hold another request's
    (large) rows beyond each row's length and over a zeroed pool."""
    rng = np.random.default_rng(13)
    lengths = [BS + 1, STEP + 2, 3]
    module, params, pool, x, positions, tables = _call(rng, lengths, "float32")
    live = ~_dead_rows(tables, lengths)[:, None]
    _, want = _both(monkeypatch, module, params, np.where(live, pool, 0.0),
                    x, positions, tables)
    _, got = _both(monkeypatch, module, params, np.where(live, pool, 1e30),
                   x, positions, tables)
    np.testing.assert_array_equal(got, want)


def test_a_padding_row_keeps_key_zero_live_and_bothers_nobody(monkeypatch):
    """Position -1: nothing is scattered, the row reads key 0 of its
    table's first block (block 0) and its softmax stays finite; the rows
    beside it read what they read without it."""
    rng = np.random.default_rng(17)
    lengths = [0, STEP + 1, 0, 5]
    module, params, pool, x, positions, tables = _call(rng, lengths, "float32")
    want, got = _both(monkeypatch, module, params, pool, x, positions, tables)
    np.testing.assert_allclose(got, want, **TOLERANCE["float32"])
    # one live key: the attention's output is that key's latent, whatever
    # the query, so the two padding rows (different inputs) agree
    assert np.isfinite(got).all() and np.abs(got[0]).max() > 1e-3
    np.testing.assert_allclose(got[0], got[2], rtol=1e-6)
    _, alone = _both(monkeypatch, module, params, pool, x[1:2], positions[1:2],
                     tables[1:2])
    np.testing.assert_allclose(got[1], alone[0], **TOLERANCE["float32"])


def test_a_nan_in_a_live_row_stays_in_the_row_that_owns_it(monkeypatch):
    """The output guard's contract from the other side: the row whose own
    key is NaN reads NaN, and the row scored next, through the same
    operands' buffers, does not."""
    rng = np.random.default_rng(19)
    lengths = [2 * STEP, T * BS, 3, STEP]
    module, params, pool, x, positions, tables = _call(rng, lengths, "float32")
    want, _ = _both(monkeypatch, module, params, pool, x, positions, tables)
    owner = 1
    pool[tables[owner, 2] * BS + 1] = np.nan
    _, got = _both(monkeypatch, module, params, pool, x, positions, tables)
    assert np.isnan(got[owner]).all()
    others = [b for b in range(len(lengths)) if b != owner]
    np.testing.assert_allclose(got[others], want[others], **TOLERANCE["float32"])


@pytest.mark.parametrize("rank,block_size,dtype,ok", [
    (512, 16, jnp.bfloat16, True),   # deepseek-v2-lite as served: 512 + 64
    (512, 16, jnp.float32, True),
    (128, 16, jnp.float32, True),    # the toy of this file
    (128, 8, jnp.float32, True),
    (512, 8, jnp.bfloat16, False),   # half a bfloat16 sublane tile a block
    (128, 4, jnp.float32, False),
    (32, 8, jnp.float32, False),     # the toy of tests/test_deepseek_v2.py
    (576, 16, jnp.bfloat16, False),  # half a lane tile in the value
])
def test_fits_says_which_leaves_the_kernel_reads(rank, block_size, dtype, ok):
    assert mla_paged_decode.fits(rank, block_size, dtype) is ok


@pytest.mark.parametrize("rank,rope,width", [
    (512, 64, 640),    # deepseek-v2-lite as served: 4.5 lane tiles a row
    (512, 128, 640),   # whole tiles already: left as it is
    (RANK, ROPE, 256), (120, 8, 128), (32, 8, 128),
])
def test_a_leaf_s_row_is_whole_lane_tiles(rank, rope, width):
    assert lanes_up(rank + rope) == width
    module = MLAttention(**dict(WIDTHS, kv_lora_rank=rank, qk_rope_head_dim=rope))
    x, i32 = jnp.zeros((1, 1, DIM)), jnp.zeros((1, 1), jnp.int32)
    cache = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), x, i32, i32))["cache"]
    assert cache[attention.LATENT_POOL].shape == (NB * BS, width)


@pytest.mark.parametrize("q_pe,pool", [
    ((2, 4, 192), (4, 8, 256)),  # a rope whose row the leaf does not hold
    ((3, 4, 16), (4, 8, 256)),   # another batch than the latent queries'
    ((2, 4, 16), (4, 8, 128)),   # a leaf with no rope lanes
    ((2, 4, 16), (4, 8, 144)),   # a leaf whose row is not whole lane tiles
    ((2, 4, 16), (4, 8, 384)),   # a leaf a lane tile wider than the row's
], ids=["rope", "batch", "leaf", "untiled", "wide"])
def test_queries_that_do_not_read_the_leaf_are_refused(q_pe, pool):
    with pytest.raises(ValueError, match="do not read pool"):
        mla_paged_decode.mla_paged_decode(
            jnp.zeros((2, 4, 128)), jnp.zeros(q_pe), jnp.zeros(pool),
            jnp.zeros((2, 3), jnp.int32), jnp.ones((2,), jnp.int32), scale=1.0,
            interpret=True)


def test_a_leaf_of_another_dtype_is_refused():
    with pytest.raises(ValueError, match="do not read pool"):
        mla_paged_decode.mla_paged_decode(
            jnp.zeros((2, 4, 128)), jnp.zeros((2, 4, 16)),
            jnp.zeros((4, 8, 256), jnp.bfloat16), jnp.zeros((2, 3), jnp.int32),
            jnp.ones((2,), jnp.int32), scale=1.0, interpret=True)


@pytest.mark.parametrize("why", ["two_positions", "expanded_form", "narrow_leaf",
                                 "short_block"])
def test_calls_the_kernel_cannot_take_keep_the_gather_arm_on_a_tpu(monkeypatch, why):
    """``s > 1``, a module that takes the expanded form at one query, a
    latent of less than a lane tile, a block of half a sublane tile: the
    routing's other questions, each answered no."""
    rng = np.random.default_rng(23)
    more = dict(WIDTHS)
    s = 1
    if why == "two_positions":
        s = 2
    elif why == "expanded_form":
        more["absorb_max_queries"] = 0
    elif why == "narrow_leaf":
        more["kv_lora_rank"] = 32
    else:
        more.update(kv_block_size=4, kv_num_blocks=4 * NB)
    module = MLAttention(**more)
    tables = _tables(rng, [5, 9])
    x = jnp.asarray(rng.standard_normal((2, s, DIM)), jnp.float32)
    positions = np.asarray([[4], [8]], np.int32) + np.arange(s, dtype=np.int32) - (s - 1)
    variables = module.init(jax.random.PRNGKey(0), x, positions, tables)

    def refuse(*a, **k):
        raise AssertionError("the kernel was called")

    want, _ = module.apply(variables, x, positions, tables, mutable=["cache"])
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    monkeypatch.setattr(mla_paged_decode, "mla_paged_decode", refuse)
    got, _ = module.apply(variables, x, positions, tables, mutable=["cache"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------- #
# the program: which calls take the kernel

LAYERS = 3


@pytest.fixture(scope="module")
def toy_lm():
    """``tests/test_deepseek_v2.py``'s toy (a dense layer, then expert
    layers) with a latent of 128, the narrowest the kernel reads."""
    from test_deepseek_v2 import MODEL_KEYS

    from pytorch_distributed_training_tpu.models import get_model

    model = get_model("DeepseekV2", num_classes=64, dtype=jnp.float32,
                      **dict(MODEL_KEYS, kv_lora_rank=RANK, num_hidden_layers=LAYERS))
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
    fns = build_paged_fns(model, BS, 16)
    pool = fns.init_pool(params)
    w, t = 4, 6
    keys = jnp.stack([jax.random.PRNGKey(1)] * w)
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    decode = (params, pool, i32(w), np.ones(w, bool), i32(w), i32(w), i32(w, t),
              keys, i32(w), i32(w))
    prefill = (params, pool, i32(w, 16), i32(w, 16), i32(w, t), i32(w), keys,
               i32(w), i32(w))
    return fns, decode, prefill, (w, t * BS, RANK + 8)  # the toy's rope is 8


def _gathers_of(text, shape):
    """Gathers into the table's rows: a block at a time ``[B, T, bs, w]``,
    reshaped to ``[B, L, w]`` afterwards."""
    b, length, width = shape
    dims = f"{b}x{length // BS}x{BS}x{width}"
    return re.findall(rf'"?stablehlo\.gather"?.*-> tensor<{dims}x', text)


def _kernels_of(text):
    return [re.search(r'kernel_name = "(\w+)"', line).group(1)
            for line in text.splitlines() if "tpu_custom_call" in line]


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
    # the kernel is lowered ONCE (``mla_paged_decode`` is a jitted function:
    # a layer's call is a call of it, so a program's set-up pays one
    # lowering whatever its depth) and called a layer, inside the scope
    # ``benchmark/decode_scopes.py`` books the layer's time under; the
    # program's other Mosaic calls are the expert layers' grouped products
    assert _kernels_of(text).count("mla_paged_decode") == 1
    assert len(re.findall(r"= call @mla_paged_decode\(", text)) == LAYERS
    assert len(re.findall(
        r'loc\("jit\(decode_step\)/DeepseekV2LM/layer\d/mla_attention/attn/'
        r'jit\(mla_paged_decode\)"', text)) == LAYERS
    assert not _gathers_of(text, gathered)
    assert not re.search(r"tensor<{}x{}x{}x".format(*gathered), text)
    # ... and every latent leaf is still the program's to update in place
    n_leaves = len(jax.tree_util.tree_leaves(decode[1]))
    assert n_leaves == LAYERS
    assert text.count("tf.aliasing_output") + text.count("jax.buffer_donor") == n_leaves


def test_decode_program_off_the_tpu_keeps_the_gather_arm(toy_fns):
    fns, decode, _, gathered = toy_fns
    text = fns.decode_step.lower(*decode).as_text()
    assert "tpu_custom_call" not in text
    assert len(_gathers_of(text, gathered)) == LAYERS


def test_prefill_program_keeps_the_gather_arm_on_a_tpu(toy_fns, monkeypatch):
    """``s > 1``: whole-prompt prefill and ``verify`` keep the expanded form
    over the gathered rows, whatever the backend."""
    fns, _, prefill, gathered = toy_fns
    here = fns.prefill.lower(*prefill).as_text()
    there = _lowered_for_tpu(fns.prefill, prefill, monkeypatch)
    assert "mla_paged_decode" not in _kernels_of(there)
    assert "call @mla_paged_decode" not in there
    assert len(_gathers_of(there, gathered)) == len(_gathers_of(here, gathered)) == LAYERS
