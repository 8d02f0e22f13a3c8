"""The Olmo-Hybrid family (models/olmo_hybrid.py, ops/gated_delta.py,
ops/attention.py::GroupedQueryAttention with ``qk_norm``, lm_parts.GatedMLP)
against the benchmark's plain reference (benchmark/reference/olmo_hybrid.py)
at a toy size on the CPU: hidden 60, 3 heads of 20, Gated DeltaNet 3 heads of
d_k 12 (no multiple of 8) x d_v 20, MLP 96, layers linear x 3 + full (one
period).  Every key of the published ``config.json`` is here under its
published name.

The reference is float32 at ``highest``, has no cache and no chunks (the
recurrence runs a position at a time) and shares no code with the program;
the weights are its ``make_params(seed)`` handed over through its
``to_checkpoint_tree``, as the benchmark hands them over.  Logits are
compared, never tokens.  TOLERANCE 2e-4 on logits of magnitude about 3:
both sides are float32 on the CPU and differ in the order of their sums (a
chunk's triangular system against a position at a time): 2e-5 was read,
2e-4 leaves ten times that and is far below what a bfloat16 state, int8
operands or a missing delta term read, which the tests below hold it to.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from pytorch_distributed_training_tpu.models import get_model, model_class
from pytorch_distributed_training_tpu.ops.attention import (
    GroupedQueryAttention, is_state_leaf, pool_leaf_role,
)
from pytorch_distributed_training_tpu.ops.gated_delta import (
    GatedDeltaNet, delta_rule_chunked_scalar,
)
from pytorch_distributed_training_tpu.ops.kda import delta_rule_chunked, delta_rule_step
from pytorch_distributed_training_tpu.serving.decode import (
    build_generate_fn, build_paged_fns,
)
from pytorch_distributed_training_tpu.serving.scheduler import ContinuousScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 512
TOLERANCE = 2e-4
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
PUBLISHED_KEYS = dict(
    model_type="olmo_hybrid", vocab_size=VOCAB, hidden_size=60,
    intermediate_size=96, num_hidden_layers=4, num_attention_heads=3,
    num_key_value_heads=3, hidden_act="silu", max_position_embeddings=256,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=PERIOD * 8, linear_num_key_heads=3, linear_num_value_heads=3,
    linear_key_head_dim=12, linear_value_head_dim=20, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
)
CONFIG = dict(PUBLISHED_KEYS, reference_pad_to=32, reference_query_block=32)
MODEL_KEYS = {k: v for k, v in PUBLISHED_KEYS.items() if k != "vocab_size"}
BLOCK, BLOCKS, SLOTS = 4, 160, 3
STATE = (3, 12, 20)  # heads, d_k, d_v


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "olmo_hybrid.py")
    spec = importlib.util.spec_from_file_location("reference_olmo_hybrid", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def weights(ref):
    """(sizes, reference-layout params on the device, the program's tree in
    float32)."""
    sizes = ref.sizes_of(CONFIG)
    host = jax.device_get(ref.make_params(7, sizes))
    tree = jax.tree.map(
        lambda a: jnp.asarray(a).astype(jnp.float32), ref.to_checkpoint_tree(host))
    return sizes, jax.tree.map(jnp.asarray, host), tree


@pytest.fixture(scope="module")
def model():
    return get_model("OlmoHybrid", num_classes=VOCAB, dtype=jnp.float32, **MODEL_KEYS)


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, size=(n,)).astype(np.int32)


def reference_logits(ref, params, tokens, **more):
    pad = -len(tokens) % 32
    padded = jnp.asarray(np.concatenate([tokens, np.zeros((pad,), np.int32)]))
    return np.asarray(ref.logits_one(params, padded, **more))[:len(tokens)]


def test_the_family_states_what_it_is(model):
    cls = model_class("olmohybrid")
    assert cls.is_language_model and cls.takes_logit_cols
    assert "served, not trained" in cls.training_unsupported
    assert model.moe_shape is None               # a dense model
    assert model.state_shape == (3,) + STATE     # layers 0-2 carry a state
    assert model.head_dim == 20                  # null in the config: 60 / 3
    assert model._kinds() == tuple(PERIOD)


def test_the_training_path_refuses_the_family_with_the_reason():
    refusal = model_class("OlmoHybrid").training_unsupported
    for other in ("DeepseekV2", "SolarOpen2", "NemotronH"):
        # one form of words for all four served families
        said = model_class(other).training_unsupported
        assert said.split(":")[0].replace(other, "OlmoHybrid") == refusal.split(":")[0]
        assert said.endswith("pytorch_distributed_training_tpu.serving")


def test_parameters_are_created_in_the_serving_dtype_and_count_as_published():
    """At the toy widths, and (shapes only) at the published ones: a linear
    layer 215.5 M, a full layer 185.8 M, 7.43 B in all."""
    bf16 = get_model("OlmoHybrid", num_classes=VOCAB, dtype=jnp.bfloat16, **MODEL_KEYS)
    shapes = jax.eval_shape(
        lambda: bf16.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert {leaf.dtype for leaf in jax.tree.leaves(shapes["params"])} == {jnp.dtype("bfloat16")}
    gdn = shapes["params"]["layer0"]["gdn"]
    assert gdn["w_qkv"].shape == (60, 3 * (12 + 12 + 20)) and gdn["A_log"].shape == (3,)
    assert shapes["params"]["layer3"]["attn"]["q_norm"].shape == (60,)
    published = get_model(
        "OlmoHybrid", num_classes=100352, dtype=jnp.bfloat16,
        rope_parameters={"rope_theta": None})
    shapes = jax.eval_shape(
        lambda: published.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))  # noqa: E731
    params = shapes["params"]
    assert count(params["layer0"]) == 215_570_172  # 88.75 M mixer + 126.81 M MLP
    assert count(params["layer3"]) == 185_809_920
    assert count(params) == 24 * 215_570_172 + 8 * 185_809_920 + 770_707_200


@pytest.mark.parametrize("key,value", [
    ("rope_parameters", {"rope_theta": 500000.0}), ("hidden_act", "gelu"),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("linear_num_key_heads", 1), ("layer_types", ["sliding_attention"] * 4),
    ("layer_types", PERIOD[:3]),
])
def test_what_is_not_written_is_refused(key, value):
    broken = get_model("OlmoHybrid", num_classes=VOCAB, **dict(MODEL_KEYS, **{key: value}))
    with pytest.raises(ValueError, match=f"OlmoHybrid: model.{key.split('.')[0]}"):
        jax.eval_shape(
            lambda: broken.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))


@pytest.mark.parametrize("length", [7, 64, 150])
def test_full_forward_matches_the_reference(ref, weights, model, length):
    _, params, tree = weights
    tokens = tokens_of(length, seed=length)
    got = model.apply({"params": tree}, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(
        np.asarray(got), reference_logits(ref, params, tokens), atol=TOLERANCE)


@pytest.mark.parametrize("control", ["bf16_state", "int8", "no_delta_term"])
def test_the_tolerance_fails_a_lower_precision_and_a_missing_term(ref, weights, control):
    """A state kept in bfloat16, int8 operands, or an update without
    ``- S'^T k``, lie far outside the tolerance the program is held to."""
    _, params, _ = weights
    tokens = tokens_of(150, seed=150)
    sound = reference_logits(ref, params, tokens)
    more = {"delta": False} if control == "no_delta_term" else {"mode": control}
    broken = reference_logits(ref, params, tokens, **more)
    assert np.abs(broken - sound).max() > 50 * TOLERANCE


def paged(model, weights):
    fns = build_paged_fns(model, BLOCK, BLOCKS, state_slots=SLOTS)
    clone = model.clone(decode=True, paged=True, kv_block_size=BLOCK,
                        kv_num_blocks=BLOCKS, state_slots=SLOTS)
    return fns, clone, fns.init_pool(weights[2])


def test_prefill_then_decode_through_pool_and_state_matches_one_full_forward(
        ref, weights, model):
    """Two rows of unequal lengths, neither a multiple of the scan's chunk
    of 64, prefilled in one padded call into slots 2 and 0; then six decode
    steps a row through the pool AND the state, a padding row riding along,
    and a seventh that slot 2 takes alone: every logit row is the
    reference's full forward over the same tokens, and the state of a slot
    that sits a step out is what it was, bit for bit."""
    _, params, tree = weights
    _, clone, pool = paged(model, weights)
    rows = [tokens_of(150 + 7, seed=1), tokens_of(70 + 7, seed=2)]
    # a bucket of two of the model's query blocks of 128: the scores are built
    # a block of one row at a time
    lens, slots, bucket, table = [150, 70], [2, 0], 256, 64
    tokens = np.zeros((2, bucket), np.int32)
    positions = np.full((2, bucket), -1, np.int32)
    tables = np.stack([np.arange(table), table + np.arange(table)]).astype(np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n], positions[i, :n] = rows[i][:n], np.arange(n)

    def program(**static):
        return jax.jit(lambda pool, *a, **k: clone.apply(
            {"params": tree, "cache": pool}, *a, mutable=["cache"], **static, **k))

    apply, step = program(), program(rows_are_slots=True)
    logits, variables = apply(pool, tokens, positions, tables,
                              state_rows=np.asarray(slots, np.int32))
    want = [reference_logits(ref, params, r) for r in rows]
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits[i, :n]), want[i][:n], atol=TOLERANCE)
    # decode: batch rows are slots; slot 1 is padding (position -1, slot -1)
    step_tables = np.zeros((SLOTS, table), np.int32)
    step_tables[2], step_tables[0] = tables[0], tables[1]
    row_of_slot = {2: 0, 0: 1}
    for k in range(6):
        tok = np.zeros((SLOTS, 1), np.int32)
        pos = np.full((SLOTS, 1), -1, np.int32)
        for slot, i in row_of_slot.items():
            tok[slot, 0], pos[slot, 0] = rows[i][lens[i] + k], lens[i] + k
        state_rows = np.where(pos[:, 0] >= 0, np.arange(SLOTS), -1).astype(np.int32)
        logits, variables = step(variables["cache"], tok, pos, step_tables,
                                 state_rows=state_rows)
        for slot, i in row_of_slot.items():
            np.testing.assert_allclose(
                np.asarray(logits[slot, 0]), want[i][lens[i] + k], atol=TOLERANCE)
    tok, pos = np.zeros((SLOTS, 1), np.int32), np.full((SLOTS, 1), -1, np.int32)
    tok[2, 0], pos[2, 0] = rows[0][lens[0] + 6], lens[0] + 6
    before = jax.tree_util.tree_flatten_with_path(
        jax.device_get(variables["cache"]))[0]
    logits, variables = step(variables["cache"], tok, pos, step_tables,
                             state_rows=np.asarray([-1, -1, 2], np.int32))
    np.testing.assert_allclose(
        np.asarray(logits[2, 0]), want[0][lens[0] + 6], atol=TOLERANCE)
    assert np.isfinite(np.asarray(logits)).all()
    after = jax.tree_util.tree_flatten_with_path(variables["cache"])[0]
    states = 0
    for (path, old), (_, new) in zip(before, after):
        if is_state_leaf(path):
            states += 1
            np.testing.assert_array_equal(old[:2], np.asarray(new)[:2])
            assert (old[2] != np.asarray(new)[2]).any()
    assert states == 2 * 3  # state and convolution rows of three linear layers


def recurrence_inputs(length, seed=None):
    keys = jax.random.split(jax.random.PRNGKey(length if seed is None else seed), 6)
    b, (h, dk, dv) = 2, STATE
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, length, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, length, h, dk)))
    v = jax.random.normal(keys[2], (b, length, h, dv))
    log_decay = -0.3 * jax.nn.softplus(jax.random.normal(keys[3], (b, length, h)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, length, h)))
    state = jax.random.normal(keys[5], (b, h, dk, dv))
    return q, k, v, log_decay, beta, state


@pytest.mark.parametrize("length", [1, 64, 150])
def test_the_scalar_scan_is_the_step_by_step_recurrence(length):
    """With a state carried IN and d_k != d_v: the chunked form over
    ``length`` positions equals ``length`` one-position updates of the
    shared :func:`delta_rule_step`, outputs and final state."""
    q, k, v, log_decay, beta, state = recurrence_inputs(length)
    outs, carried = [], state
    for t in range(length):
        out, carried = delta_rule_step(
            q[:, t], k[:, t], v[:, t], log_decay[:, t, :, None], beta[:, t], carried)
        outs.append(out)
    got, final = delta_rule_chunked_scalar(q, k, v, log_decay, beta, state)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(outs, 1)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(final), np.asarray(carried), atol=2e-5)


@pytest.mark.parametrize("length,chunk", [(64, 64), (150, 64), (150, 16)])
def test_the_scalar_scan_is_the_channel_wise_scan_fed_one_decay_a_head(length, chunk):
    q, k, v, log_decay, beta, state = recurrence_inputs(length, seed=length + chunk)
    every_channel = jnp.broadcast_to(log_decay[..., None], q.shape)
    want, want_state = delta_rule_chunked(q, k, v, every_channel, beta, state, chunk=chunk)
    got, final = delta_rule_chunked_scalar(q, k, v, log_decay, beta, state, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(final), np.asarray(want_state), atol=2e-5)


def test_padding_positions_change_neither_state_nor_output_of_the_scan():
    q, k, v, log_decay, beta, state = recurrence_inputs(40)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 30)) + ((0, 0),) * (x.ndim - 2))  # noqa: E731
    got, final = delta_rule_chunked_scalar(q, k, v, log_decay, beta, state)
    padded, padded_final = delta_rule_chunked_scalar(
        *(pad(x) for x in (q, k, v, log_decay, beta)), state)
    np.testing.assert_allclose(np.asarray(padded[:, :40]), np.asarray(got), atol=1e-6)
    np.testing.assert_allclose(np.asarray(padded_final), np.asarray(final), atol=1e-6)


@pytest.mark.parametrize("live_rows", [[1], [0, 2, 3]])
def test_the_decode_step_walks_the_live_rows(live_rows):
    """The layer alone, four slots, ``rows_are_slots``: a dead row's state
    and convolution rows are untouched bit for bit and its output is zeros;
    a fresh row (position 0) starts from zero whatever its slot held; an old
    row continues from what its slot held.  One live row takes the walk,
    three of four the dense pass (``WALK_SHARE``)."""
    slots, (h, dk, dv) = 4, STATE
    layer = GatedDeltaNet(num_heads=h, key_dim=dk, value_dim=dv, decode=True,
                          state_slots=slots)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((slots, 1, 24)), jnp.float32)
    zeros = {"params": layer.init(
        jax.random.PRNGKey(0), x, jnp.zeros((slots, 1), jnp.int32),
        jnp.arange(slots, dtype=jnp.int32))["params"]}
    dirty = {
        "gdn_state": jnp.asarray(rng.standard_normal((slots, h, dk, dv)), jnp.float32),
        "gdn_conv": jnp.asarray(
            rng.standard_normal((slots, 3, h * (2 * dk + dv))), jnp.float32),
    }
    live = np.isin(np.arange(slots), live_rows)
    fresh = live_rows[0]  # this row starts a sequence; the other live ones go on
    positions = np.where(live, 5, -1).astype(np.int32)
    positions[fresh] = 0
    state_rows = np.where(live, np.arange(slots), -1).astype(np.int32)

    def step(cache):
        return layer.apply(
            dict(zeros, cache=cache), x, positions[:, None], state_rows,
            rows_are_slots=True, mutable=["cache"])

    y, out = step(dirty)
    clean = {name: leaf.at[fresh].set(0) for name, leaf in dirty.items()}
    y_clean, out_clean = step(clean)
    for name in dirty:
        got = np.asarray(out["cache"][name])
        np.testing.assert_array_equal(got[~live], np.asarray(dirty[name])[~live])
        assert (got[live] != np.asarray(dirty[name])[live]).any()
        # what the fresh row's slot held counts for nothing
        np.testing.assert_array_equal(got[fresh], np.asarray(out_clean["cache"][name])[fresh])
    np.testing.assert_array_equal(np.asarray(y)[~live], 0)
    np.testing.assert_array_equal(np.asarray(y)[fresh], np.asarray(y_clean)[fresh])
    assert np.abs(np.asarray(y)[live]).min(axis=(1, 2)).all()


def scheduler(model, tree, **more):
    args = dict(slots=1, block_size=BLOCK, num_blocks=BLOCKS, prefix_cache=False,
                batch_buckets=[1], seq_buckets=[16, 32], max_new_tokens=6, start=False)
    return ContinuousScheduler(model, tree, **dict(args, **more))


def serve(sched, prompt):
    future = sched.submit(prompt)
    while not future.done():
        sched.tick()
    return future.result()["tokens"]


def test_two_arrivals_in_one_tick_through_the_scheduler_are_the_reference_s_forward(
        ref, weights, model):
    """Two requests of unequal length waiting when the tick comes are ONE
    padded prefill of 4 rows x 32 positions, each row's state taken at its
    own last position; then decode steps side by side on the ring, ONE
    ``decode_step`` program.  Every served token is the reference's first
    choice over prompt + served tokens (its full forward: no cache, no
    chunks), by a margin the tolerance cannot close."""
    from pytorch_distributed_training_tpu.telemetry.spans import SpanRecorder, set_recorder

    _, params, tree = weights
    prompts = [tokens_of(27, seed=21), tokens_of(9, seed=22)]
    rec = set_recorder(SpanRecorder(ring=512))
    try:
        with scheduler(model, tree, slots=4, batch_buckets=[1, 4],
                       async_depth=1) as sched:  # what an engine serves
            futures = [sched.submit(p) for p in prompts]
            while not all(f.done() for f in futures):
                sched.tick()
            snapshot = sched.metrics.snapshot()
            assert sched._fns.decode_step._cache_size() == 1
    finally:
        set_recorder(None)
    prefills = [s for s in rec.recent() if s["kind"] == "prefill"]
    assert [(s["rows"], s["bucket"]) for s in prefills] == [(2, 32)]
    assert "moe_experts_hit_count" not in snapshot  # a dense model records none
    assert snapshot["state_live_row_share_mean"] > 0
    assert snapshot["decode_steps_overlapped"] > 0  # the ring of depth 1
    for prompt, future in zip(prompts, futures):
        served = future.result()["tokens"]
        assert len(served) == 6
        seq = np.concatenate([prompt, served[:-1]])
        rows = reference_logits(ref, params, seq)[len(prompt) - 1:]
        np.testing.assert_array_equal(rows.argmax(-1), served)
        best_two = np.sort(rows, axis=-1)[:, -2:]
        assert (best_two[:, 1] - best_two[:, 0]).min() > 10 * TOLERANCE


def test_a_full_pool_makes_the_queue_s_head_wait_and_the_snapshot_counts_it(
        weights, model):
    """The pool holds one request's footprint (32 + 6 positions = 10 blocks)
    and not two: the second waits at ``KVPool.admit`` until the first
    retires, is then served what a fresh engine serves, and
    ``admission_waits`` in the snapshot says so."""
    tree = weights[2]
    first, second = tokens_of(30, seed=3), tokens_of(29, seed=4)
    with scheduler(model, tree, slots=2, batch_buckets=[1, 2], num_blocks=12) as tight, \
            scheduler(model, tree) as fresh:
        futures = [tight.submit(first), tight.submit(second)]
        while not all(f.done() for f in futures):
            tight.tick()
        snapshot = tight.metrics.snapshot()
        np.testing.assert_array_equal(futures[1].result()["tokens"], serve(fresh, second))
    assert snapshot["admission_waits"] >= 1


def test_a_slot_reused_by_a_second_request_gives_what_a_fresh_engine_gives(weights, model):
    """The one slot's state is never cleared: the second request's prefill
    starts at position 0 and therefore from a zero state."""
    tree = weights[2]
    first, second = tokens_of(23, seed=3), tokens_of(9, seed=4)
    with scheduler(model, tree) as used, scheduler(model, tree) as fresh:
        serve(used, first)
        np.testing.assert_array_equal(serve(used, second), serve(fresh, second))


def test_the_cache_tree_holds_both_kinds_of_leaf_and_the_step_three_outputs(weights, model):
    """K/V pairs of 3 heads in the one full layer's pool leaves, state and
    convolution rows a linear layer in ``[slots, ...]`` leaves told by their
    names; ``copy_rows`` passes the state by; a dense model's decode program
    returns token, finite flag and cache, no expert counts."""
    fns, _, pool = paged(model, weights)
    flat = jax.tree_util.tree_flatten_with_path(pool)[0]
    shapes = {}
    for path, leaf in flat:
        kind = "state" if is_state_leaf(path) else pool_leaf_role(path, leaf, BLOCK * BLOCKS)
        shapes.setdefault(kind, []).append(leaf.shape)
    assert shapes["scored"] == shapes["value"] == [(BLOCK * BLOCKS, 3, 20)]
    assert sorted(shapes["state"]) == sorted(
        [(SLOTS,) + STATE] * 3 + [(SLOTS, 3, 3 * (12 + 12 + 20))] * 3)
    rng = np.random.default_rng(5)
    pool = jax.tree.map(
        lambda leaf: jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype), pool)
    before = jax.tree.map(np.asarray, pool)
    rows = np.arange(8, dtype=np.int32)
    after = fns.copy_rows(pool, rows, rows + 100)
    for (path, old), (_, new) in zip(
            jax.tree_util.tree_flatten_with_path(before)[0],
            jax.tree_util.tree_flatten_with_path(after)[0]):
        if is_state_leaf(path):
            np.testing.assert_array_equal(old, np.asarray(new))
        else:
            np.testing.assert_array_equal(np.asarray(new)[100:108], old[:8])
    pad = np.full((SLOTS,), -1, np.int32)
    zeros = np.zeros((SLOTS,), np.int32)
    out = fns.decode_step(
        weights[2], after, zeros, np.ones((SLOTS,), bool), zeros, pad,
        np.zeros((SLOTS, 40), np.int32), jnp.stack([jax.random.PRNGKey(0)] * SLOTS),
        zeros, pad, pad)
    assert len(out) == 3


@pytest.mark.parametrize("what", ["prefix_cache", "draft_model", "kv_transfer",
                                  "contiguous_generate"])
def test_what_assumes_a_cache_of_token_rows_refuses_the_model(weights, model, what):
    """Each with its reason; no silent fallback."""
    tree = weights[2]
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="prefix_cache.*cannot restore the state"):
            scheduler(model, tree, prefix_cache=True)
    elif what == "draft_model":
        from pytorch_distributed_training_tpu.serving.speculative import SpeculativeSpec

        with pytest.raises(ValueError, match="speculative.*rejected draft token"):
            scheduler(model, tree, speculative=SpeculativeSpec(2))
    elif what == "kv_transfer":
        with scheduler(model, tree) as sched:
            with pytest.raises(ValueError, match="kv_transfer.*token rows, not the state"):
                sched.export_kv_prefix([1, 2, 3])
    else:
        with pytest.raises(ValueError, match="contiguous generate path has no slots"):
            build_generate_fn(model, 4)

def test_a_prefill_call_that_starts_past_position_zero_is_refused(weights, model):
    import paged_programs

    with scheduler(model, weights[2]) as sched:
        paged_programs.check_a_call_past_position_zero_is_refused(
            sched, tokens_of(11, seed=6), BLOCK, "OlmoHybridLM")


def test_a_served_run_counts_its_flash_prefill_calls(weights, model, monkeypatch):
    """Where the backend runs the kernel and a bucket is a shape it takes,
    a prefill call's full layers score through the flash forward over the
    call's own K/V (interpreted here): the ``prefill`` span says how many
    (``flash_layers``, the cache tree's one K pool of this cut), the counter
    ``prefill_flash_calls`` counts such calls beside ``prefill_calls``, and
    the tokens are the gather arm's.  A bucket of 16 is no shape of the
    kernel's: its calls keep the gather arm and count nothing."""
    from pytorch_distributed_training_tpu.ops import attention
    from pytorch_distributed_training_tpu.ops import flash_attention as gate
    from pytorch_distributed_training_tpu.telemetry.spans import get_recorder

    tree = weights[2]
    short, long = tokens_of(11, seed=6), tokens_of(40, seed=7)
    more = dict(seq_buckets=[16, 128], num_blocks=BLOCKS)
    with scheduler(model, tree, **more) as plain:
        want = [serve(plain, short), serve(plain, long)]
        assert plain._flash_layers(128) == 0
        assert "prefill_flash_calls" not in plain.metrics.snapshot()
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_INTERPRET", True)
    with scheduler(model, tree, **more) as sched:
        assert [sched._flash_layers(sb) for sb in (16, 128)] == [0, 1]
        got = [serve(sched, short), serve(sched, long)]
        snap = sched.metrics.snapshot()
        spans = [s for s in get_recorder().recent()
                 if s["kind"] == "prefill" and s.get("bucket") in (16, 128)][-2:]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert snap["prefill_calls"] == 2 and snap["prefill_flash_calls"] == 1
    assert [(s["bucket"], s["flash_layers"]) for s in spans] == [(16, 0), (128, 1)]


def test_the_prefill_program_alone_holds_the_flash_forward(weights, model, monkeypatch):
    import paged_programs

    with scheduler(model, weights[2], seq_buckets=[16, 128]) as sched:
        paged_programs.check_prefill_alone_holds_the_flash_forward(sched, 1, monkeypatch)



def test_replay_after_a_restart_rebuilds_the_state_from_position_zero(weights, model):
    """A hot restart re-prefills the prompt and re-feeds the delivered
    tokens: the continuation is the undisturbed run's."""
    tree = weights[2]
    prompt = tokens_of(11, seed=6)
    with scheduler(model, tree) as calm, scheduler(model, tree) as shaken:
        want = serve(calm, prompt)
        future = shaken.submit(prompt)
        for _ in range(3):
            shaken.tick()
        shaken._rebuild_and_requeue()
        while not future.done():
            shaken.tick()
        np.testing.assert_array_equal(future.result()["tokens"], want)
        assert shaken.metrics.snapshot().get("replay_parity_mismatch", 0) == 0


def test_the_qk_norm_is_off_unless_asked_for():
    """The field added to the shared attention module is static and off by
    default: without it the module has the parameters it had."""
    x = jnp.zeros((1, 4, 24))
    names = lambda **more: sorted(GroupedQueryAttention(  # noqa: E731
        num_heads=2, num_kv_heads=2, head_dim=8, gate=False, **more,
    ).init(jax.random.PRNGKey(0), x)["params"])
    assert names() == ["wk", "wo", "wq", "wv"]
    assert names(qk_norm=True) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]


def test_more_than_a_tile_of_heads_is_stored_in_whole_tiles():
    """12 K/V heads take the pool rows of 16 (30 take 32 at the published
    widths); the spare heads are never written and never scored: prefill and
    a decode step through the pool give the plain causal forward."""
    from pytorch_distributed_training_tpu.ops.attention import _stored_heads

    assert [_stored_heads(n) for n in (1, 2, 8, 12, 16, 30)] == [1, 2, 8, 16, 16, 32]
    layer = GroupedQueryAttention(num_heads=12, num_kv_heads=12, head_dim=8, gate=False)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 9, 24)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    want = layer.apply({"params": params}, x)
    paged_layer = layer.clone(decode=True, paged=True, kv_block_size=4, kv_num_blocks=8)
    positions = np.broadcast_to(np.arange(8, dtype=np.int32), (2, 8))
    tables = np.arange(8, dtype=np.int32).reshape(2, 4)
    got, cache = paged_layer.apply(
        {"params": params}, x[:, :8], positions, tables, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, :8]), atol=1e-5)
    leaves = jax.tree.leaves(cache["cache"])
    assert [leaf.shape for leaf in leaves] == [(32, 16, 8)] * 2
    assert all((np.asarray(leaf)[:, 12:] == 0).all() for leaf in leaves)
    got, _ = paged_layer.apply(
        {"params": params, "cache": cache["cache"]}, x[:, 8:],
        np.full((2, 1), 8, np.int32), tables, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 8:]), atol=1e-5)


def test_the_serve_config_runs_through_the_cli_at_the_toy_size(tmp_path, capsys):
    """config/serve-olmo-hybrid-7b.yml names the published keys; with the
    widths swapped for the toy's it is served end to end by ``python -m
    ...serving``: engine, scheduler, paged pool, state leaves."""
    from pytorch_distributed_training_tpu.serving.__main__ import main

    with open(os.path.join(ROOT, "config", "serve-olmo-hybrid-7b.yml")) as fp:
        cfg = yaml.safe_load(fp)
    published = {k: v for k, v in cfg["model"].items() if k != "name"}
    assert published["num_hidden_layers"] == 16
    assert set(published) == set(MODEL_KEYS)
    assert get_model("OlmoHybrid", num_classes=100352, **published).state_shape == (
        12, 30, 96, 192)
    assert cfg["serving"]["scheduler"]["prefix_cache"] is False
    cfg["dataset"]["n_classes"] = VOCAB
    cfg["model"] = dict(MODEL_KEYS, name="OlmoHybrid")
    cfg["serving"].update(dtype="float32", max_batch_size=2, batch_buckets=[1, 2],
                          seq_buckets=[8, 16], max_new_tokens=4)
    cfg["serving"]["scheduler"].update(slots=2, block_size=BLOCK, num_blocks=16)
    path = tmp_path / "serve.yml"
    path.write_text(yaml.safe_dump(cfg))
    assert main(["--config", str(path), "--requests", "4", "--log-dir", str(tmp_path)]) == 0
    snap = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["serving"]
    assert snap["retired"] == 4 and snap["state_live_row_share_mean"] > 0
    assert snap["decode_steps_overlapped"] > 0  # the ring of depth 1, by default
