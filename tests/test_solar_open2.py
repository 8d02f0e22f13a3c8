"""The Solar-Open2 family (models/solar_open2.py, ops/kda.py, ops/attention.py::
GroupedQueryAttention, ops/moe.py::DroplessMoE) against the benchmark's plain
reference (benchmark/reference/solar_open2.py) at a toy size on the CPU:
hidden 64, 4 query / 2 K/V heads of 16, KDA 4 heads x 16, 16 experts top-4 of
which this share holds 8, layers GQA + 3 KDA (one period).

The reference is float32 at ``highest``, has no cache and no chunks (the
recurrence runs a position at a time) and shares no code with the program;
the weights are its ``make_params(seed)`` handed over through its
``to_checkpoint_tree``, as the benchmark hands them over.  Logits are
compared, never tokens.  TOLERANCE 2e-4 on logits of magnitude about 4:
both sides are float32 on the CPU and differ in the order of their sums (a
chunk's triangular system against a position at a time; grouped products
against a loop over experts): 1e-5 was read, 2e-4 leaves ten times that and
is two orders below what a bfloat16 state (0.07) or a missing delta term (4)
reads, which the tests below hold it to.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models import get_model, model_class
from pytorch_distributed_training_tpu.ops.attention import is_state_leaf, pool_leaf_role
from pytorch_distributed_training_tpu.ops.kda import delta_rule_chunked, delta_rule_step
from pytorch_distributed_training_tpu.serving.decode import (
    build_generate_fn, build_paged_fns,
)
from pytorch_distributed_training_tpu.serving.scheduler import ContinuousScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 512
TOLERANCE = 2e-4
LINEAR = dict(short_conv_kernel_size=4, head_dim=16, num_heads=4, num_kv_heads=None)
CONFIG = dict(
    model_type="solar_open2", hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_attn_config=LINEAR, intermediate_size=128, moe_intermediate_size=32,
    rms_norm_eps=1e-5, rope_theta=10000, partial_rotary_factor=1,
    tie_word_embeddings=False, max_position_embeddings=256,
    first_k_dense_replace=0, use_rope=False, gqa_interval=3, gqa_layers=[0, 4, 8],
    use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
    n_routed_experts=8, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1, num_experts_per_tok=4, vocab_size=VOCAB,
    reference_pad_to=32, reference_query_block=32,
    assumed={"router_logit_std": 2.0},
    serve={"model": {"n_routed_experts": 16, "experts_held": [4, 8]}},
)
MODEL_KEYS = dict(
    {k: v for k, v in CONFIG.items()
     if k not in ("assumed", "vocab_size", "serve", "reference_pad_to",
                  "reference_query_block")},
    **CONFIG["serve"]["model"])
BLOCK, BLOCKS, SLOTS = 4, 96, 3


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "solar_open2.py")
    spec = importlib.util.spec_from_file_location("reference_solar_open2", path)
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
    return get_model("SolarOpen2", num_classes=VOCAB, dtype=jnp.float32, **MODEL_KEYS)


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, size=(n,)).astype(np.int32)


def reference_logits(ref, params, tokens, **more):
    pad = -len(tokens) % 32
    padded = jnp.asarray(np.concatenate([tokens, np.zeros((pad,), np.int32)]))
    return np.asarray(ref.logits_one(params, padded, **more))[:len(tokens)]


def test_the_family_states_what_it_is(model):
    cls = model_class("solaropen2")
    assert cls.is_language_model and cls.takes_logit_cols
    assert "served, not trained" in cls.training_unsupported
    assert model.moe_shape == (4, 4, 8)          # every layer has experts
    assert model.state_shape == (3, 4, 16, 16)   # layers 1-3 carry a state
    assert [model._is_full_layer(i) for i in range(4)] == [True, False, False, False]


def test_parameters_are_created_in_the_serving_dtype():
    bf16 = get_model("SolarOpen2", num_classes=VOCAB, dtype=jnp.bfloat16, **MODEL_KEYS)
    shapes = jax.eval_shape(
        lambda: bf16.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert {leaf.dtype for leaf in jax.tree.leaves(shapes["params"])} == {jnp.dtype("bfloat16")}
    moe = shapes["params"]["layer2"]["moe"]
    assert moe["router"].shape == (64, 16) and moe["w_down"].shape == (8, 32, 64)


@pytest.mark.parametrize("length", [7, 64, 150])
def test_full_forward_matches_the_reference(ref, weights, model, length):
    _, params, tree = weights
    tokens = tokens_of(length, seed=length)
    got = model.apply({"params": tree}, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(
        np.asarray(got), reference_logits(ref, params, tokens), atol=TOLERANCE)


@pytest.mark.parametrize("control", ["bf16_state", "no_delta_term"])
def test_the_tolerance_fails_a_lower_precision_and_a_missing_term(ref, weights, control):
    """A state kept in bfloat16, or an update without ``- S'^T k``, lies far
    outside the tolerance the program is held to."""
    _, params, _ = weights
    tokens = tokens_of(150, seed=150)
    sound = reference_logits(ref, params, tokens)
    more = {"mode": "bf16_state"} if control == "bf16_state" else {"delta": False}
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
    of 64, prefilled in one call into slots 2 and 0; then six decode steps a
    row through the pool AND the state, a padding row riding along, and a
    seventh that slot 2 takes alone: every logit row is the reference's full forward over the same tokens."""
    _, params, tree = weights
    _, clone, pool = paged(model, weights)
    rows = [tokens_of(150 + 7, seed=1), tokens_of(70 + 7, seed=2)]
    lens, slots, bucket, table = [150, 70], [2, 0], 160, 40
    tokens = np.zeros((2, bucket), np.int32)
    positions = np.full((2, bucket), -1, np.int32)
    tables = np.stack([np.arange(table), table + np.arange(table)]).astype(np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n], positions[i, :n] = rows[i][:n], np.arange(n)
    def program(**static):
        return jax.jit(lambda pool, *a, **k: clone.apply(
            {"params": tree, "cache": pool}, *a, mutable=["cache", "moe_stats"],
            **static, **k))

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
    # one step more with most rows dead: slot 2 alone lives, and the state
    # leaves of the two slots that sit it out are what they were, bit for bit
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
    for (path, old), (_, new) in zip(before, after):
        if is_state_leaf(path):
            np.testing.assert_array_equal(old[:2], np.asarray(new)[:2])
            assert (old[2] != np.asarray(new)[2]).any()


@pytest.mark.parametrize("length", [1, 64, 150])
def test_chunked_scan_is_the_step_by_step_recurrence(length):
    """With a state carried IN: the chunked form over ``length`` positions
    equals ``length`` one-position updates, outputs and final state."""
    keys = jax.random.split(jax.random.PRNGKey(length), 6)
    b, h, d = 2, 4, 16
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, length, h, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, length, h, d)))
    v = jax.random.normal(keys[2], (b, length, h, d))
    log_decay = -0.3 * jax.nn.softplus(jax.random.normal(keys[3], (b, length, h, d)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, length, h)))
    state = jax.random.normal(keys[5], (b, h, d, d))
    outs, carried = [], state
    for t in range(length):
        out, carried = delta_rule_step(
            q[:, t], k[:, t], v[:, t], log_decay[:, t], beta[:, t], carried)
        outs.append(out)
    got, final = delta_rule_chunked(q, k, v, log_decay, beta, state, chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(outs, 1)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(final), np.asarray(carried), atol=2e-5)


def scheduler(model, tree, **more):
    args = dict(slots=1, block_size=BLOCK, num_blocks=BLOCKS, prefix_cache=False,
                batch_buckets=[1], seq_buckets=[16, 32], max_new_tokens=6, start=False)
    return ContinuousScheduler(model, tree, **dict(args, **more))


def serve(sched, prompt):
    future = sched.submit(prompt)
    while not future.done():
        sched.tick()
    return future.result()["tokens"]


def test_every_decode_step_leaves_its_expert_counts_in_a_span(weights, model):
    """``moe_counts`` (hit = held experts that got a token, summed over the
    four layers; rows = live rows): what a reader of traced seconds takes in
    place of the whole run's histogram."""
    from pytorch_distributed_training_tpu.telemetry.spans import SpanRecorder, set_recorder

    rec = set_recorder(SpanRecorder(ring=256))
    try:
        with scheduler(model, weights[2]) as sched:
            serve(sched, tokens_of(9, seed=4))
            steps = sched.metrics.snapshot()["moe_experts_hit_count"]
    finally:
        set_recorder(None)
    counts = [s for s in rec.recent() if s["kind"] == "moe_counts"]
    assert len(counts) == steps > 0
    held = model.moe_shape[2]
    assert all(s["rows"] == 1 and 0 <= s["hit"] <= 4 * min(held, 4) for s in counts)


def test_the_share_of_live_state_rows_is_observed_a_decode_step(weights, model):
    """``state_live_row_share``: of the slots of a ``[slots, ...]`` state
    leaf, those a decode step's rows live in, which is what the step's walk
    (``ops/state_rows.py``) reads and writes.  One request in four slots."""
    with scheduler(model, weights[2], slots=4, batch_buckets=[1, 4]) as sched:
        assert sched._state_live_row_share(np.asarray([7, -1, 0, -1])) == 0.5
        assert "state_live_row_share_mean" not in sched.metrics.snapshot()
        serve(sched, tokens_of(9, seed=4))
        snapshot = sched.metrics.snapshot()
    assert snapshot["state_live_row_share_mean"] == pytest.approx(0.25)
    assert snapshot["state_live_row_share_p50"] == pytest.approx(0.25)


def test_a_slot_reused_by_a_second_request_gives_what_a_fresh_engine_gives(weights, model):
    """The one slot's state is never cleared: the second request's prefill
    starts at position 0 and therefore from a zero state."""
    tree = weights[2]
    first, second = tokens_of(23, seed=3), tokens_of(9, seed=4)
    with scheduler(model, tree) as used, scheduler(model, tree) as fresh:
        serve(used, first)
        np.testing.assert_array_equal(serve(used, second), serve(fresh, second))


@pytest.mark.parametrize("what", ["copy_rows", "padding_rows"])
def test_a_state_leaf_is_untouched_by(weights, model, what):
    fns, _, pool = paged(model, weights)
    rng = np.random.default_rng(5)
    pool = jax.tree.map(
        lambda leaf: jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype), pool)
    before = jax.tree.map(np.asarray, pool)
    if what == "copy_rows":
        rows = np.arange(8, dtype=np.int32)
        after = fns.copy_rows(pool, rows, rows + 100)
    else:
        # a decode step in which every row is padding: position -1, slot -1
        pad = np.full((SLOTS,), -1, np.int32)
        keys = jnp.stack([jax.random.PRNGKey(0)] * SLOTS)
        zeros = np.zeros((SLOTS,), np.int32)
        _, _, after, _ = fns.decode_step(
            weights[2], pool, zeros, np.ones((SLOTS,), bool), zeros, pad,
            np.zeros((SLOTS, 40), np.int32), keys, zeros, pad, pad)
    flat_before = jax.tree_util.tree_flatten_with_path(before)[0]
    flat_after = jax.tree_util.tree_flatten_with_path(after)[0]
    states = 0
    for (path, old), (_, new) in zip(flat_before, flat_after):
        if is_state_leaf(path):
            states += 1
            assert pool_leaf_role(path, old, BLOCK * BLOCKS) is None
            np.testing.assert_array_equal(old, np.asarray(new))
        elif what == "copy_rows":
            # the pool's rows were copied: the programs tell the two apart
            np.testing.assert_array_equal(np.asarray(new)[100:108], old[:8])
    assert states == 2 * 3  # state and convolution rows of three KDA layers


def test_a_decode_row_that_names_another_slot_is_answered_with_nan(weights, model):
    """The decode program's step (``rows_are_slots``) reads and writes the
    state where it lies: row i is slot i.  A live row that names another
    slot is not served something else: its output is NaN, which the serving
    programs' output guard evicts."""
    fns, _, pool = paged(model, weights)
    keys = jnp.stack([jax.random.PRNGKey(0)] * SLOTS)
    zeros = np.zeros((SLOTS,), np.int32)
    pos = np.asarray([5, 5, -1], np.int32)
    tables = np.tile(np.arange(40, dtype=np.int32), (SLOTS, 1))
    crossed = np.asarray([0, 2, -1], np.int32)  # row 1 names slot 2
    _, finite, _, _ = fns.decode_step(
        weights[2], pool, zeros, np.ones((SLOTS,), bool), zeros, pos, tables,
        keys, zeros, np.full((SLOTS,), -1, np.int32), crossed)
    assert list(np.asarray(finite)[:2]) == [True, False]


def test_the_fixed_width_step_is_stated_not_inferred(weights, model):
    """``rows_are_slots`` is the caller's statement: a call of another width
    is refused at trace time, and without the flag a one-position call over
    as many rows as slots is addressed by ``state_rows`` like any other."""
    _, clone, pool = paged(model, weights)
    variables = {"params": weights[2], "cache": pool}
    one = np.zeros((1, 1), np.int32)
    with pytest.raises(ValueError, match="rows_are_slots is the decode step"):
        clone.apply(variables, one, one, np.zeros((1, 40), np.int32),
                    state_rows=np.zeros((1,), np.int32), rows_are_slots=True,
                    mutable=["cache", "moe_stats"])
    # rows 0..2 name slots 2, 0, 1: crossed, and served (finite) all the same
    wide = np.zeros((SLOTS, 1), np.int32)
    tables = np.tile(np.arange(40, dtype=np.int32), (SLOTS, 1))
    logits, _ = clone.apply(variables, wide, wide + 5, tables,
                            state_rows=np.asarray([2, 0, 1], np.int32),
                            mutable=["cache", "moe_stats"])
    assert np.isfinite(np.asarray(logits)).all()


def test_a_state_leaf_is_told_by_its_name_not_by_its_size(weights, model):
    """As many slots as pool rows: the leading sizes coincide, the roles do
    not."""
    fns = build_paged_fns(model, BLOCK, 2, state_slots=BLOCK * 2)
    pool = fns.init_pool(weights[2])
    roles = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        assert leaf.shape[0] == BLOCK * 2
        roles.setdefault(pool_leaf_role(path, leaf, BLOCK * 2), []).append(
            is_state_leaf(path))
    assert roles[None] == [True] * 6
    assert roles["scored"] == [False] and roles["value"] == [False]


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
        from pytorch_distributed_training_tpu.serving.disagg import DisaggFleet

        with scheduler(model, tree) as sched:
            for verb, arg in ((sched.export_kv_prefix, [1, 2, 3]),
                              (sched.export_kv_refs, [1, 2, 3]),
                              (sched.import_kv_blocks, [])):
                with pytest.raises(ValueError, match="kv_transfer.*token rows, not the state"):
                    verb(arg)

            class Fleet:
                replicas, router, replica_factory = [sched], None, None

            with pytest.raises(ValueError, match="serving.disagg"):
                DisaggFleet(Fleet(), prefill_replicas=[sched])
    else:
        with pytest.raises(ValueError, match="contiguous generate path has no slots"):
            build_generate_fn(model, 4)

def test_a_prefill_call_that_starts_past_position_zero_is_refused(weights, model):
    import paged_programs

    with scheduler(model, weights[2]) as sched:
        paged_programs.check_a_call_past_position_zero_is_refused(
            sched, tokens_of(11, seed=6), BLOCK, "SolarOpen2LM")


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
