"""The Nemotron-H family (models/nemotron_h.py, ops/mamba2.py, ops/moe.py::
DroplessMoE in its latent form, ops/attention.py::GroupedQueryAttention at
2 K/V heads) against the benchmark's plain reference
(benchmark/reference/nemotron_h.py) at the toy size of tests/nemotron_toy.py:
the first 11 layers of the published pattern, ``MEMEMEM*EME``.

The reference is float32 at ``highest``, has no cache and no chunks (the
recurrence runs a position at a time) and shares no code with the program;
the weights are its ``make_params(seed)`` handed over through its
``to_checkpoint_tree``, as the benchmark hands them over.  Logits are
compared, never tokens, except through the scheduler, which returns tokens.
TOLERANCE 1e-4 on logits of magnitude about 4: both sides are float32 on the
CPU and differ in the order of their sums (a chunk's products against a
position at a time; grouped products against a loop over experts): 7e-6 was
read, 1e-4 leaves fourteen times that and is fifty times below what a state
kept in bfloat16 reads (5e-3 and more from 40 positions on) and four orders
below an int8 product (1.6-2.9), which a test below holds it to.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from nemotron_toy import CONFIG, MODEL_KEYS, VOCAB, load_reference

from pytorch_distributed_training_tpu.models import get_model, model_class
from pytorch_distributed_training_tpu.ops.attention import is_state_leaf, pool_leaf_role
from pytorch_distributed_training_tpu.serving.decode import (
    build_generate_fn, build_paged_fns,
)
from pytorch_distributed_training_tpu.serving.scheduler import ContinuousScheduler

TOLERANCE = 1e-4
BLOCK, BLOCKS, SLOTS = 4, 96, 3


@pytest.fixture(scope="module")
def ref():
    return load_reference()


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
    return get_model("NemotronH", num_classes=VOCAB, dtype=jnp.float32, **MODEL_KEYS)


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, size=(n,)).astype(np.int32)


def reference_logits(ref, params, tokens, **more):
    pad = -len(tokens) % 32
    padded = jnp.asarray(np.concatenate([tokens, np.zeros((pad,), np.int32)]))
    return np.asarray(ref.logits_one(params, padded, **more))[:len(tokens)]


def test_the_family_states_what_it_is(model):
    cls = model_class("nemotronh")
    assert cls.is_language_model and cls.takes_logit_cols
    assert "served, not trained" in cls.training_unsupported
    assert model.pattern == "MEMEMEM*EME"
    assert model.moe_shape == (5, 6, 4)          # five E layers, top-6, 4 held
    assert model.state_shape == (5, 16, 8, 16)   # five M layers carry a state
    # a layer is a mixer OR a feed-forward part, never a pair
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    kinds = {"M": "mamba", "E": "moe", "*": "attn"}
    for i, kind in enumerate(model.pattern):
        assert sorted(shapes[f"layer{i}"]) == sorted(["norm", kinds[kind]])
    # the multi-token-prediction module is carried in the config and not built
    assert sorted(shapes) == sorted(
        [f"layer{i}" for i in range(11)] + ["tok_embedding", "norm", "head"])


def test_parameters_are_created_in_the_serving_dtype():
    bf16 = get_model("NemotronH", num_classes=VOCAB, dtype=jnp.bfloat16, **MODEL_KEYS)
    shapes = jax.eval_shape(
        lambda: bf16.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    moe = shapes["layer1"]["moe"]
    # all in the serving dtype but the correction bias, float32 as published
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        bias = "e_score_correction_bias" in jax.tree_util.keystr(path)
        assert leaf.dtype == (jnp.float32 if bias else jnp.bfloat16), path
    assert moe["e_score_correction_bias"].shape == (16,)
    # the router reads the full width and all 16; the held experts are latent
    assert moe["router"].shape == (64, 16) and moe["latent_down"].shape == (64, 32)
    assert moe["w_up"].shape == (4, 32, 48) and moe["w_down"].shape == (4, 48, 32)
    assert moe["shared_up"].shape == (64, 96)
    mamba = shapes["layer0"]["mamba"]
    assert mamba["in_proj"].shape == (64, 128 + (128 + 2 * 4 * 16) + 16)
    assert mamba["conv_w"].shape == (4, 256) and mamba["conv_b"].shape == (256,)
    attn = shapes["layer7"]["attn"]
    assert attn["wq"].shape == (64, 128) and attn["wk"].shape == (64, 32)
    assert "w_gate" not in attn


@pytest.mark.parametrize("key,value,why", [
    ("hybrid_override_pattern", "ME-*EMEMEMEM", "'-', a dense MLP"),
    ("hybrid_override_pattern", "MEM", "names 3 layers"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act"),
    ("n_group", 2, "n_group"),
    ("residual_in_fp32", True, "residual_in_fp32"),
    ("sliding_window", 4096, "sliding_window"),
    ("expand", 4, "expand"),
    ("use_bias", True, "use_bias"),
])
def test_what_is_not_written_is_refused(key, value, why):
    keys = dict(MODEL_KEYS, **{key: value})
    broken = get_model("NemotronH", num_classes=VOCAB, **keys)
    with pytest.raises(ValueError, match=why):
        broken.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("length", [7, 64, 150])
def test_full_forward_matches_the_reference(ref, weights, model, length):
    _, params, tree = weights
    tokens = tokens_of(length, seed=length)
    got = model.apply({"params": tree}, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(
        np.asarray(got), reference_logits(ref, params, tokens), atol=TOLERANCE)


@pytest.mark.parametrize("control", ["bf16_state", "int8", "no_decay", "no_correction_bias"])
def test_the_tolerance_fails_a_lower_precision_and_a_missing_term(ref, weights, control):
    """A state kept in bfloat16, int8 products, a state that never decays
    or a choice made without the correction bias lie far outside the
    tolerance the program is held to."""
    _, params, _ = weights
    tokens = tokens_of(150, seed=150)
    sound = reference_logits(ref, params, tokens)
    more = {"bf16_state": {"mode": "bf16_state"}, "int8": {"mode": "int8"},
            "no_decay": {"decay": False}, "no_correction_bias": {"biased": False}}[control]
    broken = reference_logits(ref, params, tokens, **more)
    assert np.abs(broken - sound).max() > 20 * TOLERANCE


def paged(model, weights):
    fns = build_paged_fns(model, BLOCK, BLOCKS, state_slots=SLOTS)
    clone = model.clone(decode=True, paged=True, kv_block_size=BLOCK,
                        kv_num_blocks=BLOCKS, state_slots=SLOTS)
    return fns, clone, fns.init_pool(weights[2])


def test_prefill_then_decode_through_pool_and_state_matches_one_full_forward(
        ref, weights, model):
    """Two rows of unequal lengths, neither a multiple of the scan's chunk,
    prefilled in one call into slots 2 and 0; then six decode steps a row
    through the pool AND the state, a padding row riding along, and a seventh
    that slot 2 takes alone: every logit row is the reference's full forward
    over the same tokens."""
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


def scheduler(model, tree, **more):
    args = dict(slots=1, block_size=BLOCK, num_blocks=BLOCKS, prefix_cache=False,
                batch_buckets=[1], seq_buckets=[16, 32], max_new_tokens=6, start=False)
    return ContinuousScheduler(model, tree, **dict(args, **more))


def serve(sched, prompt):
    future = sched.submit(prompt)
    while not future.done():
        sched.tick()
    return future.result()["tokens"]


@pytest.mark.parametrize(
    "estimate, calls",
    [(None, [(2, 32)]), ((0.0, 35.0), [(1, 32), (1, 16)])],
    ids=["one_padded_call", "a_call_a_row"],
)
def test_two_arrivals_in_one_tick_through_the_scheduler_are_the_reference_s_forward(
        ref, weights, model, estimate, calls):
    """A burst: two requests of unequal length waiting when the tick comes
    are ONE padded prefill of 4 rows x 32 positions (a scheduler with no
    estimate of a call's time) or a call a row at its own bucket (time
    taken to follow the padded tokens), each row's state taken at its own
    last position in its own slot; then decode steps side by side.  Every
    served token is the reference's first choice over prompt + served
    tokens (its full forward: no cache, no chunks), by a margin the
    tolerance cannot close."""
    from pytorch_distributed_training_tpu.telemetry.spans import SpanRecorder, set_recorder

    _, params, tree = weights
    prompts = [tokens_of(27, seed=21), tokens_of(9, seed=22)]
    rec = set_recorder(SpanRecorder(ring=512))
    try:
        with scheduler(model, tree, slots=4, batch_buckets=[1, 4]) as sched:
            if estimate is not None:
                sched.set_prefill_cost(*estimate)
            futures = [sched.submit(p) for p in prompts]
            while not all(f.done() for f in futures):
                sched.tick()
            snapshot = sched.metrics.snapshot()
    finally:
        set_recorder(None)
    prefills = [s for s in rec.recent() if s["kind"] == "prefill"]
    assert [(s["rows"], s["bucket"]) for s in prefills] == calls
    assert snapshot["moe_experts_hit_count"] > 0
    for prompt, future in zip(prompts, futures):
        served = future.result()["tokens"]
        assert len(served) == 6
        seq = np.concatenate([prompt, served[:-1]])
        rows = reference_logits(ref, params, seq)[len(prompt) - 1:]
        np.testing.assert_array_equal(rows.argmax(-1), served)
        best_two = np.sort(rows, axis=-1)[:, -2:]
        assert (best_two[:, 1] - best_two[:, 0]).min() > 10 * TOLERANCE


def test_the_warm_up_s_timed_calls_leave_pool_and_state_as_they_were():
    """The engine times two prefill programs on a FULL call's inputs (live
    positions: an expert layer leaves a padding position out of its
    products): block tables one past the pool and state slots of -1, so
    no row of the pool and no slot of the state is written, and a request
    served after the warm-up gets what it got before."""
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = {
        "dataset": {"name": "synthetic_text", "n_classes": VOCAB},
        "model": dict(MODEL_KEYS, name="NemotronH"),
        "serving": {
            "dtype": "float32", "max_batch_size": 8, "max_delay_ms": 2,
            "batch_buckets": [8], "seq_buckets": [16, 32], "max_new_tokens": 6,
            "temperature": 0.0,
            "scheduler": {"enabled": True, "slots": 4, "block_size": BLOCK,
                          "num_blocks": BLOCKS, "prefix_cache": False},
        },
    }
    prompt = tokens_of(23, seed=3)
    with InferenceEngine.from_config(cfg) as engine:
        sched = engine.scheduler
        before = engine.submit(prompt).result(timeout=120)["tokens"]
        held = jax.device_get(jax.tree_util.tree_leaves(sched._pool))
        assert sum(bool(leaf.any()) for leaf in held) >= 12  # rows AND states
        engine.warmup()
        assert sched._prefill_cost is not None
        for old, new in zip(held, jax.tree_util.tree_leaves(sched._pool)):
            np.testing.assert_array_equal(old, np.asarray(new))
        after = engine.submit(prompt).result(timeout=120)["tokens"]
        np.testing.assert_array_equal(after, before)


def test_the_share_of_live_state_rows_is_observed_a_decode_step(weights, model):
    """``state_live_row_share``: of the slots of a ``[slots, ...]`` state
    leaf, those a decode step's rows live in, which is what the step's walk
    (``ops/state_rows.py``) reads and writes.  One request in four slots."""
    with scheduler(model, weights[2], slots=4, batch_buckets=[1, 4]) as sched:
        assert sched._state_live_row_share(np.asarray([7, -1, 0, -1])) == 0.5
        assert "state_live_row_share_mean" not in sched.metrics.snapshot()
        serve(sched, tokens_of(9, seed=22))
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


def test_the_cache_tree_holds_both_kinds_of_leaf(weights, model):
    """K/V rows of the one attention layer in the pool; state and
    convolution rows of the five Mamba layers a slot, told by their names."""
    fns, _, pool = paged(model, weights)
    roles = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        state = is_state_leaf(path)
        roles.setdefault((state, pool_leaf_role(path, leaf, BLOCK * BLOCKS)), []).append(
            leaf.shape)
    assert sorted(roles[(True, None)]) == sorted(
        [(SLOTS, 16, 8, 16)] * 5 + [(SLOTS, 3, 256)] * 5)
    assert roles[(False, "scored")] == roles[(False, "value")] == [(BLOCK * BLOCKS, 2, 16)]
    # copy_rows moves pool rows and leaves every state leaf as it was
    rng = np.random.default_rng(5)
    pool = jax.tree.map(
        lambda leaf: jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype), pool)
    before = jax.tree.map(np.asarray, pool)
    rows = np.arange(8, dtype=np.int32)
    after = fns.copy_rows(pool, rows, rows + 100)
    for (path, old), new in zip(
            jax.tree_util.tree_flatten_with_path(before)[0], jax.tree.leaves(after)):
        if is_state_leaf(path):
            np.testing.assert_array_equal(old, np.asarray(new))
        else:
            np.testing.assert_array_equal(np.asarray(new)[100:108], old[:8])


@pytest.mark.parametrize("what", ["prefix_cache", "draft_model", "kv_transfer",
                                  "contiguous_generate"])
def test_what_assumes_a_cache_of_token_rows_refuses_the_model(weights, model, what):
    """The refusals a ``state_shape`` model gets, each with its reason and
    none by the model's name: the second such model meets the first's."""
    tree = weights[2]
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="prefix_cache cannot serve NemotronHLM"
                                             ".*cannot restore the state"):
            scheduler(model, tree, prefix_cache=True)
    elif what == "draft_model":
        from pytorch_distributed_training_tpu.serving.speculative import SpeculativeSpec

        with pytest.raises(ValueError, match="speculative.*rejected draft token"):
            scheduler(model, tree, speculative=SpeculativeSpec(2))
    elif what == "kv_transfer":
        with scheduler(model, tree) as sched:
            for verb, arg in ((sched.export_kv_prefix, [1, 2, 3]),
                              (sched.import_kv_blocks, [])):
                with pytest.raises(ValueError, match="kv_transfer.*token rows, not the state"):
                    verb(arg)
    else:
        with pytest.raises(ValueError, match="contiguous generate path has no slots"):
            build_generate_fn(model, 4)

def test_a_prefill_call_that_starts_past_position_zero_is_refused(weights, model):
    import paged_programs

    with scheduler(model, weights[2]) as sched:
        paged_programs.check_a_call_past_position_zero_is_refused(
            sched, tokens_of(11, seed=6), BLOCK, "NemotronHLM")


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
