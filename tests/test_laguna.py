"""The Laguna family (models/laguna.py; ops/attention.py::GroupedQueryAttention
with a rotary term, a window and a gate a head; ops/attention.py::
window_attention, the ring a slot) against the benchmark's plain reference
(benchmark/reference/laguna.py) at a toy size on the CPU: hidden 48, 2 K/V
heads of 16, full layers of 6 query heads (groups of 3, half a head's lanes
rotated by YaRN) and window layers of 8 (groups of 4, every lane rotated),
window 8, a dense layer 0 then 8 experts of 24 top-2 beside a shared one, 5
layers (the dense one and one period after it).  Every key of the published
``config.json`` is here under its published name.

The reference is float32 at ``highest``, masks and keeps no ring, and shares
no code with the program; the weights are its ``make_params(seed)`` handed
over through its ``to_checkpoint_tree``, as the benchmark hands them over.
Logits are compared, never tokens.  TOLERANCE 2e-4 on logits of magnitude
about 4: both sides are float32 on the CPU and differ in the order of their
sums (a band of two blocks, or a ring in the order of ``p % window``, against
a mask over every key): 1.2e-5 was read, 2e-4 leaves more than ten times that
and is far below what int8 operands, a window one position off or an
element-wise gate read, which the tests below hold it to.
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
from pytorch_distributed_training_tpu.models.laguna import rotary_term
from pytorch_distributed_training_tpu.ops.attention import (
    WINDOW_LEAVES, GroupedQueryAttention, is_state_leaf, pool_leaf_role,
)
from pytorch_distributed_training_tpu.ops.moe import DroplessMoE
from pytorch_distributed_training_tpu.serving.decode import (
    build_generate_fn, build_paged_fns,
)
from pytorch_distributed_training_tpu.serving.scheduler import ContinuousScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def is_ring(path) -> bool:
    """A window layer's ring among the cache tree's leaves, by its name."""
    return path[-1].key in WINDOW_LEAVES


VOCAB = 512
TOLERANCE = 2e-4
WINDOW = 8
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
YARN = {
    "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
    "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 64,
    "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5,
}
ROPE = {
    "full_attention": YARN,
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    "original_max_position_embeddings": 16,
}
PUBLISHED_KEYS = dict(
    model_type="laguna", vocab_size=VOCAB, hidden_size=48, intermediate_size=96,
    num_hidden_layers=5, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=256, attention_bias=False,
    rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    tie_word_embeddings=False, gating=True, sliding_window=WINDOW,
    rope_parameters=ROPE, layer_types=PERIOD * 3,
    moe_apply_router_weight_on_input=False, partial_rotary_factor=0.5,
    mlp_layer_types=["dense"] + ["sparse"] * 11, moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[6, 8, 8, 8] * 3,
)
CONFIG = dict(
    PUBLISHED_KEYS, reference_pad_to=32, reference_query_block=32,
    assumed={"router_logit_std": 2.0, "gate_logit_std": 1.5},
)
MODEL_KEYS = {k: v for k, v in PUBLISHED_KEYS.items() if k != "vocab_size"}
BLOCK, BLOCKS, SLOTS = 4, 160, 3


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "laguna.py")
    spec = importlib.util.spec_from_file_location("reference_laguna", path)
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
    return get_model("Laguna", num_classes=VOCAB, dtype=jnp.float32, **MODEL_KEYS)


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, size=(n,)).astype(np.int32)


def reference_logits(ref, params, tokens, **more):
    pad = -len(tokens) % 32
    padded = jnp.asarray(np.concatenate([tokens, np.zeros((pad,), np.int32)]))
    return np.asarray(ref.logits_one(params, padded, **more))[:len(tokens)]


def test_the_family_states_what_it_is(model):
    cls = model_class("laguna")
    assert cls.is_language_model and cls.takes_logit_cols
    assert "served, not trained" in cls.training_unsupported
    assert model.moe_shape == (4, 2, 8)       # layer 0 is dense
    assert model.window_shape == (3, WINDOW, 2)  # layers 1-3; 0 and 4 are full
    assert model.state_shape == (3, 2, WINDOW, 16)
    assert [heads for _, heads, _ in model._layers()] == [6, 8, 8, 8, 6]
    full = get_model("Laguna", num_classes=VOCAB, **dict(
        MODEL_KEYS, layer_types=["full_attention"] * 5))
    assert full.window_shape is None and full.state_shape is None


def test_the_training_path_refuses_the_family_with_the_reason():
    refusal = model_class("Laguna").training_unsupported
    for other in ("DeepseekV2", "SolarOpen2", "NemotronH", "OlmoHybrid"):
        # one form of words for all five served families
        said = model_class(other).training_unsupported
        assert said.split(":")[0].replace(other, "Laguna") == refusal.split(":")[0]
        assert said.endswith("pytorch_distributed_training_tpu.serving")
    from types import SimpleNamespace

    from pytorch_distributed_training_tpu.engine.topology import parse_topology

    cfg = {"model": {"name": "Laguna", "hidden_size": 48}}
    with pytest.raises(ValueError, match="Laguna cannot be trained.*flash kernels.*no window"):
        parse_topology(SimpleNamespace(), cfg, {"dtype": "float32"}, None)


def test_parameters_are_created_in_the_serving_dtype_and_count_as_published():
    """At the toy widths, and (shapes only) at the published ones: a full
    layer's attention 29.46 M, a sliding layer's 37.88 M, an expert layer
    809.0 M, 33.44 B in all (the published 33.4B)."""
    bf16 = get_model("Laguna", num_classes=VOCAB, dtype=jnp.bfloat16, **MODEL_KEYS)
    shapes = jax.eval_shape(
        lambda: bf16.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert {leaf.dtype for leaf in jax.tree.leaves(shapes["params"])} == {jnp.dtype("bfloat16")}
    assert shapes["params"]["layer0"]["attn"]["w_gate"].shape == (48, 6)  # a gate a head
    assert shapes["params"]["layer1"]["attn"]["w_gate"].shape == (48, 8)
    assert shapes["params"]["layer1"]["attn"]["wq"].shape == (48, 8 * 16)
    assert "mlp" in shapes["params"]["layer0"] and "moe" in shapes["params"]["layer1"]
    published = get_model("Laguna", num_classes=100352, dtype=jnp.bfloat16, **{
        k: v for k, v in catalog_config().items() if k != "vocab_size"})
    shapes = jax.eval_shape(
        lambda: published.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))  # noqa: E731
    params = shapes["params"]
    assert count(params["layer0"]["attn"]) == 29_458_432
    assert count(params["layer1"]["attn"]) == 37_879_808
    assert count(params["layer1"]["moe"]) == 256 * 3_145_728 + 3_145_728 + 524_288
    assert count(params["layer0"]) == 79_794_176
    # 9 sparse full layers, 30 sliding, the dense layer 0, embedding and head
    assert count(params) == 33_442_596_864 == (
        9 * 838_438_912 + 30 * 846_860_288 + 79_794_176 + 2 * 2048 * 100352 + 2048)


def catalog_config():
    """The published config, as config/serve-laguna-xs2.yml carries it (all
    40 layers of it)."""
    with open(os.path.join(ROOT, "config", "serve-laguna-xs2.yml")) as fp:
        cfg = yaml.safe_load(fp)
    model = {k: v for k, v in cfg["model"].items() if k not in ("name", "experts_held")}
    return dict(model, num_hidden_layers=40, vocab_size=cfg["dataset"]["n_classes"])


@pytest.mark.parametrize("key,value", [
    ("gating", "per-element"), ("gating", False), ("attention_bias", True),
    ("tie_word_embeddings", True), ("moe_apply_router_weight_on_input", True),
    ("rope_parameters", dict(ROPE, full_attention=dict(YARN, rope_type="linear"))),
    ("rope_parameters", {"full_attention": YARN}),
    ("layer_types", ["chunked_attention"] * 5), ("layer_types", PERIOD),
    ("shared_expert_intermediate_size", 30),
])
def test_what_is_not_written_is_refused(key, value):
    broken = get_model("Laguna", num_classes=VOCAB, **dict(MODEL_KEYS, **{key: value}))
    with pytest.raises(ValueError, match=f"Laguna: model.{key}"):
        jax.eval_shape(
            lambda: broken.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))


def test_the_sibling_s_gating_value_is_read_as_a_gate_a_head():
    named = get_model("Laguna", num_classes=VOCAB, **dict(MODEL_KEYS, gating="per-head"))
    shapes = jax.eval_shape(
        lambda: named.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert shapes["params"]["layer1"]["attn"]["w_gate"].shape == (48, 8)


def test_yarn_frequencies_and_amplitude_are_the_published_formula_s():
    """At the published parameters (64 rotated lanes, base 500,000, factor 64
    over 4,096 positions, beta 64 and 1), numbers worked out by hand: the
    correction dimensions are 64 ln(4096 / (2 pi n)) / (2 ln 500000) = 5.66
    for n = 64 and 15.80 for n = 1, so the ramp runs from pair 5 to pair 16;
    a pair before it keeps ``500000^(-2i/64)``, a pair after it has that over
    64, pair 10 is 5/11 of the way."""
    published = catalog_config()["rope_parameters"]
    lanes, freq, amplitude = rotary_term(published["full_attention"], 128)
    assert lanes == 64 and len(freq) == 32
    by_hand = {0: 1.0, 5: 0.12869483, 10: 0.0091519, 16: 2.2097087e-05, 31: 4.7093e-08}
    for pair, want in by_hand.items():
        assert freq[pair] == pytest.approx(want, rel=2e-4), pair
    assert amplitude == pytest.approx(0.1 * np.log(64) + 1) == pytest.approx(1.4158883)
    lanes, freq, amplitude = rotary_term(published["sliding_attention"], 128)
    assert lanes == 128 and amplitude == 1.0
    assert freq[0] == 1.0 and freq[63] == pytest.approx(10000 ** (-126 / 128), rel=1e-6)
    # with no factor given in the config, YaRN's own: 0.1 ln(factor) + 1
    bare = {k: v for k, v in published["full_attention"].items() if k != "attention_factor"}
    assert rotary_term(bare, 128)[2] == pytest.approx(1.4158883)


@pytest.mark.parametrize("length", [5, 8, 9, 27])
def test_full_forward_matches_the_reference(ref, weights, model, length):
    """Rows inside the window, exactly the window, one past it and more than
    three windows long."""
    _, params, tree = weights
    tokens = tokens_of(length, seed=length)
    got = model.apply({"params": tree}, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(
        np.asarray(got), reference_logits(ref, params, tokens), atol=TOLERANCE)


@pytest.mark.parametrize("control", ["int8", "window_one_wider", "no_routed_experts"])
def test_the_tolerance_fails_a_lower_precision_and_a_wrong_window(ref, weights, control):
    """int8 operands, a window that keeps ONE position more, or a sum without
    the routed experts, lie far outside the tolerance the program is held to."""
    _, params, _ = weights
    tokens = tokens_of(27, seed=27)
    sound = reference_logits(ref, params, tokens)
    more = {"int8": {"mode": "int8"}, "window_one_wider": {"window_shift": 1},
            "no_routed_experts": {"routed": False}}[control]
    broken = reference_logits(ref, params, tokens, **more)
    assert np.abs(broken - sound).max() > 50 * TOLERANCE


def paged(model, weights):
    fns = build_paged_fns(model, BLOCK, BLOCKS, state_slots=SLOTS)
    clone = model.clone(decode=True, paged=True, kv_block_size=BLOCK,
                        kv_num_blocks=BLOCKS, state_slots=SLOTS)
    return fns, clone, fns.init_pool(weights[2])


def test_prefill_then_decode_through_pool_and_ring_matches_one_full_forward(
        ref, weights, model):
    """Two rows of unequal lengths (27: more than three windows; 5: inside
    one) prefilled in ONE padded call of 32 positions (four bands of 8) into
    slots 2 and 0; then 20 decode steps a row through the pool AND the ring,
    a padding row riding along: the short row crosses the window's edge and
    then wraps its ring twice, the long one wraps it twice more.  Every logit
    row is the reference's full forward over the same tokens; a slot that
    sits a step out keeps its ring bit for bit."""
    _, params, tree = weights
    _, clone, pool = paged(model, weights)
    steps = 20
    lens, slots, bucket, table = [27, 5], [2, 0], 32, 16
    rows = [tokens_of(n + steps + 1, seed=i + 1) for i, n in enumerate(lens)]
    tokens = np.zeros((2, bucket), np.int32)
    positions = np.full((2, bucket), -1, np.int32)
    tables = np.stack([np.arange(table), table + np.arange(table)]).astype(np.int32)
    for i, n in enumerate(lens):
        tokens[i, :n], positions[i, :n] = rows[i][:n], np.arange(n)
    apply = jax.jit(lambda pool, *a, **k: clone.apply(
        {"params": tree, "cache": pool}, *a, mutable=["cache", "moe_stats"], **k))
    logits, variables = apply(pool, tokens, positions, tables,
                              state_rows=np.asarray(slots, np.int32))
    want = [reference_logits(ref, params, r) for r in rows]
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits[i, :n]), want[i][:n], atol=TOLERANCE)
    # decode: batch rows are slots; slot 1 is padding (position -1, slot -1)
    step_tables = np.zeros((SLOTS, table), np.int32)
    step_tables[2], step_tables[0] = tables[0], tables[1]
    row_of_slot = {2: 0, 0: 1}
    for k in range(steps):
        tok = np.zeros((SLOTS, 1), np.int32)
        pos = np.full((SLOTS, 1), -1, np.int32)
        for slot, i in row_of_slot.items():
            tok[slot, 0], pos[slot, 0] = rows[i][lens[i] + k], lens[i] + k
        state_rows = np.where(pos[:, 0] >= 0, np.arange(SLOTS), -1).astype(np.int32)
        logits, variables = apply(variables["cache"], tok, pos, step_tables,
                                  state_rows=state_rows, rows_are_slots=True)
        for slot, i in row_of_slot.items():
            np.testing.assert_allclose(
                np.asarray(logits[slot, 0]), want[i][lens[i] + k], atol=TOLERANCE)
    # one more step that slot 2 takes alone
    tok, pos = np.zeros((SLOTS, 1), np.int32), np.full((SLOTS, 1), -1, np.int32)
    tok[2, 0], pos[2, 0] = rows[0][lens[0] + steps], lens[0] + steps
    before = jax.tree_util.tree_flatten_with_path(jax.device_get(variables["cache"]))[0]
    logits, variables = apply(variables["cache"], tok, pos, step_tables,
                              state_rows=np.asarray([-1, -1, 2], np.int32),
                              rows_are_slots=True)
    np.testing.assert_allclose(
        np.asarray(logits[2, 0]), want[0][lens[0] + steps], atol=TOLERANCE)
    assert np.isfinite(np.asarray(logits)).all()
    after = jax.tree_util.tree_flatten_with_path(variables["cache"])[0]
    rings = 0
    for (path, old), (_, new) in zip(before, after):
        if is_ring(path):
            rings += 1
            assert old.shape == (SLOTS * WINDOW, 2, 16)
            np.testing.assert_array_equal(old[:2 * WINDOW], np.asarray(new)[:2 * WINDOW])
            changed = (old[2 * WINDOW:] != np.asarray(new)[2 * WINDOW:]).any(axis=(1, 2))
            assert changed.sum() == 1  # the one position that left the window
    assert rings == 2 * 3  # keys and values of three window layers


def attention_layer(**more):
    return GroupedQueryAttention(
        num_heads=8, num_kv_heads=2, head_dim=16, gate="head",
        rotary_dim=16, rotary_inv_freq=rotary_term(ROPE["sliding_attention"], 16)[1],
        **more)


@pytest.mark.parametrize("length,bucket", [(32, 32), (19, 32), (5, 8), (8, 8), (64, 64)])
def test_the_banded_prefill_is_the_masked_square_form(length, bucket):
    """The window layer alone: a paged call scores blocks of 8 query rows
    against their own block and the one before; the plain call masks the
    ``[S, S]`` scores to ``i - 8 < j <= i``.  The real positions agree, and
    the ring holds the call's last ``min(length, 8)`` keys at ``p % 8``,
    rotated as they were scored, and nothing of the padding."""
    layer = attention_layer(window=WINDOW)
    x = jnp.asarray(np.random.default_rng(length).standard_normal((1, bucket, 48)),
                    jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    want = layer.apply({"params": params}, x[:, :length])
    ring_layer = layer.clone(decode=True, paged=True, kv_block_size=BLOCK,
                             kv_num_blocks=4, state_slots=2)
    positions = np.where(np.arange(bucket) < length, np.arange(bucket), -1)[None]
    got, cache = ring_layer.apply(
        {"params": params}, x, positions.astype(np.int32), None,
        np.asarray([1], np.int32), mutable=["cache"])
    np.testing.assert_allclose(
        np.asarray(got[:, :length]), np.asarray(want), atol=1e-5)
    keys = np.asarray(cache["cache"]["window_k"])
    assert keys.shape == (2 * WINDOW, 2, 16) and (keys[:WINDOW] == 0).all()
    written = {p % WINDOW for p in range(max(0, length - WINDOW), length)}
    for row in range(WINDOW):
        assert (keys[WINDOW + row] != 0).any() == (row in written)
    # the next position's query reads exactly those keys, through the ring
    step = np.random.default_rng(1).standard_normal((1, 1, 48)).astype(np.float32)
    whole = layer.apply(
        {"params": params}, jnp.concatenate([x[:, :length], step], axis=1))
    got, _ = ring_layer.apply(
        {"params": params, "cache": cache["cache"]}, step,
        np.full((1, 1), length, np.int32), None, np.asarray([1], np.int32),
        mutable=["cache"])
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(whole[0, -1]), atol=1e-5)


def test_a_window_layer_s_call_is_whole_bands():
    layer = attention_layer(window=WINDOW, decode=True, paged=True, kv_block_size=BLOCK,
                            kv_num_blocks=4, state_slots=1)
    x = jnp.zeros((1, 12, 48))
    with pytest.raises(ValueError, match="no multiple of its window 8"):
        layer.init(jax.random.PRNGKey(0), x, jnp.zeros((1, 12), jnp.int32), None,
                   jnp.zeros((1,), jnp.int32))


def test_what_the_shared_attention_module_gained_is_off_unless_asked_for():
    """Rotary term, window and the gate's second shape are static fields of
    the shared module and off by default: without them it has the parameters
    it had, no position term (a permutation of the positions permutes the
    output rows' inputs alike) and no slot-addressed leaf."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 6, 24)), jnp.float32)
    plain = GroupedQueryAttention(num_heads=4, num_kv_heads=2, head_dim=8)
    params = plain.init(jax.random.PRNGKey(0), x)["params"]
    assert {n: p.shape for n, p in params.items()} == {
        "wq": (24, 32), "wk": (24, 16), "wv": (24, 16), "wo": (32, 24),
        "w_gate": (24, 32)}
    # NoPE: the last row's output does not care in which order the others came
    shuffled = x[:, [2, 0, 4, 1, 3, 5]]
    np.testing.assert_allclose(
        np.asarray(plain.apply({"params": params}, x))[0, -1],
        np.asarray(plain.apply({"params": params}, shuffled))[0, -1], atol=1e-6)
    turned = plain.clone(rotary_dim=4, rotary_inv_freq=(1.0, 0.1))
    assert np.abs(
        np.asarray(turned.apply({"params": params}, x))[0, -1]
        - np.asarray(turned.apply({"params": params}, shuffled))[0, -1]).max() > 1e-3
    paged_layer = plain.clone(decode=True, paged=True, kv_block_size=4, kv_num_blocks=4)
    _, cache = paged_layer.apply(
        {"params": params}, x[:, :4], np.arange(4, dtype=np.int32)[None],
        np.arange(4, dtype=np.int32)[None], mutable=["cache"])
    assert sorted(cache["cache"]) == ["k_pool", "v_pool"]
    with pytest.raises(ValueError, match="gate is True, 'head' or False"):
        plain.clone(gate="row").init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="needs 2 frequencies, got 1"):
        plain.clone(rotary_dim=4, rotary_inv_freq=(1.0,)).init(jax.random.PRNGKey(0), x)


def scheduler(model, tree, **more):
    args = dict(slots=1, block_size=BLOCK, num_blocks=BLOCKS, prefix_cache=False,
                batch_buckets=[1], seq_buckets=[16, 32], max_new_tokens=12, start=False)
    return ContinuousScheduler(model, tree, **dict(args, **more))


def serve(sched, prompt):
    future = sched.submit(prompt)
    while not future.done():
        sched.tick()
    return future.result()["tokens"]


def test_two_arrivals_in_one_tick_through_the_scheduler_are_the_reference_s_forward(
        ref, weights, model):
    """Two requests of unequal length waiting when the tick comes are ONE
    padded prefill of 2 rows x 32 positions; then 12 decode steps side by
    side on the ring of depth 1, ONE ``decode_step`` program, both rows past
    their window.  Every served token is the reference's first choice over
    prompt + served tokens (its full forward: no cache, no ring), by a margin
    the tolerance cannot close; every ``decode_step`` span says how many
    positions the step's rows read in a window layer and in a full one."""
    from pytorch_distributed_training_tpu.telemetry.spans import SpanRecorder, set_recorder

    _, params, tree = weights
    prompts = [tokens_of(27, seed=21), tokens_of(5, seed=22)]
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
    steps = [s for s in rec.recent() if s["kind"] == "decode_step"]
    first = steps[0]  # both rows live: lengths 28 and 6 with the fed token
    assert (first["active"], first["window_keys"], first["full_keys"]) == (2, 8 + 6, 28 + 6)
    assert all(s["window_keys"] <= s["active"] * WINDOW for s in steps)
    assert steps[-1]["full_keys"] > steps[-1]["window_keys"]
    assert snapshot["moe_experts_hit_count"] > 0
    assert snapshot["state_live_row_share_mean"] > 0
    assert snapshot["decode_steps_overlapped"] > 0  # the ring of depth 1
    for prompt, future in zip(prompts, futures):
        served = future.result()["tokens"]
        assert len(served) == 12
        seq = np.concatenate([prompt, served[:-1]])
        rows = reference_logits(ref, params, seq)[len(prompt) - 1:]
        np.testing.assert_array_equal(rows.argmax(-1), served)
        best_two = np.sort(rows, axis=-1)[:, -2:]
        assert (best_two[:, 1] - best_two[:, 0]).min() > 10 * TOLERANCE


def test_admission_counts_the_full_layers_blocks_only(weights, model):
    """The pool holds one request's footprint (32 + 12 positions = 11 blocks
    of 4) and not two; the rings beside it take no block whatever the rows'
    lengths.  The second request waits at ``KVPool.admit`` until the first
    retires, is then served what a fresh engine serves, every block is free
    again at the end, and ``admission_waits`` in the snapshot says so."""
    tree = weights[2]
    first, second = tokens_of(30, seed=3), tokens_of(29, seed=4)
    with scheduler(model, tree, slots=2, batch_buckets=[1, 2], num_blocks=12) as tight, \
            scheduler(model, tree) as fresh:
        futures = [tight.submit(first), tight.submit(second)]
        while not all(f.done() for f in futures):
            tight.tick()
        snapshot = tight.metrics.snapshot()
        np.testing.assert_array_equal(futures[1].result()["tokens"], serve(fresh, second))
        assert tight._kv.blocks_in_use == 0  # a finished row gives everything back
    assert snapshot["admission_waits"] >= 1


@pytest.mark.parametrize("first_len,second_len", [(27, 5), (5, 27), (30, 9)])
def test_a_ring_reused_by_a_fresh_row_leaks_nothing(weights, model, first_len, second_len):
    """The one slot's ring is never cleared: a second request's prefill
    writes its own last rows over it and a row shorter than the window reads
    only what it wrote.  Whatever the first request left (a full ring, or
    rows the second never writes), the second is served what a fresh engine
    serves it."""
    tree = weights[2]
    first, second = tokens_of(first_len, seed=3), tokens_of(second_len, seed=4)
    with scheduler(model, tree) as used, scheduler(model, tree) as fresh:
        serve(used, first)
        np.testing.assert_array_equal(serve(used, second), serve(fresh, second))


def test_a_stale_ring_row_s_nan_stays_out_of_a_fresh_row(weights, model):
    """What an evicted request left in its ring may be a NaN: a fresh row
    that has not written that far masks the row's score AND zeroes its value
    (``0 * NaN`` must not reach the sum)."""
    _, clone, pool = paged(model, weights)
    poisoned = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.full_like(leaf, jnp.nan)
        if is_ring(path) else leaf, pool)
    tokens = tokens_of(6, seed=9)
    positions = np.full((1, 8), -1, np.int32)
    positions[0, :5] = np.arange(5)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :5] = tokens[:5]
    table = np.arange(16, dtype=np.int32)[None]
    slot = np.asarray([1], np.int32)
    apply = lambda cache, *a: clone.apply(  # noqa: E731
        {"params": weights[2], "cache": cache}, *a, state_rows=slot,
        mutable=["cache", "moe_stats"])
    outs = []
    for start in (pool, poisoned):
        _, variables = apply(start, padded, positions, table)
        logits, _ = apply(variables["cache"], tokens[5:6][None],
                          np.full((1, 1), 5, np.int32), table)
        outs.append(np.asarray(logits))
    assert np.isfinite(outs[1]).all()
    np.testing.assert_array_equal(outs[0], outs[1])


def test_the_cache_tree_holds_both_kinds_of_row_and_the_step_four_outputs(weights, model):
    """K/V pairs of 2 heads in the two full layers' pool leaves, a ring of
    ``slots x window`` rows a window layer told by its name; ``copy_rows``
    passes the rings by; the decode program returns token, finite flag,
    cache and the expert counts."""
    fns, _, pool = paged(model, weights)
    flat = jax.tree_util.tree_flatten_with_path(pool)[0]
    shapes = {}
    for path, leaf in flat:
        kind = "ring" if is_state_leaf(path) else pool_leaf_role(path, leaf, BLOCK * BLOCKS)
        shapes.setdefault(kind, []).append(leaf.shape)
    assert shapes["scored"] == shapes["value"] == [(BLOCK * BLOCKS, 2, 16)] * 2
    assert shapes["ring"] == [(SLOTS * WINDOW, 2, 16)] * 6
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
    assert len(out) == 4


@pytest.mark.parametrize("what", ["prefix_cache", "draft_model", "kv_transfer",
                                  "contiguous_generate"])
def test_what_assumes_a_cache_of_token_rows_refuses_the_model(weights, model, what):
    """Each with its reason; no silent fallback: a ring is addressed by slot
    as a recurrent state is."""
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

def test_the_prefill_program_alone_holds_the_flash_forward(weights, model, monkeypatch):
    import paged_programs

    with scheduler(model, weights[2], seq_buckets=[16, 128]) as sched:
        paged_programs.check_prefill_alone_holds_the_flash_forward(sched, 2, monkeypatch)



def test_replay_after_a_restart_rebuilds_the_rings_from_position_zero(weights, model):
    """A hot restart re-prefills the prompt and re-feeds the delivered
    tokens: the continuation is the undisturbed run's."""
    tree = weights[2]
    prompt = tokens_of(11, seed=6)
    with scheduler(model, tree) as calm, scheduler(model, tree) as shaken:
        want = serve(calm, prompt)
        future = shaken.submit(prompt)
        for _ in range(5):
            shaken.tick()
        shaken._rebuild_and_requeue()
        while not future.done():
            shaken.tick()
        np.testing.assert_array_equal(future.result()["tokens"], want)
        assert shaken.metrics.snapshot().get("replay_parity_mismatch", 0) == 0


@pytest.mark.parametrize("replay", [False, True], ids=["fresh", "replay"])
def test_a_prefill_call_that_starts_past_position_zero_is_refused(weights, model, replay):
    """The layers take a multi-token call's column for the position (a full
    layer scoring the call's own keys, a window layer's band and ring
    write), so the scheduler, which decides what a call holds, refuses a
    call whose rows start anywhere else: here a request made to look as if
    a prefix of one block were cached, fresh and on the replay path."""
    tree = weights[2]
    with scheduler(model, tree) as sched:
        future = sched.submit(tokens_of(11, seed=6))
        if replay:
            for _ in range(3):
                sched.tick()
            sched._rebuild_and_requeue()
        calls = sched._prefill_calls

        def a_piece(newly):
            for req in newly:
                req.admission.cached_len = BLOCK
            return calls(newly)

        sched._prefill_calls = a_piece
        with pytest.raises(ValueError, match=r"rows start at \[4\] cannot serve LagunaLM"
                                             r".*prefilled whole, from position 0"):
            while not future.done():
                sched._tick_inner()  # under tick()'s restarts the same refusal


def test_expert_shares_add_up_to_the_whole_layer(ref, weights):
    """The guide's shares test at this family's form (softmax scores over
    all 8, top-2 renormalised, times 2.5): four shares of 2 experts each
    route over all 8 and return their own experts' part; the four parts and
    the shared expert, counted once, add up to the uncut reference's layer."""
    sizes, params, _ = weights
    p = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(0).standard_normal((19, 48)), jnp.float32)
    arch = sizes["arch"]
    whole = np.asarray(ref.experts_layer(x, p, arch=arch))
    moe = jax.tree.map(
        lambda a: jnp.asarray(a).astype(jnp.float32),
        ref.to_checkpoint_tree(jax.device_get(params))["layer1"]["moe"])
    total, pairs = np.zeros_like(whole), 0
    for first in range(0, 8, 2):
        share = DroplessMoE(
            dim=48, num_experts=8, top_k=2, hidden=24, shared_hidden=24,
            norm_topk_prob=True, routed_scaling_factor=2.5, experts_held=(first, 2))
        held = dict(moe, w_gate_up=moe["w_gate_up"][first:first + 2],
                    w_down=moe["w_down"][first:first + 2])
        part, group_sizes = share.apply({"params": held}, x, method=DroplessMoE.routed_part)
        total += np.asarray(part)
        pairs += int(np.asarray(group_sizes).sum())
        if first == 0:
            total += np.asarray(share.apply({"params": held}, x, method=DroplessMoE.shared_part))
    assert pairs == 19 * 2  # every token's two choices were computed somewhere
    np.testing.assert_allclose(total, whole, atol=TOLERANCE)


def test_the_serve_config_runs_through_the_cli_at_the_toy_size(tmp_path, capsys):
    """config/serve-laguna-xs2.yml names the published keys; with the widths
    swapped for the toy's it is served end to end by ``python -m
    ...serving``: engine, scheduler, paged pool, rings; the snapshot says
    what a token costs in the pool and what the rings hold."""
    from pytorch_distributed_training_tpu.serving.__main__ import main

    with open(os.path.join(ROOT, "config", "serve-laguna-xs2.yml")) as fp:
        cfg = yaml.safe_load(fp)
    published = {k: v for k, v in cfg["model"].items() if k != "name"}
    assert published["num_hidden_layers"] == 17 and published["experts_held"] == [0, 32]
    assert set(published) == set(MODEL_KEYS) | {"experts_held"}
    served = get_model("Laguna", num_classes=100352, **published)
    assert served.window_shape == (12, 512, 5) and served.moe_shape == (16, 8, 32)
    assert cfg["serving"]["scheduler"]["prefix_cache"] is False
    assert all(bucket % 512 == 0 for bucket in cfg["serving"]["seq_buckets"])
    cfg["dataset"]["n_classes"] = VOCAB
    cfg["model"] = dict(MODEL_KEYS, name="Laguna", experts_held=[0, 4])
    cfg["serving"].update(dtype="float32", max_batch_size=2, batch_buckets=[1, 2],
                          seq_buckets=[8, 16], max_new_tokens=12)
    cfg["serving"]["scheduler"].update(slots=2, block_size=BLOCK, num_blocks=16)
    path = tmp_path / "serve.yml"
    path.write_text(yaml.safe_dump(cfg))
    assert main(["--config", str(path), "--requests", "4", "--log-dir", str(tmp_path)]) == 0
    snap = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["serving"]
    assert snap["retired"] == 4 and snap["state_live_row_share_mean"] > 0
    assert snap["decode_steps_overlapped"] > 0  # the ring of depth 1, by default
    # the warm-up sizes the cache tree as it lies (the benchmark warms up)
    from pytorch_distributed_training_tpu.config_parsing import get_serve_cfg
    from pytorch_distributed_training_tpu.serving import InferenceEngine

    with InferenceEngine.from_config(get_serve_cfg(str(path))) as engine:
        engine.warmup()
        snap = engine.metrics.snapshot()
    # two full layers keep a row's whole history: K and V, 2 heads of 16, float32
    assert snap["pool_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert snap["kv_pool_bytes"] == 16 * BLOCK * snap["pool_bytes_per_token"]
    # three window layers keep 8 positions a slot, whatever the rows' lengths
    assert snap["window_ring_bytes"] == snap["state_cache_bytes"] == 3 * 2 * (2 * WINDOW) * 2 * 16 * 4
