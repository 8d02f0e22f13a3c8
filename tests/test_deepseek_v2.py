"""The DeepSeek-V2 family (models/deepseek_v2.py, ops/mla.py, ops/moe.py::
DroplessMoE) against the benchmark's plain reference
(benchmark/reference/deepseek_v2.py) at a toy size on the CPU: hidden 64,
4 heads with the published 2:1:2 nope/rope/v proportions, 8 experts top-3
with two shared, 1 dense + 2 expert layers.

The reference is float32 at ``highest`` and shares no code with the program;
the weights are its ``make_params(seed)`` handed over through its
``to_checkpoint_tree``, as the benchmark hands them over.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models import get_model, model_class
from pytorch_distributed_training_tpu.ops.mla import MLAttention
from pytorch_distributed_training_tpu.ops.moe import DroplessMoE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 512
CONFIG = dict(
    attention_bias=False, first_k_dense_replace=1, hidden_act="silu",
    hidden_size=64, intermediate_size=160, kv_lora_rank=32,
    max_position_embeddings=4096, model_type="deepseek_v2",
    moe_intermediate_size=48, moe_layer_freq=1, n_group=1, n_routed_experts=8,
    n_shared_experts=2, norm_topk_prob=False, num_attention_heads=4,
    num_experts_per_tok=3, num_hidden_layers=3, num_key_value_heads=4,
    q_lora_rank=None, qk_nope_head_dim=16, qk_rope_head_dim=8, rms_norm_eps=1e-6,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=0.707,
                      mscale_all_dim=0.707, original_max_position_embeddings=64,
                      type="yarn"),
    rope_theta=10000, routed_scaling_factor=1, scoring_func="softmax",
    seq_aux=True, tie_word_embeddings=False, topk_group=1, topk_method="greedy",
    v_head_dim=16, vocab_size=VOCAB, assumed={"router_logit_std": 2.0},
)
MODEL_KEYS = {k: v for k, v in CONFIG.items() if k not in ("assumed", "vocab_size")}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "deepseek_v2.py")
    spec = importlib.util.spec_from_file_location("reference_deepseek_v2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def weights(ref):
    """(sizes, reference-layout params on the device, the program's tree)."""
    sizes = ref.sizes_of(CONFIG)
    host = jax.device_get(ref.make_params(7, sizes))
    return sizes, jax.tree.map(jnp.asarray, host), ref.to_checkpoint_tree(host)


def program(dtype, tree, **more):
    model = get_model("DeepseekV2", num_classes=VOCAB, dtype=DTYPES[dtype],
                      **dict(MODEL_KEYS, **more))
    return model, jax.tree.map(lambda a: jnp.asarray(a).astype(DTYPES[dtype]), tree)


def test_the_family_states_what_it_is():
    assert model_class("DeepseekV2").is_language_model
    assert model_class("transformerlm").is_language_model
    assert not getattr(model_class("ResNet50"), "is_language_model", False)
    model, _ = program("float32", {})
    assert model.max_len == 4096 and model.moe_shape == (2, 3, 8)
    assert get_model("TransformerLM", num_classes=8).is_language_model
    assert getattr(get_model("TransformerLM", num_classes=8), "moe_shape", None) is None


def test_parameters_are_created_in_the_serving_dtype(weights):
    _, _, tree = weights
    model, params = program("bfloat16", tree)
    made = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    )["params"]
    assert jax.tree.structure(made) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(made), jax.tree.leaves(params)):
        assert (got.shape, got.dtype) == (want.shape, jnp.bfloat16)


# the program in float32 is the reference to rounding; in bfloat16 the
# router's sixth and seventh choices can swap on a rounded input, so a few
# logits move by tenths while the mean stays small
PREFILL_LIMITS = {"float32": (1e-4, 1e-5), "bfloat16": (0.6, 0.04)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_logits_match_the_reference(ref, weights, dtype):
    sizes, params, tree = weights
    model, p = program(dtype, tree)
    tokens = np.random.default_rng(0).integers(0, VOCAB, (2, 24)).astype(np.int32)
    got = np.asarray(model.apply({"params": p}, tokens), np.float32)
    worst, mean = PREFILL_LIMITS[dtype]
    for row in range(2):
        want = np.asarray(ref.logits_for(params, tokens[row], sizes["H"], "f32"))
        assert np.abs(got[row] - want).max() < worst
        assert np.abs(got[row] - want).mean() < mean


def paged_run(model, p, prompts, tables, new, block_size, num_blocks, poison):
    """Prefill the prompts in one call (rows at different positions), then
    decode token by token through the pool, every step fed the reference-
    free greedy token.  The pool starts as ``poison`` everywhere: a recycled
    block's stale rows.  Returns the logits of every position the pool
    served, one array a row."""
    paged = model.clone(decode=True, paged=True, kv_block_size=block_size,
                        kv_num_blocks=num_blocks)
    shapes = jax.eval_shape(
        lambda: paged.apply({"params": p}, jnp.zeros((1, 1), jnp.int32),
                            jnp.zeros((1, 1), jnp.int32),
                            jnp.zeros((1, 1), jnp.int32), mutable=["cache"])[1]
    )["cache"]
    pool = jax.tree.map(lambda s: jnp.full(s.shape, poison, s.dtype), shapes)
    width = max(len(q) for q in prompts) + 3
    tokens = np.zeros((len(prompts), width), np.int32)
    positions = np.full((len(prompts), width), -1, np.int32)
    for r, prompt in enumerate(prompts):
        tokens[r, :len(prompt)] = prompt
        positions[r, :len(prompt)] = np.arange(len(prompt))
    apply = jax.jit(lambda pool, t, pos: paged.apply(
        {"params": p, "cache": pool}, t, pos, tables, mutable=["cache", "moe_stats"]))
    logits, state = apply(pool, tokens, positions)
    seqs = [list(q) for q in prompts]
    served = [[np.asarray(logits[r, len(q) - 1], np.float32)]
              for r, q in enumerate(prompts)]
    for _ in range(new):
        for r in range(len(seqs)):
            seqs[r].append(int(served[r][-1].argmax()))
        prev = np.array([[s[-1]] for s in seqs], np.int32)
        pos = np.array([[len(s) - 1] for s in seqs], np.int32)
        logits, state = apply(state["cache"], prev, pos)
        for r in range(len(seqs)):
            served[r].append(np.asarray(logits[r, 0], np.float32))
    return seqs, [np.stack(rows) for rows in served], state


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_paged_decode_matches_one_full_forward(ref, weights, dtype):
    """Two rows at different positions, block tables out of order, and a
    pool whose every row starts as NaN (what a recycled block of an evicted
    request holds): each served position's logits against ONE full forward
    of the reference over the finished sequence."""
    sizes, params, tree = weights
    model, p = program(dtype, tree)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32) for n in (9, 5)]
    tables = np.zeros((2, 6), np.int32)
    tables[0, :5], tables[1, :4] = [3, 7, 9, 11, 13], [5, 2, 20, 21]
    seqs, served, state = paged_run(model, p, prompts, tables, 6, 4, 32, jnp.nan)
    worst, mean = PREFILL_LIMITS[dtype]
    for r, prompt in enumerate(prompts):
        want = np.asarray(ref.logits_for(
            params, np.array(seqs[r], np.int32), sizes["H"], "f32"))
        want = want[len(prompt) - 1:]
        assert np.isfinite(served[r]).all()
        assert np.abs(served[r] - want).max() < worst
        assert np.abs(served[r] - want).mean() < mean
    hit, load = state["moe_stats"]["experts_hit"][0], state["moe_stats"]["expert_load_max"][0]
    assert 2 <= int(hit) <= 2 * 6 and 2 <= int(load) <= 2 * 2  # 2 rows x 3, 2 layers


def test_absorbed_attention_is_the_unabsorbed_function():
    """The two forms over the same rows: six queries against themselves."""
    kw = dict(num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              kv_lora_rank=32, rope_scaling=tuple(sorted(CONFIG["rope_scaling"].items())))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 64))
    expanded = MLAttention(**kw, absorb_max_queries=1)
    absorbed = MLAttention(**kw, absorb_max_queries=64)
    params = expanded.init(jax.random.PRNGKey(1), x)
    a = expanded.apply(params, x)
    b = absorbed.apply(params, x)
    assert float(jnp.abs(a).max()) > 0.1
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def moe_layer(**more):
    sizes = dict(dim=64, num_experts=8, top_k=3, hidden=48, shared_hidden=96)
    return DroplessMoE(**dict(sizes, **more))


def reference_layer(ref, params, x):
    """The reference's expert layer on the program's parameters."""
    p = params["params"]
    half = p["w_gate_up"].shape[-1] // 2
    shared = p["shared_gate_up"].shape[-1] // 2
    layer = {
        "router": p["router"], "e_gate": p["w_gate_up"][..., :half],
        "e_up": p["w_gate_up"][..., half:], "e_down": p["w_down"],
        "s_gate": p["shared_gate_up"][:, :shared],
        "s_up": p["shared_gate_up"][:, shared:], "s_down": p["shared_down"],
    }
    arch = ref.sizes_of(CONFIG)["arch"]
    return np.asarray(ref._experts(x, layer, arch, "f32"))


@pytest.mark.parametrize("routing", ["uneven", "an_empty_expert", "all_at_one"])
def test_dropless_layer_matches_the_loop(ref, routing):
    """Every routed token is computed, whatever the routing: as it falls,
    with an expert that no token chooses, with every token at one expert."""
    layer = moe_layer()
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (40, 64))) + 0.1
    params = layer.init(jax.random.PRNGKey(3), x)
    router = params["params"]["router"]
    if routing == "an_empty_expert":
        router = router.at[:, 5].set(-1.0)   # x > 0: its logit is far below
    if routing == "all_at_one":
        router = router.at[:, 2].set(1.0)    # every token's first choice
    params = {"params": dict(params["params"], router=router)}
    got, sizes = layer.apply(params, x)
    sizes = np.asarray(sizes)
    assert sizes.sum() == 40 * 3             # nothing dropped
    if routing == "an_empty_expert":
        assert sizes[5] == 0
    if routing == "all_at_one":
        assert sizes[2] == 40
    np.testing.assert_allclose(
        np.asarray(got), reference_layer(ref, params, x), atol=2e-5)


def test_a_padded_token_is_neither_routed_nor_counted():
    layer = moe_layer()
    x = jax.random.normal(jax.random.PRNGKey(4), (10, 64))
    params = layer.init(jax.random.PRNGKey(5), x)
    mask = jnp.arange(10) < 7
    routed, sizes = layer.apply(params, x, mask, method=DroplessMoE.routed_part)
    whole, _ = layer.apply(params, x, method=DroplessMoE.routed_part)
    assert int(sizes.sum()) == 7 * 3
    np.testing.assert_allclose(np.asarray(routed[:7]), np.asarray(whole[:7]), atol=1e-6)
    assert float(jnp.abs(routed[7:]).max()) == 0.0


def solar_reference_layer(params, x, top_k):
    """The uncut expert layer of the Solar-Open2 reference (gates
    renormalised over the chosen, ``norm_topk_prob: true``) on the program's
    parameters: every expert held, one shared."""
    path = os.path.join(ROOT, "benchmark", "reference", "solar_open2.py")
    spec = importlib.util.spec_from_file_location("reference_solar_open2", path)
    solar = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(solar)
    p = params["params"]
    half = p["w_gate_up"].shape[-1] // 2
    shared = p["shared_gate_up"].shape[-1] // 2
    layer = {
        "router": p["router"], "e_gate": p["w_gate_up"][..., :half],
        "e_up": p["w_gate_up"][..., half:], "e_down": p["w_down"],
        "s_gate": p["shared_gate_up"][:, :shared],
        "s_up": p["shared_gate_up"][:, shared:], "s_down": p["shared_down"],
    }
    arch = solar.Arch(
        heads=4, kv_heads=2, head_dim=16, lin_heads=4, lin_dim=16, taps=4,
        top_k=top_k, held_first=0, held=p["w_down"].shape[0], rms_eps=1e-5,
        routed_scaling=1.0, gqa_gate=True, neg_eigval=True, pad_to=16,
        query_block=16)
    return np.asarray(solar.experts_layer(x, layer, arch=arch))


@pytest.mark.parametrize("shares, experts, top_k, renormalised", [
    (2, 8, 3, False), (4, 8, 3, False),
    # Solar-Open2's layer: 16 experts in 4 shares of 4, the gates
    # renormalised over the chosen 4 of ALL 16, one shared expert
    (4, 16, 4, True),
])
def test_expert_shares_add_up_to_the_whole_layer(ref, shares, experts, top_k,
                                                 renormalised):
    """One chip's share of an expert-parallel layer routes over all experts
    and returns its own experts' part; the parts of all shares and the
    shared expert, counted ONCE, are the uncut reference's whole layer."""
    sizes = dict(num_experts=experts, top_k=top_k, norm_topk_prob=renormalised)
    whole = moe_layer(**sizes)
    x = jax.random.normal(jax.random.PRNGKey(6), (24, 64))
    params = whole.init(jax.random.PRNGKey(7), x)
    held = experts // shares
    total = np.asarray(whole.apply(params, x, method=DroplessMoE.shared_part))
    counted = 0
    for i in range(shares):
        share = moe_layer(experts_held=(i * held, held), **sizes)
        p = dict(params["params"])
        p["w_gate_up"] = p["w_gate_up"][i * held:(i + 1) * held]
        p["w_down"] = p["w_down"][i * held:(i + 1) * held]
        part, group_sizes = share.apply({"params": p}, x, method=DroplessMoE.routed_part)
        total = total + np.asarray(part)
        counted += int(group_sizes.sum())
    assert counted == 24 * top_k
    want = (solar_reference_layer(params, x, top_k) if renormalised
            else reference_layer(ref, params, x))
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_long_calls_run_in_pieces_with_the_same_result():
    x = jax.random.normal(jax.random.PRNGKey(8), (50, 64))
    params = moe_layer().init(jax.random.PRNGKey(9), x)
    one, sizes_one = moe_layer().apply(params, x)
    pieces, sizes_pieces = moe_layer(token_chunk=16).apply(params, x)
    np.testing.assert_allclose(np.asarray(one), np.asarray(pieces), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(sizes_one), np.asarray(sizes_pieces))


# ------------------------------------------------ through the engine

# the served tokens' mean logit gap as benchmark/drivers/serve.py computes
# it.  Sound: 0 in float32 (the served token IS the reference's first
# choice), 5e-4 in bfloat16 here.  A program that left the routed experts or
# the rotary part out reads 0.28 and 0.60.
GAP_LIMIT = {"float32": 1e-3, "bfloat16": 0.02}


@pytest.fixture(scope="module")
def served(ref, weights):
    """{dtype: (prompts, tokens served through InferenceEngine + scheduler)}"""
    from pytorch_distributed_training_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_training_tpu.serving import InferenceEngine

    _, _, tree = weights
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32) for n in (5, 12, 9, 3)]
    out = {}
    for dtype in DTYPES:
        model, p = program(dtype, tree)
        with InferenceEngine(
            model, p, {}, make_mesh(), is_lm=True, batch_buckets=[1, 4],
            seq_buckets=[8, 16], max_batch_size=4, max_delay_ms=1.0,
            max_new_tokens=8, temperature=0.0, eos_id=None,
            scheduler={"enabled": True, "slots": 4, "block_size": 4,
                       "num_blocks": 32, "prefix_cache": True},
        ) as engine:
            futures = [engine.submit(q) for q in prompts]
            tokens = [f.result(timeout=300)["tokens"] for f in futures]
            snapshot = engine.metrics.snapshot()
        out[dtype] = (prompts, tokens, snapshot)
    return out


def mean_gap(ref, weights, prompts, tokens, chosen=None):
    """How far below the reference's best logit the served token's lies,
    the mean over the generated positions; with ``chosen`` the token a
    crippled forward would have served instead."""
    sizes, params, _ = weights
    gaps = []
    for prompt, toks in zip(prompts, tokens):
        seq = jnp.asarray(np.concatenate([prompt, toks[:-1]]).astype(np.int32))
        rows = np.asarray(ref.logits_one(params, seq))[len(prompt) - 1:]
        if chosen is not None:
            toks = np.asarray(ref.logits_one(params, seq, **chosen))[
                len(prompt) - 1:].argmax(-1)
        gaps.append(rows.max(-1) - rows[np.arange(len(rows)), np.asarray(toks)])
    return float(np.concatenate(gaps).mean())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_served_tokens_logit_gap_is_inside_the_limit(ref, weights, served, dtype):
    prompts, tokens, snapshot = served[dtype]
    assert all(len(t) == 8 for t in tokens)
    assert mean_gap(ref, weights, prompts, tokens) <= GAP_LIMIT[dtype]
    # the decode program's two counts reached ServingMetrics
    layers, top_k = 2, 3
    assert snapshot["moe_experts_hit_count"] >= 7
    assert layers <= snapshot["moe_experts_hit_mean"] <= layers * 8
    assert snapshot["moe_expert_load_max_p50"] >= layers
    assert snapshot["moe_load_max_over_mean_p50"] >= 1.0


@pytest.mark.parametrize("left_out", ["routed", "rotary"])
def test_a_program_that_leaves_a_part_out_fails_the_limit(ref, weights, served, left_out):
    """The comparison can tell: the tokens a forward without the routed
    experts, or without the rotary part, would serve lie far outside the
    limit that the sound program keeps."""
    prompts, tokens, _ = served["float32"]
    gap = mean_gap(ref, weights, prompts, tokens, chosen={left_out: False})
    assert gap > 3 * max(GAP_LIMIT.values())


def test_training_is_refused_with_a_reason():
    from types import SimpleNamespace

    from pytorch_distributed_training_tpu.engine.topology import parse_topology

    cfg = {"model": {"name": "DeepseekV2", "hidden_size": 64}}
    with pytest.raises(ValueError, match="served, not trained"):
        parse_topology(SimpleNamespace(), cfg, {"dtype": "float32"}, None)


def test_pool_leaves_are_found_by_what_the_attention_declares(weights):
    """kv_transfer's leaves of a latent pool: one a layer, found by the
    attention module's declaration and the pool's rows, not by a name that
    the serving code spells."""
    from pytorch_distributed_training_tpu.serving.decode import build_paged_fns
    from pytorch_distributed_training_tpu.serving.kv_transfer import pool_row_leaves

    _, _, tree = weights
    model, p = program("float32", tree)
    pool = build_paged_fns(model, 4, 8).init_pool(p)
    leaves = pool_row_leaves(pool, 32)
    # a row of 32 + 8 values in one whole lane tile
    assert [leaf.shape for _, leaf in leaves] == [(32, 128)] * 3
    assert pool_row_leaves(pool, 31) == []


def test_the_lanes_past_a_row_stay_zero_through_every_program(weights):
    """A leaf's row is whole lane tiles and no program writes anything but
    zeros past ``rank + rope``: after a prefill, two decode steps and a
    ``copy_rows`` the pool's written rows hold values in their first 40
    lanes and zeros in the other 88, as a fresh pool does everywhere."""
    from pytorch_distributed_training_tpu.serving.decode import build_paged_fns

    _, _, tree = weights
    model, p = program("bfloat16", tree)
    fns = build_paged_fns(model, 4, 8)
    pool = fns.init_pool(p)
    assert all(not np.asarray(leaf, np.float32).any() for leaf in jax.tree.leaves(pool))
    keys = jnp.stack([jax.random.PRNGKey(0)] * 2)
    row, none = np.zeros(2, np.int32), np.full(2, -1, np.int32)
    tables = np.asarray([[3, 5], [6, 0]], np.int32)
    prompt = np.asarray([[7, 8, 9, 10, 11], [12, 13, 14, 0, 0]], np.int32)
    positions = np.asarray([[0, 1, 2, 3, 4], [0, 1, 2, -1, -1]], np.int32)
    tok, _, pool, *_ = fns.prefill(
        p, pool, prompt, positions, tables, np.asarray([4, 2], np.int32), keys,
        row, none)
    for pos in ([5, 3], [6, 4]):
        tok, _, pool, *_ = fns.decode_step(
            p, pool, np.asarray(tok), np.ones(2, bool), np.asarray(tok),
            np.asarray(pos, np.int32), tables, keys, row, none)
    # row 0: positions 0-6 in blocks 3 and 5; row 1: 0-4 in blocks 6 and 0
    written = np.asarray([12, 13, 14, 15, 20, 21, 22, 24, 25, 26, 27, 0])
    copied = np.arange(4, 8)  # block 1 <- block 3; the rest dropped
    oob = np.full(4, 32, np.int32)
    pool = fns.copy_rows(pool, np.concatenate([np.arange(12, 16), oob]).astype(np.int32),
                         np.concatenate([copied, oob]).astype(np.int32))
    for leaf in jax.tree.leaves(pool):
        leaf = np.asarray(leaf, np.float32)
        assert leaf.shape == (32, 128)
        assert not leaf[:, 40:].any()
        live = np.concatenate([written, copied])
        assert np.abs(leaf[live, :40]).max(axis=1).min() > 0
        np.testing.assert_array_equal(leaf[copied], leaf[12:16])
        assert not np.delete(leaf, live, axis=0).any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_s_arm_matches_one_full_forward(ref, dtype, monkeypatch):
    """``test_prefill_then_paged_decode_matches_one_full_forward`` with the
    decode steps through the kernel (interpreted; the prefill keeps the
    gather arm): a latent of one lane tile, the narrowest the kernel reads,
    in a leaf of two, blocks of 16, and dense layers only (the routing's
    question is the expert layer's too, and its kernel has no CPU form)."""
    from pytorch_distributed_training_tpu.ops import flash_attention as gate
    from pytorch_distributed_training_tpu.ops import mla_paged_decode

    config = dict(CONFIG, kv_lora_rank=128, first_k_dense_replace=3)
    sizes = ref.sizes_of(config)
    host = jax.device_get(ref.make_params(7, sizes))
    params = jax.tree.map(jnp.asarray, host)
    model, p = program(dtype, ref.to_checkpoint_tree(host), kv_lora_rank=128,
                       first_k_dense_replace=3)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32) for n in (9, 5)]
    tables = np.asarray([[3, 1], [2, 0]], np.int32)
    kernel, calls = mla_paged_decode.mla_paged_decode, []

    def interpreted(q_lat, q_pe, leaf, *rest, **kw):
        calls.append(leaf.shape)
        return kernel(q_lat, q_pe, leaf, *rest, interpret=True, **kw)

    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    monkeypatch.setattr(mla_paged_decode, "mla_paged_decode", interpreted)
    seqs, served, _ = paged_run(model, p, prompts, tables, 6, 16, 4, jnp.nan)
    assert set(calls) == {(4, 16, 256)}  # the decode call's trace, a layer
    worst, mean = PREFILL_LIMITS[dtype]
    for r, prompt in enumerate(prompts):
        want = np.asarray(ref.logits_for(
            params, np.array(seqs[r], np.int32), sizes["H"], "f32"))
        want = want[len(prompt) - 1:]
        assert np.isfinite(served[r]).all()
        assert np.abs(served[r] - want).max() < worst
        assert np.abs(served[r] - want).mean() < mean


@pytest.mark.parametrize("family", ["latent_leaf", "key_value_pair"])
def test_warmup_says_what_the_decode_program_keeps_beside_the_pool(weights, family):
    """The scheduler path's warm-up reads the compiled decode step's
    temporaries into the gauge ``decode_program_temp_bytes``, beside
    ``pool_aliased_bytes``: a whole leaf that a step copies is a leaf's
    bytes there, while the alias reads the whole pool either way."""
    from pytorch_distributed_training_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_training_tpu.serving import InferenceEngine

    if family == "latent_leaf":
        model, p = program("float32", weights[2])
    else:
        model = get_model("TransformerLM", num_classes=VOCAB, embed_dim=32,
                          depth=2, num_heads=4, max_len=32)
        p = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    with InferenceEngine(
        model, p, {}, make_mesh(), is_lm=True, batch_buckets=[4], seq_buckets=[8],
        max_batch_size=4, max_delay_ms=1.0, max_new_tokens=4, temperature=0.0,
        eos_id=None, scheduler={"enabled": True, "slots": 4, "block_size": 4,
                                "num_blocks": 32, "prefix_cache": False},
    ) as engine:
        assert "decode_program_temp_bytes" not in engine.metrics.snapshot()
        engine.warmup()
        snapshot = engine.metrics.snapshot()
        sched = engine.scheduler
        pool_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(sched._pool))
        step = sched._step_inputs(())
        decode = sched._fns.decode_step.lower(
            sched.params, sched._pool, sched._zero_carry(), *step,
            *sched._state_rows(np.full((4,), -1, np.int32))).compile()
    assert snapshot["pool_aliased_bytes"] == snapshot["kv_pool_bytes"] == pool_bytes
    temp = decode.memory_analysis().temp_size_in_bytes
    assert snapshot["decode_program_temp_bytes"] == temp > 0



def test_a_model_without_experts_keeps_its_programs_outputs():
    """The decode program of a model that states no ``moe_shape`` returns
    what it returned before there was one: token, finite flag, pool."""
    from pytorch_distributed_training_tpu.serving.decode import build_paged_fns

    model = get_model("TransformerLM", num_classes=64, embed_dim=32, depth=1,
                      num_heads=2, max_len=32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    fns = build_paged_fns(model, 4, 8)
    out = fns.decode_step(
        params, fns.init_pool(params), np.zeros(2, np.int32), np.ones(2, bool),
        np.zeros(2, np.int32), np.zeros(2, np.int32),
        np.zeros((2, 2), np.int32), jnp.stack([jax.random.PRNGKey(0)] * 2),
        np.zeros(2, np.int32), np.full(2, -1, np.int32))
    assert len(out) == 3


@pytest.mark.parametrize("name", ["prefill", "decode_step", "decode_step.carried"])
def test_every_latent_pool_leaf_is_donated_to_the_program(weights, name):
    """The latent pool's leaves (one a layer) are donated like a K/V pair's:
    the lowered program marks each for reuse, and the pool passed in is gone
    once the call is made; the one returned is the pool.  The one decode
    program under a mask of all rows (a caller that knows every row's token)
    and of none (``.carried``: the ring feeds each row the carried token)."""
    from pytorch_distributed_training_tpu.serving.decode import build_paged_fns

    _, _, tree = weights
    model, p = program("float32", tree)
    fns = build_paged_fns(model, 4, 8)
    pool = fns.init_pool(p)
    n_leaves = len(jax.tree_util.tree_leaves(pool))
    assert n_leaves == 3
    keys = jnp.stack([jax.random.PRNGKey(0)] * 2)
    row = np.zeros(2, np.int32)
    tables = np.zeros((2, 2), np.int32)
    none = np.full(2, -1, np.int32)
    args = {
        "prefill": (p, pool, np.zeros((2, 4), np.int32),
                    np.full((2, 4), -1, np.int32), tables, row, keys, row, none),
        "decode_step": (p, pool, row, np.ones(2, bool), row, none, tables,
                        keys, row, none),
        "decode_step.carried": (p, pool, row, np.zeros(2, bool), row, none,
                                tables, keys, row, none),
    }[name]
    fn = getattr(fns, name.partition(".")[0])
    text = fn.lower(*args).as_text()
    marked = text.count("tf.aliasing_output") + text.count("jax.buffer_donor")
    assert marked == n_leaves
    out = fn(*args)
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(pool))
    assert jax.tree.structure(out[2]) == jax.tree.structure(pool)
    assert not any(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(out[2]))
