"""Serving subsystem oracles (serving/ + the TransformerLM decode mode).

The load-bearing test is decode parity: the KV-cache incremental path must
reproduce the full-forward logits exactly (same math, fp32, CPU) including
rows with DIFFERENT prompt lengths right-padded into one batch — the
property the per-row cache positions (ops/attention.py) exist for.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models.transformer_lm import TransformerLM
from pytorch_distributed_training_tpu.serving.batcher import DynamicBatcher
from pytorch_distributed_training_tpu.serving.decode import build_generate_fn
from pytorch_distributed_training_tpu.serving.metrics import ServingMetrics

VOCAB = 61


def small_lm(**kwargs):
    kw = dict(vocab_size=VOCAB, max_len=32, embed_dim=32, depth=2, num_heads=4)
    kw.update(kwargs)
    return TransformerLM(**kw)


@pytest.fixture(scope="module")
def lm_and_params():
    model = small_lm()
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


# --------------------------------------------------------------------- #
# decode parity


@pytest.mark.slow
def test_decode_parity_incremental_matches_full(lm_and_params):
    model, params = lm_and_params
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 12), 0, VOCAB)
    full = model.apply({"params": params}, toks)

    dm = model.clone(decode=True)
    prompt = 5
    prefill, variables = dm.apply(
        {"params": params}, toks[:, :prompt], mutable=["cache"]
    )
    np.testing.assert_allclose(
        np.asarray(prefill), np.asarray(full[:, :prompt]), rtol=2e-5, atol=2e-5
    )
    cache = variables["cache"]
    for i in range(prompt, 12):
        pos = jnp.full((3,), i, jnp.int32)
        step, variables = dm.apply(
            {"params": params, "cache": cache},
            toks[:, i : i + 1],
            pos,
            mutable=["cache"],
        )
        cache = variables["cache"]
        np.testing.assert_allclose(
            np.asarray(step[:, 0]), np.asarray(full[:, i]), rtol=2e-5, atol=2e-5
        )


def test_decode_parity_ragged_prompt_lengths(lm_and_params):
    """Right-padded rows of different lengths in ONE batch stay exact."""
    model, params = lm_and_params
    rng = np.random.default_rng(2)
    lens = [3, 7, 5]
    pad_s = max(lens)
    rows = [rng.integers(0, VOCAB, ln).astype(np.int32) for ln in lens]
    batch = np.zeros((len(lens), pad_s), np.int32)
    for i, row in enumerate(rows):
        batch[i, : lens[i]] = row

    dm = model.clone(decode=True)
    prefill, variables = dm.apply(
        {"params": params}, jnp.asarray(batch), mutable=["cache"]
    )
    cache = variables["cache"]
    # continue each row from ITS OWN length with the same continuation token
    cont = np.full((len(lens), 1), 9, np.int32)
    pos = jnp.asarray(lens, jnp.int32)  # next position = prompt_len
    step, _ = dm.apply(
        {"params": params, "cache": cache}, jnp.asarray(cont), pos,
        mutable=["cache"],
    )
    for i, ln in enumerate(lens):
        # oracle: full forward over just this row's real tokens + cont
        seq = np.concatenate([rows[i], [9]])[None]
        full = model.apply({"params": params}, jnp.asarray(seq))
        np.testing.assert_allclose(
            np.asarray(step[i, 0]), np.asarray(full[0, ln]),
            rtol=2e-5, atol=2e-5,
        )
        # and the prefill logits at the row's last real position match too
        np.testing.assert_allclose(
            np.asarray(prefill[i, ln - 1]), np.asarray(full[0, ln - 1]),
            rtol=2e-5, atol=2e-5,
        )


@pytest.mark.slow
def test_generate_greedy_matches_manual_argmax(lm_and_params):
    """build_generate_fn's loop = repeated full-forward argmax continuation."""
    model, params = lm_and_params
    max_new = 4
    gen = build_generate_fn(model, max_new_tokens=max_new, temperature=0.0)
    rng = np.random.default_rng(3)
    lens = [2, 6]
    pad_s = 8
    toks = np.zeros((2, pad_s), np.int32)
    for i, ln in enumerate(lens):
        toks[i, :ln] = rng.integers(0, VOCAB, ln)
    out, gen_len = gen(
        params, jnp.asarray(toks), jnp.asarray(lens, jnp.int32),
        jax.random.PRNGKey(0),
    )
    out = np.asarray(out)
    assert np.asarray(gen_len).tolist() == [max_new, max_new]  # no eos_id set
    for i, ln in enumerate(lens):
        seq = list(toks[i, :ln])
        for j in range(max_new):
            logits = model.apply(
                {"params": params}, jnp.asarray([seq], jnp.int32)
            )
            nxt = int(np.asarray(logits)[0, -1].argmax())
            assert out[i, j] == nxt, f"row {i} token {j}"
            seq.append(nxt)


def test_generate_eos_early_exit(lm_and_params):
    """Rows report gen_len up to and including EOS; later slots are 0."""
    model, params = lm_and_params
    max_new = 6
    toks = np.asarray([[4, 2, 0, 0]], np.int32)
    lens = np.asarray([2], np.int32)
    # find what greedy generates, then declare its SECOND token the EOS so
    # the loop must stop at gen_len == 2
    free = build_generate_fn(model, max_new_tokens=max_new, temperature=0.0)
    out_free, _ = free(params, jnp.asarray(toks), jnp.asarray(lens),
                       jax.random.PRNGKey(0))
    eos = int(np.asarray(out_free)[0, 1])
    gen = build_generate_fn(
        model, max_new_tokens=max_new, temperature=0.0, eos_id=eos
    )
    out, gen_len = gen(params, jnp.asarray(toks), jnp.asarray(lens),
                       jax.random.PRNGKey(0))
    out, gen_len = np.asarray(out), np.asarray(gen_len)
    assert gen_len[0] == 2
    assert out[0, 1] == eos
    assert not out[0, 2:].any()


def test_decode_mode_rejects_seq_axis():
    model = small_lm(seq_axis="sequence", decode=True)
    with pytest.raises(ValueError, match="single-shard"):
        model.apply({}, jnp.zeros((1, 4), jnp.int32), mutable=["cache"])


# --------------------------------------------------------------------- #
# batcher


def test_batcher_flushes_on_size():
    batches = []
    done = threading.Event()

    def run(reqs):
        batches.append(len(reqs))
        if sum(batches) >= 4:
            done.set()
        return [r.payload for r in reqs]

    with DynamicBatcher(run, max_batch_size=4, max_delay_ms=10_000) as b:
        futures = [b.submit(i) for i in range(4)]
        assert [f.result(timeout=5) for f in futures] == [0, 1, 2, 3]
        assert done.wait(timeout=5)
    # the hour-long delay never elapsed: the size bound alone flushed
    assert batches[0] == 4


def test_batcher_flushes_on_deadline():
    batches = []

    def run(reqs):
        batches.append(len(reqs))
        return [r.payload for r in reqs]

    with DynamicBatcher(run, max_batch_size=64, max_delay_ms=30) as b:
        t0 = time.monotonic()
        fut = b.submit("only")
        assert fut.result(timeout=5) == "only"
        waited = time.monotonic() - t0
    assert batches == [1]
    # flushed by the delay bound, far below any size-bound fill
    assert waited < 5


def test_batcher_propagates_exceptions():
    def run(reqs):
        raise RuntimeError("boom")

    with DynamicBatcher(run, max_batch_size=2, max_delay_ms=1) as b:
        fut = b.submit(0)
        with pytest.raises(RuntimeError, match="boom"):
            fut.result(timeout=5)


def test_batcher_close_drains_queue():
    seen = []

    def run(reqs):
        time.sleep(0.02)  # let a backlog build behind the first flush
        seen.extend(r.payload for r in reqs)
        return [None] * len(reqs)

    b = DynamicBatcher(run, max_batch_size=2, max_delay_ms=1)
    futures = [b.submit(i) for i in range(7)]
    b.close()
    for f in futures:
        f.result(timeout=5)
    assert sorted(seen) == list(range(7))


# --------------------------------------------------------------------- #
# engine: compile count bounded by the bucket grid


@pytest.fixture(scope="module")
def lm_engine():
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = {
        "dataset": {"name": "synthetic_text", "n_classes": VOCAB},
        "model": {
            "name": "TransformerLM",
            "embed_dim": 32,
            "depth": 2,
            "num_heads": 4,
            "max_len": 32,
        },
        "serving": {
            "dtype": "float32",
            "max_batch_size": 4,
            "max_delay_ms": 2,
            "batch_buckets": [4],
            "seq_buckets": [8, 16],
            "max_new_tokens": 4,
            "temperature": 0.0,
        },
    }
    with InferenceEngine.from_config(cfg) as engine:
        yield engine


def test_engine_compile_count_bounded_by_buckets(lm_engine):
    rng = np.random.default_rng(0)
    futures = [
        lm_engine.submit(rng.integers(0, VOCAB, ln).astype(np.int32))
        for ln in (1, 3, 5, 8, 9, 11, 14, 16, 2, 13)  # both seq buckets,
        # many distinct lengths and batch fills
    ]
    results = [f.result(timeout=120) for f in futures]
    for res in results:
        assert 1 <= res["gen_len"] <= 4
        assert res["tokens"].shape == (res["gen_len"],)
    # 1 batch bucket x 2 seq buckets, 2 programs per cell (prefill +
    # decode are separate jits since the round-6 phase split) => at most
    # 4 XLA programs ever
    assert lm_engine.compile_count() <= 4


def test_engine_rejects_oversized_prompt(lm_engine):
    with pytest.raises(ValueError, match="exceeds largest seq bucket"):
        lm_engine.submit(np.zeros(17, np.int32))
    with pytest.raises(ValueError, match="1-D"):
        lm_engine.submit(np.zeros((2, 4), np.int32))


def test_engine_bucket_overflow_guard():
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = {
        "dataset": {"name": "synthetic_text", "n_classes": VOCAB},
        "model": {"name": "TransformerLM", "embed_dim": 32, "depth": 1,
                  "num_heads": 4, "max_len": 16},
        "serving": {"dtype": "float32", "seq_buckets": [16],
                    "max_new_tokens": 4},
    }
    with pytest.raises(ValueError, match="exceeds"):
        InferenceEngine.from_config(cfg)


# --------------------------------------------------------------------- #
# checkpoint -> serving restore round-trip


def test_load_serving_state_round_trip(tmp_path, lm_and_params):
    from pytorch_distributed_training_tpu.engine.checkpoint import (
        Checkpointer,
        load_serving_state,
    )
    from pytorch_distributed_training_tpu.engine.steps import TrainState

    model, params = lm_and_params
    state = TrainState(
        params=params, batch_stats={}, opt_state={}, ema={}
    )
    ckpt = Checkpointer(str(tmp_path / "ckpt"), interval=1)
    ckpt.save(7, state)
    ckpt.wait()
    ckpt.close()

    restored, batch_stats, step = load_serving_state(str(tmp_path / "ckpt"))
    assert step == 7
    assert batch_stats == {}
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params,
        restored,
    )


def test_load_serving_state_missing_dir(tmp_path):
    from pytorch_distributed_training_tpu.engine.checkpoint import (
        load_serving_state,
    )

    with pytest.raises(FileNotFoundError):
        load_serving_state(str(tmp_path / "empty"))


# --------------------------------------------------------------------- #
# metrics + CLI


def test_metrics_snapshot_percentiles():
    m = ServingMetrics()
    now = time.monotonic()
    m.record_batch([now - 0.010, now - 0.020], n_items=8, queue_depth=3)
    m.record_batch([now - 0.100], n_items=4, queue_depth=1)
    snap = m.snapshot()
    assert snap["requests"] == 3
    assert snap["batches"] == 2
    assert snap["items"] == 12
    assert snap["max_queue_depth"] == 3
    assert 9.0 <= snap["latency_ms_p50"] <= 105.0
    assert snap["latency_ms_p50"] <= snap["latency_ms_p99"]
    assert snap["latency_ms_p99"] <= 105.0  # largest recorded ~100ms


def test_metrics_phase_split_and_gen_lens():
    """Round 6: per-request generated-token counts + prefill/decode rates."""
    from pytorch_distributed_training_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    now = time.monotonic()
    m.record_batch(
        [now, now], n_items=7, gen_lens=[3, 4], prompt_tokens=20,
        prefill_s=0.01, decode_s=0.07,
    )
    m.record_batch(
        [now], n_items=2, gen_lens=[2], prompt_tokens=5,
        prefill_s=0.01, decode_s=0.01,
    )
    snap = m.snapshot()
    assert snap["gen_tokens"] == 9
    assert snap["gen_len_mean"] == pytest.approx(3.0)
    assert snap["gen_len_p50"] == pytest.approx(3.0)
    # PR 7 attribution fix: generated token 0 of each request is SAMPLED
    # BY THE PREFILL PROGRAM, so it counts toward prefill throughput (3
    # requests -> +3 prefill tokens) and not decode's (9 gen - 3)
    assert snap["prefill_tokens_per_sec"] == pytest.approx((25 + 3) / 0.02)
    assert snap["decode_tokens_per_sec"] == pytest.approx((9 - 3) / 0.08)
    # image-path batches (no gen_lens) must not emit the LM-only fields
    m2 = ServingMetrics()
    m2.record_batch([now], n_items=4)
    assert "gen_tokens" not in m2.snapshot()
    assert "prefill_tokens_per_sec" not in m2.snapshot()


def test_metrics_bounded_under_sustained_traffic():
    """PR 6 fix: per-request latency/batch/gen-len storage no longer grows
    one float per request forever — it's an Algorithm-R reservoir.  Counts
    and means stay EXACT under eviction; percentiles stay estimates of the
    true stream percentiles (the reservoir is a uniform sample of the whole
    stream, not a sliding window)."""
    from pytorch_distributed_training_tpu.serving.metrics import _RESERVOIR

    m = ServingMetrics()
    n = 3 * _RESERVOIR  # well past capacity -> heavy eviction
    # latencies sweep 0..~120ms uniformly so percentiles have a known truth;
    # stamp per call (record_batch reads its own monotonic clock)
    for i in range(n):
        m.record_batch(
            [time.monotonic() - (i % 1200) * 1e-4], n_items=1, gen_lens=[i % 7]
        )
    snap = m.snapshot()
    # exact-under-eviction surfaces
    assert snap["requests"] == n
    assert snap["batches"] == n
    assert snap["items"] == n
    assert snap["gen_tokens"] == sum(i % 7 for i in range(n))
    assert snap["latency_ms_mean"] == pytest.approx(59.95, abs=2.0)
    # percentile estimates track the true uniform stream (true p50=60, p99=118.8);
    # reservoir std at n=2048 keeps 15%/10% above 4 sigma
    assert snap["latency_ms_p50"] == pytest.approx(60.0, rel=0.15)
    assert snap["latency_ms_p99"] == pytest.approx(118.8, rel=0.10)
    # storage is actually bounded at the reservoir
    assert len(m._latency_ms._sample) == _RESERVOIR
    assert len(m._batch_size._sample) == _RESERVOIR
    assert len(m._gen_len._sample) == _RESERVOIR


def test_serving_cli_smoke(tmp_path, capsys):
    """The acceptance-criteria round trip, in-process (fast: tiny model)."""
    import json

    from pytorch_distributed_training_tpu.serving.__main__ import main

    cfg = tmp_path / "serve.yml"
    cfg.write_text(
        """
dataset: {name: synthetic_text, n_classes: 61}
model: {name: TransformerLM, embed_dim: 32, depth: 2, num_heads: 4, max_len: 32}
serving:
    dtype: float32
    max_batch_size: 4
    max_delay_ms: 2
    seq_buckets: [8, 16]
    max_new_tokens: 4
"""
    )
    rc = main(
        ["--config", str(cfg), "--requests", "8", "--log-dir", str(tmp_path)]
    )
    assert rc == 0
    tail = capsys.readouterr().out.strip().splitlines()[-1]
    snap = json.loads(tail)["serving"]
    assert snap["requests"] == 8
    # 2 per exercised bucket cell since the prefill/decode phase split
    assert snap["compile_count"] <= 4
    assert snap["latency_ms_p50"] > 0


# --------------------------------------------------------------------- #
# PR 7: paged KV pool — block allocator


def test_block_allocator_alloc_free_recycle():
    from pytorch_distributed_training_tpu.serving.kv_pool import BlockAllocator

    a = BlockAllocator(num_blocks=4, block_size=8)
    assert a.num_free == 4
    got = a.alloc(3)
    assert sorted(got) == [0, 1, 2] and a.num_free == 1
    assert a.alloc(0) == []
    assert a.alloc(2) is None  # exhaustion: all-or-nothing, no partial grant
    assert a.num_free == 1  # failed alloc took nothing
    a.free([1])
    # LIFO recycling: the just-freed block is re-issued first
    assert a.alloc(1) == [1]
    with pytest.raises(ValueError, match="double free"):
        a.free([3, 3])


def test_paged_pool_admission_control_and_refcounts():
    from pytorch_distributed_training_tpu.serving.kv_pool import PagedKVPool

    pool = PagedKVPool(num_blocks=4, block_size=4, prefix_cache=False)
    # plen 8 + max_new 4 = 12 tokens -> 3 blocks
    a1 = pool.admit(list(range(8)), 4)
    assert a1 is not None and len(a1.block_ids) == 3 and a1.n_shared == 0
    assert pool.blocks_in_use == 3
    # second identical footprint cannot fit -> wait (None), NEVER an OOM
    assert pool.admit(list(range(100, 108)), 4) is None
    assert pool.blocks_in_use == 3  # failed admit leaked nothing
    pool.release(a1)
    assert pool.blocks_in_use == 0
    a2 = pool.admit(list(range(100, 108)), 4)
    assert a2 is not None
    # a footprint larger than the whole pool can never be satisfied
    with pytest.raises(ValueError, match="only has"):
        pool.admit(list(range(16)), 4)


def test_paged_pool_prefix_cache_reuse_and_eviction():
    from pytorch_distributed_training_tpu.serving.kv_pool import PagedKVPool

    pool = PagedKVPool(num_blocks=6, block_size=4, prefix_cache=True)
    prompt = list(range(9))  # 2 full cacheable blocks ((9-1)//4)
    a1 = pool.admit(prompt, 3)  # 3 blocks total
    assert a1.n_shared == 0
    pool.register_prefix(prompt, a1)
    pool.release(a1)
    # request blocks freed, but the 2 cacheable ones stay held by the cache
    assert pool.blocks_in_use == 2
    a2 = pool.admit(prompt, 3)
    assert a2.n_shared == 2 and a2.cached_len == 8
    # shared blocks are the SAME physical blocks, not copies
    assert a2.block_ids[:2] == a1.block_ids[:2]
    pool.release(a2)
    # a big unrelated request forces LRU eviction of the cache-only blocks
    a3 = pool.admit(list(range(50, 66)), 8)  # 6 blocks = whole pool
    assert a3 is not None and pool.prefix_evictions == 2
    assert pool.lookup_prefix(prompt) == []  # evicted -> cold again
    pool.release(a3)
    assert pool.blocks_in_use == 0


# --------------------------------------------------------------------- #
# PR 7: paged attention — decode parity + bitwise prefix-hit oracle


def test_paged_prefill_prefix_hit_bitwise_logits(lm_and_params):
    """A warm (prefix-hit) suffix prefill must produce BITWISE-identical
    logits to the cold full-prompt prefill at the overlapping positions:
    the gathered pool K/V is the same bytes in the same logical order, and
    per-position layers cannot see batch composition."""
    from pytorch_distributed_training_tpu.serving.decode import build_paged_fns

    model, params = lm_and_params
    fns = build_paged_fns(model, block_size=4, num_blocks=8)
    paged = model.clone(
        decode=True, paged=True, kv_block_size=4, kv_num_blocks=8
    )
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, VOCAB, 7).astype(np.int32)  # 1 cacheable block

    pool0 = fns.init_pool(params)
    cold_logits, v = paged.apply(
        {"params": params, "cache": pool0},
        jnp.asarray(prompt[None]),
        jnp.arange(7, dtype=jnp.int32)[None],
        jnp.asarray([[0, 1]], jnp.int32),
        mutable=["cache"],
    )
    # warm: block 0 (positions 0..3) already filled by the pass above is
    # shared read-only; the suffix runs against a FRESH physical block
    warm_logits, _ = paged.apply(
        {"params": params, "cache": v["cache"]},
        jnp.asarray(prompt[None, 4:]),
        jnp.arange(4, 7, dtype=jnp.int32)[None],
        jnp.asarray([[0, 3]], jnp.int32),
        mutable=["cache"],
    )
    np.testing.assert_array_equal(
        np.asarray(warm_logits[0]), np.asarray(cold_logits[0, 4:])
    )


def _run_scheduler_to_done(sched, futures, limit=200):
    n = 0
    while any(not f.done() for f in futures):
        sched.tick()
        n += 1
        assert n < limit, "scheduler failed to drain"
    return n


def test_scheduler_greedy_parity_with_contiguous(lm_and_params):
    """Acceptance oracle: the paged scheduler reproduces the contiguous
    whole-batch path token for token (greedy)."""
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )

    model, params = lm_and_params
    max_new = 6
    gen = build_generate_fn(model, max_new_tokens=max_new, temperature=0.0,
                            eos_id=1)
    rng = np.random.default_rng(3)
    lens = [2, 6, 4]
    toks = np.zeros((3, 8), np.int32)
    rows = []
    for i, ln in enumerate(lens):
        rows.append(rng.integers(2, VOCAB, ln).astype(np.int32))
        toks[i, :ln] = rows[i]
    out, gl = gen(params, jnp.asarray(toks), jnp.asarray(lens, jnp.int32),
                  jax.random.PRNGKey(7))
    out, gl = np.asarray(out), np.asarray(gl)

    sched = ContinuousScheduler(
        model, params, slots=4, block_size=4, num_blocks=16,
        batch_buckets=[4], seq_buckets=[8], max_new_tokens=max_new,
        temperature=0.0, eos_id=1, start=False,
    )
    futs = [sched.submit(rows[i]) for i in range(3)]
    _run_scheduler_to_done(sched, futs)
    for i, f in enumerate(futs):
        res = f.result()
        assert res["gen_len"] == gl[i]
        np.testing.assert_array_equal(res["tokens"], out[i, : gl[i]])


def test_scheduler_sampled_parity_with_contiguous(lm_and_params):
    """Sampled mode: per-row per-token-index keys make a row's draw
    independent of batch composition, so the scheduler (re-batching rows
    every step) still matches the whole-batch path token for token."""
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )

    model, params = lm_and_params
    max_new = 6
    gen = build_generate_fn(model, max_new_tokens=max_new, temperature=0.8,
                            eos_id=1)
    rng = np.random.default_rng(3)
    lens = [2, 6, 4]
    toks = np.zeros((3, 8), np.int32)
    rows = []
    for i, ln in enumerate(lens):
        rows.append(rng.integers(2, VOCAB, ln).astype(np.int32))
        toks[i, :ln] = rows[i]
    R = jax.random.PRNGKey(7)
    out, gl = gen(params, jnp.asarray(toks), jnp.asarray(lens, jnp.int32), R)
    out, gl = np.asarray(out), np.asarray(gl)

    sched = ContinuousScheduler(
        model, params, slots=4, block_size=4, num_blocks=16,
        batch_buckets=[4], seq_buckets=[8], max_new_tokens=max_new,
        temperature=0.8, eos_id=1, start=False,
    )
    # row r of the whole-batch call draws with fold_in(R, r)
    futs = [
        sched.submit(rows[i], rng=jax.random.fold_in(R, i)) for i in range(3)
    ]
    _run_scheduler_to_done(sched, futs)
    for i, f in enumerate(futs):
        res = f.result()
        assert res["gen_len"] == gl[i]
        np.testing.assert_array_equal(res["tokens"], out[i, : gl[i]])


def test_scheduler_retire_and_refill_deterministic(lm_and_params):
    """Scripted arrival trace: a short request retires mid-flight and its
    slot is refilled from the queue while the long one keeps decoding;
    replaying the trace gives bit-identical streams and tick counts."""
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )

    model, params = lm_and_params
    rng = np.random.default_rng(5)
    p_long = rng.integers(2, VOCAB, 6).astype(np.int32)
    p_short = rng.integers(2, VOCAB, 3).astype(np.int32)
    p_queued = rng.integers(2, VOCAB, 4).astype(np.int32)

    def run_trace():
        sched = ContinuousScheduler(
            model, params, slots=2, block_size=4, num_blocks=16,
            batch_buckets=[2], seq_buckets=[8], max_new_tokens=6,
            temperature=0.0, eos_id=None, start=False,
        )
        f_long = sched.submit(p_long)                      # 6 tokens
        f_short = sched.submit(p_short, max_new_tokens=2)  # retires early
        f_queued = sched.submit(p_queued)                  # waits for a slot
        events = []
        ticks = 0
        while any(not f.done() for f in (f_long, f_short, f_queued)):
            sched.tick()
            ticks += 1
            events.append(
                (sched.active(), f_long.done(), f_short.done(),
                 f_queued.done())
            )
            assert ticks < 100
        # the short row retired first and the queued request was admitted
        # BEFORE the long one finished — iteration-level refill: some tick
        # after the short retirement runs with BOTH slots live again
        assert any(
            e[2] and not e[1] and e[0] == 2 for e in events
        ), "freed slot was not refilled mid-flight"
        results = tuple(
            (f.result()["gen_len"], f.result()["tokens"].tolist())
            for f in (f_long, f_short, f_queued)
        )
        snap = sched.metrics.snapshot()
        return ticks, events, results, snap

    t1, e1, r1, s1 = run_trace()
    t2, e2, r2, s2 = run_trace()
    assert (t1, e1, r1) == (t2, e2, r2)
    assert r1[1][0] == 2  # per-request max_new honored by early retire
    assert s1["retired"] == 3 and s1["admitted"] == 3
    assert 0 < s1["slot_occupancy_mean"] <= 1.0
    assert s1["block_util_max"] <= 1.0


def test_scheduler_admission_waits_instead_of_oom(lm_and_params):
    """Pool exhaustion parks the queue head until blocks free up — the
    request waits, the pool never over-commits."""
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )

    model, params = lm_and_params
    rng = np.random.default_rng(6)
    # each request: plen 8 + max_new 4 = 12 tokens -> 3 blocks of a
    # 4-block pool, so two can never be resident together
    sched = ContinuousScheduler(
        model, params, slots=2, block_size=4, num_blocks=4,
        prefix_cache=False,
        batch_buckets=[2], seq_buckets=[8], max_new_tokens=4,
        temperature=0.0, eos_id=None, start=False,
    )
    f1 = sched.submit(rng.integers(2, VOCAB, 8).astype(np.int32))
    f2 = sched.submit(rng.integers(2, VOCAB, 8).astype(np.int32))
    _run_scheduler_to_done(sched, [f1, f2])
    assert f1.result()["gen_len"] == 4
    assert f2.result()["gen_len"] == 4
    snap = sched.metrics.snapshot()
    assert snap["admission_waits"] >= 1
    assert sched._kv.blocks_in_use == 0  # everything recycled


def test_scheduler_streams_tokens_and_mirrors_telemetry(lm_and_params):
    """on_token sees every token in order, and scheduler counters are
    mirrored into the process telemetry registry (serving_* prefix)."""
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )
    from pytorch_distributed_training_tpu.telemetry.registry import (
        get_registry,
    )

    model, params = lm_and_params
    before = get_registry().counters().get("serving_retired", 0)
    sched = ContinuousScheduler(
        model, params, slots=2, block_size=4, num_blocks=16,
        batch_buckets=[2], seq_buckets=[8], max_new_tokens=4,
        temperature=0.0, eos_id=None, start=False,
    )
    seen = []
    fut = sched.submit(
        np.asarray([5, 9, 13], np.int32), on_token=seen.append
    )
    _run_scheduler_to_done(sched, [fut])
    res = fut.result()
    assert seen == res["tokens"].tolist()
    assert get_registry().counters()["serving_retired"] == before + 1


def test_scheduler_background_loop_and_deadline(lm_and_params):
    """The threaded loop drains submissions without manual ticks; an
    impossible queue deadline resolves with TimeoutError."""
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )

    model, params = lm_and_params
    with ContinuousScheduler(
        model, params, slots=2, block_size=4, num_blocks=16,
        batch_buckets=[2], seq_buckets=[8], max_new_tokens=3,
        temperature=0.0, eos_id=None,
    ) as sched:
        futs = [
            sched.submit(np.asarray([3 + i, 7], np.int32)) for i in range(5)
        ]
        for f in futs:
            assert f.result(timeout=60)["gen_len"] == 3
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit(np.asarray([1], np.int32))


# --------------------------------------------------------------------- #
# PR 7: engine integration — scheduler path, compile-count bound


@pytest.fixture(scope="module")
def sched_engine():
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = {
        "dataset": {"name": "synthetic_text", "n_classes": VOCAB},
        "model": {
            "name": "TransformerLM",
            "embed_dim": 32,
            "depth": 2,
            "num_heads": 4,
            "max_len": 32,
        },
        "serving": {
            "dtype": "float32",
            "max_batch_size": 4,
            "max_delay_ms": 2,
            "batch_buckets": [4],
            "seq_buckets": [8, 16],
            "max_new_tokens": 4,
            "temperature": 0.0,
            "scheduler": {
                "enabled": True,
                "slots": 4,
                "block_size": 4,
                "num_blocks": 32,
                "prefix_cache": True,
            },
        },
    }
    with InferenceEngine.from_config(cfg) as engine:
        yield engine


def test_engine_scheduler_compile_count_independent_of_requests(sched_engine):
    """The XLA program count is pinned by the bucket grid + ONE decode
    step program no matter how many requests stream through."""
    rng = np.random.default_rng(0)
    futures = [
        sched_engine.submit(rng.integers(0, VOCAB, ln).astype(np.int32))
        for ln in (1, 3, 5, 8, 9, 11, 14, 16, 2, 13, 6, 16, 1, 7)
    ]
    results = [f.result(timeout=120) for f in futures]
    for res in results:
        assert 1 <= res["gen_len"] <= 4
        assert res["tokens"].shape == (res["gen_len"],)
    count_now = sched_engine.compile_count()
    # 1 batch bucket x 2 seq buckets prefill programs + 1 decode-step
    # program: <= 3 ever
    assert count_now <= 3
    # MORE traffic (fresh lengths, repeat lengths) must not add programs
    futures = [
        sched_engine.submit(rng.integers(0, VOCAB, ln).astype(np.int32))
        for ln in (4, 10, 12, 15, 3, 8)
    ]
    for f in futures:
        f.result(timeout=120)
    assert sched_engine.compile_count() == count_now
    snap = sched_engine.metrics.snapshot()
    assert snap["retired"] == 20
    assert "slot_occupancy_mean" in snap


def test_engine_scheduler_per_request_max_new_and_streaming(sched_engine):
    seen = []
    fut = sched_engine.submit(
        np.asarray([4, 8, 15], np.int32), max_new_tokens=2,
        on_token=seen.append,
    )
    res = fut.result(timeout=60)
    assert res["gen_len"] <= 2
    assert seen == res["tokens"].tolist()


def test_engine_batcher_path_truncates_per_request_cap(lm_engine):
    """On the legacy batcher path the per-request cap truncates host-side
    (the batch still pays the full decode — the pathology the scheduler
    removes); streaming/rng need the scheduler and fail loudly."""
    fut = lm_engine.submit(np.asarray([4, 8, 15], np.int32), max_new_tokens=2)
    res = fut.result(timeout=60)
    assert res["gen_len"] <= 2
    assert res["tokens"].shape == (res["gen_len"],)
    with pytest.raises(ValueError, match="scheduler"):
        lm_engine.submit(np.asarray([4], np.int32), on_token=lambda t: None)


# --------------------------------------------------------------------- #
# PR 7: batcher backlog no longer counts expired requests


def test_batcher_backlog_sweeps_expired_before_shedding():
    """Doomed (past-deadline) requests sitting in the queue must not eat
    the backlog budget: submit sweeps them out before the depth check, so
    a live request is admitted where it previously shed."""
    from pytorch_distributed_training_tpu.serving.batcher import (
        OverloadedError,
    )

    release = threading.Event()

    def run(reqs):
        release.wait(timeout=10)  # pin the flush thread on the 1st batch
        return [r.payload for r in reqs]

    b = DynamicBatcher(
        run, max_batch_size=1, max_delay_ms=1, max_backlog=2
    )
    try:
        f0 = b.submit("head")  # occupies the flush thread
        time.sleep(0.05)  # let the loop pick f0 up, emptying the queue
        doomed = [b.submit(i, deadline_ms=10) for i in range(2)]
        # backlog now "full" of requests that are already dead on arrival
        time.sleep(0.05)
        live = b.submit("live")  # old code: OverloadedError here
        release.set()
        assert f0.result(timeout=5) == "head"
        assert live.result(timeout=5) == "live"
        for f in doomed:
            with pytest.raises(TimeoutError):
                f.result(timeout=5)
        assert b.timeouts == 2
        # shedding still works against a backlog of LIVE requests
        release.clear()
        g0 = b.submit("head2")
        time.sleep(0.05)
        keep = [b.submit(i) for i in range(2)]
        with pytest.raises(OverloadedError):
            b.submit("overflow")
        release.set()
        g0.result(timeout=5)
        for f in keep:
            f.result(timeout=5)
    finally:
        release.set()
        b.close()


# --------------------------------------------------------------------- #
# multi-tenant decode modes (PR 17): int8 quant, multi-LoRA, speculative


def _paged_sched(model, params, **kw):
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )

    kw.setdefault("slots", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("batch_buckets", [4])
    kw.setdefault("seq_buckets", [8])
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("eos_id", 1)
    return ContinuousScheduler(model, params, start=False, **kw)


def _sched_results(sched, prompts, submit_kwargs=None):
    sk = submit_kwargs or [{}] * len(prompts)
    futs = [sched.submit(p, **s) for p, s in zip(prompts, sk)]
    _run_scheduler_to_done(sched, futs)
    return [f.result() for f in futs]


@pytest.fixture(scope="module")
def mode_prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(2, VOCAB, ln).astype(np.int32) for ln in (2, 6, 4)]


@pytest.fixture(scope="module")
def plain_sched_results(lm_and_params, mode_prompts):
    """Shared reference: plain paged-scheduler greedy streams + compile
    count — every mode oracle compares against this one run."""
    model, params = lm_and_params
    sched = _paged_sched(model, params)
    res = _sched_results(sched, mode_prompts)
    return res, sched.compile_count()


def test_quant_roundtrip_bounded_error(lm_and_params):
    """Per-channel symmetric int8: dequant(quant(W)) is within half a
    quantization step of W per element, and only 2-D kernels quantize."""
    from pytorch_distributed_training_tpu.ops.quant import (
        dequantize_tree,
        is_quantized_leaf,
        quantize_tree,
    )

    _, params = lm_and_params
    qtree = quantize_tree(params)
    deq = dequantize_tree(qtree, jnp.float32)
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_q = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(qtree)
    }
    checked = 0
    for path, leaf in flat_p:
        ps = "/".join(str(getattr(k, "key", k)) for k in path)
        if ps.endswith("kernel") and leaf.ndim == 2:
            q = flat_q[ps + "/q"]
            s = flat_q[ps + "/s"]
            assert q.dtype == jnp.int8
            step = np.asarray(s)[0]  # one scale per output channel
            err = np.abs(
                np.asarray(leaf, np.float32)
                - np.asarray(q, np.float32) * step
            )
            assert (err <= step / 2 + 1e-7).all()
            checked += 1
    assert checked >= 4  # qkv/proj per block + head
    # the dequantized tree mirrors the original structure exactly
    assert jax.tree_util.tree_structure(deq) == jax.tree_util.tree_structure(
        params
    )
    assert not any(
        is_quantized_leaf(l) for l in jax.tree_util.tree_leaves(deq)
    )


def test_quant_decode_greedy_drift_bound_and_compile_pin(
    lm_and_params, mode_prompts, plain_sched_results
):
    """Int8-decode oracle: greedy streams match the plain path within the
    stated drift bound (<= 10% of positions; exact on this f32 model),
    and quant adds ZERO XLA programs (same program set, int8 inputs)."""
    model, params = lm_and_params
    base, base_compiles = plain_sched_results
    sched = _paged_sched(model, params, quant=True)
    res = _sched_results(sched, mode_prompts)
    total = drift = 0
    for a, b in zip(res, base):
        assert a["gen_len"] == b["gen_len"]
        n = min(len(a["tokens"]), len(b["tokens"]))
        drift += int((np.asarray(a["tokens"][:n]) != np.asarray(
            b["tokens"][:n])).sum())
        total += n
    assert drift <= 0.1 * total, f"int8 drift {drift}/{total}"
    assert sched.compile_count() == base_compiles


@pytest.mark.slow
def test_lora_multiplexed_parity_with_merged_engine(
    lm_and_params, mode_prompts, plain_sched_results
):
    """Multi-LoRA oracle: a mixed batch (tenant-a, base, tenant-b) decodes
    token-identically to (1) a merged-weights (W + A B) single-adapter
    engine per tenant and (2) the plain engine for the base row — and the
    stacked factors add ZERO XLA programs."""
    from pytorch_distributed_training_tpu.serving.lora import LoraRegistry

    model, params = lm_and_params
    base, base_compiles = plain_sched_results
    reg = LoraRegistry(4, [{"name": "tenant-a", "seed": 0}, "tenant-b"])
    lmodel, lparams = reg.graft(model, params)
    # amplify the synthesized factors so the delta actually flips greedy
    # tokens on this tiny model — both the multiplexed tree and the merged
    # reference derive from the SAME amplified leaves, so parity still
    # compares a real (non-vacuous) delta
    lparams = jax.tree_util.tree_map_with_path(
        lambda p, leaf: leaf * 30.0
        if str(getattr(p[-1], "key", p[-1])).endswith(("_lora_a", "_lora_b"))
        else leaf,
        lparams,
    )
    sched = _paged_sched(lmodel, lparams, lora=reg)
    res = _sched_results(
        sched, mode_prompts,
        [{"adapter": "tenant-a"}, {}, {"adapter": "tenant-b"}],
    )
    # base row rides the SAME batch and still matches the plain engine
    np.testing.assert_array_equal(res[1]["tokens"], base[1]["tokens"])
    assert sched.compile_count() == base_compiles
    # per-tenant rows match their merged-weights single-adapter engine
    for name, row in (("tenant-a", 0), ("tenant-b", 2)):
        merged = _paged_sched(model, reg.merged_params(lparams, name))
        ref = _sched_results(merged, mode_prompts)
        assert res[row]["gen_len"] == ref[row]["gen_len"]
        np.testing.assert_array_equal(res[row]["tokens"], ref[row]["tokens"])
    # the synthesized delta is REAL: tenant rows diverge from the base
    assert any(
        not np.array_equal(res[r]["tokens"], base[r]["tokens"])
        for r in (0, 2)
    ), "LoRA factors produced a no-op delta; the oracle proved nothing"


def test_lora_registry_validation():
    from pytorch_distributed_training_tpu.serving.lora import LoraRegistry

    with pytest.raises(ValueError, match="rank"):
        LoraRegistry(0, ["a"])
    with pytest.raises(ValueError, match="at least one"):
        LoraRegistry(4, [])
    with pytest.raises(ValueError, match="duplicate"):
        LoraRegistry(4, ["a", {"name": "a"}])
    with pytest.raises(ValueError, match="unknown serving.lora.adapters"):
        LoraRegistry(4, [{"name": "a", "rank": 2}])
    reg = LoraRegistry(4, ["a", "b"])
    assert reg.id_of("b") == 1
    with pytest.raises(ValueError, match="registered"):
        reg.id_of("nope")


def test_prefix_cache_adapter_namespace_isolation():
    """Cross-tenant regression: identical prompts under different
    namespaces must NOT share cached K/V blocks (the adapter delta feeds
    qkv, so reuse would be silent corruption), while same-namespace
    lookups still hit."""
    from pytorch_distributed_training_tpu.serving.kv_pool import PagedKVPool

    pool = PagedKVPool(num_blocks=16, block_size=4)
    prompt = list(range(10, 19))  # 2 full blocks + 1 token
    adm = pool.admit(prompt, max_new=4, namespace=0)
    pool.register_prefix(prompt, adm, namespace=0)
    assert len(pool.lookup_prefix(prompt, namespace=0)) == 2
    assert pool.lookup_prefix(prompt, namespace=1) == []
    assert pool.lookup_prefix(prompt) == []  # base (None) is its own tenant
    # a second tenant registers the SAME prompt: distinct blocks
    adm2 = pool.admit(prompt, max_new=4, namespace=1)
    assert adm2.n_shared == 0
    pool.register_prefix(prompt, adm2, namespace=1)
    hit0 = pool.lookup_prefix(prompt, namespace=0)
    hit1 = pool.lookup_prefix(prompt, namespace=1)
    assert hit0 and hit1 and set(hit0).isdisjoint(hit1)
    pool.check_invariants()


def test_scheduler_prefix_cache_isolated_per_adapter(lm_and_params):
    """Scheduler-level isolation: the same prompt served under two
    adapters records prefix MISSES, under one adapter twice records a
    hit — the namespacing is wired through admit/register, not just the
    pool API."""
    from pytorch_distributed_training_tpu.serving.lora import LoraRegistry

    model, params = lm_and_params
    prompt = np.arange(2, 8).astype(np.int32)  # 6 tokens > block_size 4

    def run(adapters_pair):
        reg = LoraRegistry(4, ["tenant-a", "tenant-b"])
        lmodel, lparams = reg.graft(model, params)
        sched = _paged_sched(lmodel, lparams, lora=reg)
        f1 = sched.submit(prompt, adapter=adapters_pair[0])
        _run_scheduler_to_done(sched, [f1])
        f2 = sched.submit(prompt, adapter=adapters_pair[1])
        _run_scheduler_to_done(sched, [f2])
        return sched.metrics.snapshot().get("prefix_hit_blocks", 0)

    assert run(("tenant-a", "tenant-a")) == 1  # (6-1)//4 reusable blocks
    assert run(("tenant-a", "tenant-b")) == 0  # cross-tenant: no reuse


def test_speculative_self_draft_exact_and_compile_pin(
    lm_and_params, mode_prompts, plain_sched_results
):
    """Self-draft (draft == target) pin: committed streams are token-
    identical to plain decode AND the acceptance rate is exactly 1.0 —
    any fork/backfill/position bug shows up as a rejected proposal.
    Program budget: target prefill(+1/bucket) + verify + copy_rows +
    draft prefill(+1/bucket) + draft decode; the target decode_step is
    NEVER compiled, so with one seq bucket that's base + 3."""
    from pytorch_distributed_training_tpu.serving.speculative import (
        SpeculativeSpec,
    )

    model, params = lm_and_params
    base, base_compiles = plain_sched_results
    sched = _paged_sched(model, params, speculative=SpeculativeSpec(k=3))
    res = _sched_results(sched, mode_prompts)
    for a, b in zip(res, base):
        assert a["gen_len"] == b["gen_len"]
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
    snap = sched.metrics.snapshot()
    assert snap["spec_acceptance_rate"] == 1.0
    assert snap["spec_rounds"] >= 1
    # target decode_step never compiles in spec mode; verify + copy_rows +
    # draft prefill + draft decode are the only additions
    assert sched.compile_count() == base_compiles + 3


def test_speculative_distinct_draft_parity(
    lm_and_params, mode_prompts, plain_sched_results
):
    """The real configuration: an independent (smaller, random-init)
    draft model. Whatever the draft proposes, the committed stream is
    the TARGET's greedy stream, token for token; only the acceptance
    rate (reported in the snapshot) depends on the draft."""
    from pytorch_distributed_training_tpu.serving.speculative import (
        SpeculativeSpec,
    )

    model, params = lm_and_params
    base, _ = plain_sched_results
    draft = small_lm(depth=1)
    dparams = draft.init(
        jax.random.PRNGKey(9), jnp.zeros((1, 1), jnp.int32)
    )["params"]
    sched = _paged_sched(
        model, params,
        speculative=SpeculativeSpec(k=3, draft_model=draft,
                                    draft_params=dparams),
    )
    res = _sched_results(sched, mode_prompts)
    for a, b in zip(res, base):
        assert a["gen_len"] == b["gen_len"]
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert 0.0 <= sched.metrics.snapshot()["spec_acceptance_rate"] <= 1.0


def test_speculative_spec_and_accept_rules():
    from pytorch_distributed_training_tpu.serving.speculative import (
        SpeculativeSpec,
        greedy_accept,
        sampled_accept,
    )

    with pytest.raises(ValueError, match="k must be"):
        SpeculativeSpec(0)
    with pytest.raises(ValueError, match="together"):
        SpeculativeSpec(2, draft_model=object())
    # greedy: clean sweep emits k proposals + bonus
    assert greedy_accept([5, 7], [5, 7, 9]) == (2, [5, 7, 9])
    # first mismatch emits the target correction and stops
    assert greedy_accept([5, 7], [5, 8, 9]) == (1, [5, 8])
    assert greedy_accept([4], [6, 9]) == (0, [6])
    with pytest.raises(ValueError, match="k\\+1"):
        greedy_accept([1, 2], [1, 2])
    # sampled, p == q point masses: always accepts, bonus from p[k]
    V = 4
    p = np.zeros((3, V)); q = np.zeros((2, V))
    p[0, 1] = p[1, 2] = p[2, 3] = 1.0
    q[0, 1] = q[1, 2] = 1.0
    rng = np.random.default_rng(0)
    assert sampled_accept([1, 2], q, p, rng) == (2, [1, 2, 3])
    # draft proposes a token p gives zero mass: certain rejection, the
    # correction is drawn from the residual (= p itself here)
    q2 = np.zeros((2, V)); q2[0, 0] = q2[1, 0] = 1.0
    n, emitted = sampled_accept([0, 0], q2, p, rng)
    assert n == 0 and emitted == [1]


def test_metrics_per_adapter_namespacing():
    """Per-tenant instruments mirror the replica_id namespacing pattern:
    adapter-tagged retirements land in adapter_<name>_* alongside the
    flat ledger; untagged requests stay flat-only."""
    m = ServingMetrics()
    t0 = time.monotonic() - 0.01
    m.record_request(t0, gen_len=4, adapter="tenant-a")
    m.record_request(t0, gen_len=2, adapter="tenant-a")
    m.record_request(t0, gen_len=8, adapter="tenant-b")
    m.record_request(t0, gen_len=1)  # base: no adapter keys
    snap = m.snapshot()
    assert snap["requests"] == 4 and snap["gen_tokens"] == 15
    assert snap["adapter_tenant-a_requests"] == 2
    assert snap["adapter_tenant-a_gen_tokens"] == 6
    assert snap["adapter_tenant-b_requests"] == 1
    assert snap["adapter_tenant-b_gen_tokens"] == 8
    assert snap["adapter_tenant-a_latency_ms_p50"] > 0
    assert snap["adapter_tenant-b_latency_ms_p99"] > 0
    # spec acceptance ratio is derived from the counters when present
    m.incr("spec_proposed", 8); m.incr("spec_accepted", 6)
    assert m.snapshot()["spec_acceptance_rate"] == 0.75


def test_engine_mode_config_validation(lm_and_params):
    """serving.quant/lora/speculative parse with the copy-pop-raise
    idiom; LoRA and speculative refuse the batcher path."""
    from pytorch_distributed_training_tpu.serving.engine import (
        InferenceEngine,
    )

    model, params = lm_and_params

    def build(**over):
        from pytorch_distributed_training_tpu.parallel.mesh import make_mesh

        kw = dict(
            is_lm=True, batch_buckets=[2], seq_buckets=[8],
            max_batch_size=2, max_delay_ms=1.0, max_new_tokens=4,
        )
        kw.update(over)
        return InferenceEngine(model, params, {}, make_mesh(), **kw)

    with pytest.raises(ValueError, match="unknown serving.quant"):
        build(quant={"enabled": True, "bogus": 1})
    with pytest.raises(ValueError, match="unknown serving.speculative"):
        build(speculative={"enabled": True, "kk": 2})
    with pytest.raises(ValueError, match="scheduler.enabled"):
        build(lora={"enabled": True, "adapters": ["a"]})
    with pytest.raises(ValueError, match="scheduler.enabled"):
        build(speculative={"enabled": True})
    eng = build(quant={"enabled": False})  # disabled block parses clean
    assert eng.serving_modes == {
        "quant": False, "lora": False, "speculative": False,
    }
    eng.close()


# --------------------------------------------------------------------- #
# the decode ring (ContinuousScheduler's async_depth: 0 = a step is read in
# its own tick, "sync" below; an engine serves 1)


def _async_mixed_case(lm_and_params, temperature, depth):
    """Run the same mixed workload at depth 0 and at ``depth``: 6 prompts through 2
    slots (refill happens while the pipeline is full), mixed gen-lens via
    per-request caps and EOS retirement."""
    model, params = lm_and_params
    rng = np.random.default_rng(11)
    lens = [2, 6, 4, 3, 5, 2]
    prompts = [rng.integers(2, VOCAB, ln).astype(np.int32) for ln in lens]
    caps = [None, 2, None, 1, 3, None]
    R = jax.random.PRNGKey(7)
    kwargs = [
        {
            "max_new_tokens": caps[i],
            **({"rng": jax.random.fold_in(R, i)} if temperature else {}),
        }
        for i in range(len(prompts))
    ]
    out = []
    for async_depth in (0, depth):
        sched = _paged_sched(
            model, params, slots=2, temperature=temperature,
            async_depth=async_depth,
        )
        out.append(_sched_results(sched, prompts, kwargs))
        sched.close()
    return out


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("depth", [1, 2])
def test_scheduler_async_parity_bitwise(lm_and_params, temperature, depth):
    """A ring that holds steps is bitwise token-identical to depth 0,
    greedy AND sampled, under mixed gen-lens (per-request caps + EOS) and
    slot refill mid-pipeline."""
    sync, pipelined = _async_mixed_case(lm_and_params, temperature, depth)
    for i, (a, b) in enumerate(zip(sync, pipelined)):
        assert a["gen_len"] == b["gen_len"], f"request {i} gen_len diverged"
        np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_scheduler_async_compile_pin(lm_and_params, mode_prompts,
                                     plain_sched_results):
    """A ring that holds steps adds NO program over depth 0: one body, one
    ``decode_step``.  The pin also guards the sharding
    trap: the first dispatch's zero carry must hit the SAME cache entry
    as the steady-state carried token, or the program doubles."""
    model, params = lm_and_params
    _, base_compiles = plain_sched_results
    sched = _paged_sched(model, params, async_depth=2)
    _sched_results(sched, mode_prompts)
    assert sched.compile_count() == base_compiles
    # more decode traffic must not add programs (carry sharding stable)
    rng = np.random.default_rng(17)
    _sched_results(
        sched, [rng.integers(2, VOCAB, n).astype(np.int32) for n in (5, 3)]
    )
    assert sched.compile_count() == base_compiles
    sched.close()


def test_scheduler_async_validation(lm_and_params):
    """async_depth must be >= 0 and is mutually exclusive with
    speculative decoding (the accept/reject loop must observe every
    verify result on the host before the next round)."""
    from pytorch_distributed_training_tpu.serving.speculative import (
        SpeculativeSpec,
    )

    model, params = lm_and_params
    with pytest.raises(ValueError, match="async_depth"):
        _paged_sched(model, params, async_depth=-1)
    with pytest.raises(ValueError, match="mutually exclusive"):
        _paged_sched(
            model, params, async_depth=1, speculative=SpeculativeSpec(k=2),
        )


@pytest.mark.parametrize("draft, want", [(False, 1), (True, 0)],
                         ids=["unnamed", "draft_unnamed"])
def test_engine_serves_the_ring_unless_the_configuration_says_otherwise(
    draft, want
):
    """The ring's depth is no option: an engine serves depth 1, and depth 0
    beside a speculative draft (a round reads its own verify before the
    next is proposed, and the scheduler refuses a ring beside one)."""
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = _warm_engine_cfg()
    if draft:
        cfg["serving"]["speculative"] = {"enabled": True, "k": 2}
    with InferenceEngine.from_config(cfg) as engine:
        assert engine.scheduler._async_depth == want
        assert (engine.scheduler._spec is not None) == draft
        out = engine.submit(np.asarray([4, 8, 15], np.int32)).result(timeout=120)
        assert out["gen_len"] == 4
        share = engine.metrics.snapshot()["decode_overlap_share"]
    # three decode steps behind the prefill's token: the first finds the
    # ring empty, the others each find the one before them in it
    assert share == (pytest.approx(2 / 3) if want else 0.0)


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "draft"])
def test_engine_refuses_a_configuration_that_names_async_depth(draft):
    """``serving.scheduler.async_depth`` was a key until PR 46: a
    configuration that still names it is told so, as of any key the
    section does not have, and no scheduler thread is left behind."""
    import threading

    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = _warm_engine_cfg(async_depth=0 if draft else 1)
    if draft:
        cfg["serving"]["speculative"] = {"enabled": True, "k": 2}
    before = {t for t in threading.enumerate() if t.name == "serving-scheduler"}
    with pytest.raises(ValueError, match=r"unknown serving\.scheduler keys: "
                                         r"\['async_depth'\]"):
        InferenceEngine.from_config(cfg)
    left = {t for t in threading.enumerate()
            if t.name == "serving-scheduler" and t.is_alive()} - before
    assert not left


@pytest.mark.parametrize("depth", [0, 1, 2], ids=["sync", "ring_1", "ring_2"])
def test_decode_overlap_share_counts_the_steps_dispatched_over_a_full_ring(
    lm_and_params, depth
):
    """A scripted run, one request of 12 tokens, a tick at a time: of its
    11 decode steps the ring dispatches all but the first while an earlier
    step's tokens are still unread, depth 0 none; the ``decode_step``
    span carries the ring's length at the dispatch."""
    from pytorch_distributed_training_tpu.telemetry import (
        SpanRecorder,
        set_recorder,
    )

    model, params = lm_and_params
    rec = set_recorder(SpanRecorder(ring=4096))
    try:
        sched = _paged_sched(model, params, async_depth=depth, eos_id=None,
                             max_new_tokens=12, num_blocks=32)
        fut = sched.submit(np.asarray([3, 4, 5], np.int32))
        delivered = []
        while not fut.done():
            sched.tick()
            delivered.append(len(sched._slots[0].tokens) if sched._slots[0] else 12)
        sched.close()
    finally:
        set_recorder(None)
    assert fut.result()["gen_len"] == 12
    steps = 11
    snap = sched.metrics.snapshot()
    assert snap["decode_steps_dispatched"] == steps
    inflight = [s["inflight"] for s in rec.recent() if s["kind"] == "decode_step"]
    assert inflight == [min(k, depth) for k in range(steps)]
    if depth == 0:
        assert snap["decode_overlap_share"] == 0.0
        # a token a tick: the prefill's, then each step's own
        assert delivered == list(range(2, 13))
    else:
        assert snap["decode_overlap_share"] >= (steps - 1) / steps
        assert snap["decode_steps_overlapped"] == steps - 1
        # the ring delivers ``depth`` ticks behind its dispatch, and the
        # tick that finds nothing left to dispatch drains what is in it
        assert delivered[0] == 1 and delivered[-1] == 12
        assert len(delivered) == steps + 1


def test_decode_overlap_share_of_a_fleet_is_recomputed_from_the_counts():
    from pytorch_distributed_training_tpu.serving.metrics import (
        aggregate_snapshots,
    )

    a = {"decode_steps_dispatched": 10, "decode_steps_overlapped": 9,
         "decode_overlap_share": 0.9}
    b = {"decode_steps_dispatched": 30, "decode_steps_overlapped": 0,
         "decode_overlap_share": 0.0}
    out = aggregate_snapshots({"r0": a, "r1": b})
    assert out["decode_steps_dispatched"] == 40
    assert out["decode_overlap_share"] == pytest.approx(9 / 40)
    assert "decode_overlap_share" not in aggregate_snapshots({"r0": {"requests": 1}})


@pytest.mark.parametrize("depth", [0, 1], ids=["sync", "async"])
def test_scheduler_tick_metrics_surface(lm_and_params, mode_prompts, depth):
    """tick_host_ms / decode_dispatch_gap_ms land in the snapshot at
    both depths (gap samples need back-to-back decode ticks, which
    any multi-token request produces)."""
    model, params = lm_and_params
    sched = _paged_sched(model, params, async_depth=depth)
    _sched_results(sched, mode_prompts)
    snap = sched.metrics.snapshot()
    sched.close()
    for key in (
        "tick_host_ms_p50", "tick_host_ms_p99", "tick_host_ms_mean",
        "decode_dispatch_gap_ms_p50", "decode_dispatch_gap_ms_p99",
    ):
        assert key in snap, key
        assert snap[key] >= 0.0


def test_engine_warmup_compiles_everything_up_front(sched_engine):
    """warmup() compiles the full program set at restore time: traffic
    after it adds ZERO programs, and a second warmup is a no-op."""
    first = sched_engine.warmup()
    assert first["programs"] >= 0  # module-scoped engine may be part-warm
    warm = sched_engine.compile_count()
    assert sched_engine.warmup()["programs"] == 0  # idempotent
    rng = np.random.default_rng(5)
    futs = [
        sched_engine.submit(rng.integers(2, VOCAB, n).astype(np.int32))
        for n in (3, 9, 5)
    ]
    for f in futs:
        assert f.result(timeout=60)["gen_len"] >= 1
    assert sched_engine.compile_count() == warm


def test_fleet_add_replica_warms_and_records_readiness(lm_and_params):
    """ServingFleet.add_replica warms the new replica before it joins
    placement and publishes scale_up_ready_ms in its metrics snapshot."""
    from pytorch_distributed_training_tpu.serving.fleet import ServingFleet
    from pytorch_distributed_training_tpu.serving.router import FleetRouter
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )

    model, params = lm_and_params

    def factory(rid):
        return ContinuousScheduler(
            model, params, slots=2, block_size=4, num_blocks=16,
            batch_buckets=[2], seq_buckets=[8], max_new_tokens=4,
            temperature=0.0, start=False, replica_id=rid,
        )

    r0 = factory(0)
    router = FleetRouter([r0], base_rng=jax.random.PRNGKey(0),
                         heartbeat_timeout_s=None, start_monitor=False)
    fleet = ServingFleet([r0], router, replica_factory=factory)
    idx = fleet.add_replica()
    rep = fleet.replicas[idx]
    snap = rep.metrics.snapshot()
    assert snap["scale_up_ready_ms"] > 0.0
    router.shutdown()
    for r in fleet.replicas:
        r.close()


# --------------------------------------------------------------------- #
# tick phases and the request's life (PR 24): spans + ServingMetrics


def _decode_bodies():
    from pytorch_distributed_training_tpu.serving.speculative import (
        SpeculativeSpec,
    )

    # "sync" is the ring at depth 0: a step is read in the tick that sent it
    return {
        "sync": {},
        "async_ring": {"async_depth": 2},
        "speculative": {"speculative": SpeculativeSpec(k=2)},
    }


@pytest.mark.parametrize("body", ["sync", "async_ring", "speculative"])
def test_scheduler_tick_phase_spans_and_request_records(
    lm_and_params, mode_prompts, body
):
    """The ring at depth 0 and at depth 2 and a speculative round split a
    tick into the same kinds, each a child
    of ``tick``; every retired request leaves one ``request`` record with
    its id and its four stamps in order; the counts of the life histograms
    follow the requests and tokens served."""
    from pytorch_distributed_training_tpu.serving.metrics import TICK_PHASES
    from pytorch_distributed_training_tpu.telemetry import (
        SpanRecorder,
        set_recorder,
    )

    model, params = lm_and_params
    rec = set_recorder(SpanRecorder(ring=4096))
    try:
        sched = _paged_sched(model, params, **_decode_bodies()[body])
        results = _sched_results(sched, mode_prompts)
        snap = sched.metrics.snapshot()
        sched.close()
    finally:
        set_recorder(None)
    spans = rec.recent()
    ticks = [s for s in spans if s["kind"] == "tick"]
    phases = [s for s in spans if s["kind"] in TICK_PHASES]
    assert {s["kind"] for s in phases} == set(TICK_PHASES)
    assert all(s["parent"] == "tick" for s in phases)
    assert all(s["parent"] is None for s in ticks)
    assert {s["step"] for s in phases} <= {s["step"] for s in ticks}
    prefill = next(s for s in phases if s["kind"] == "prefill")
    assert prefill["rows"] == 3 and prefill["bucket"] == 8
    assert prefill["tokens"] == sum(len(p) for p in mode_prompts)
    assert prefill["reqs"] == [0, 1, 2]
    # telemetry/slo.py pairs recoveries with the productive kind
    assert any(s["kind"] == "decode_step" and "active" in s for s in phases)

    requests = [s for s in spans if s["kind"] == "request"]
    assert sorted(s["req"] for s in requests) == [0, 1, 2]
    for s, res in zip(sorted(requests, key=lambda s: s["req"]), results):
        assert s["parent"] is None and s["tokens"] == res["gen_len"]
        assert (s["t"] <= s["admitted_at"] <= s["first_token_at"]
                <= s["finished_at"])
        assert s["prefix_blocks"] == 0

    n_req = len(results)
    n_tok = sum(r["gen_len"] for r in results)
    assert snap["queue_wait_ms_count"] == n_req
    assert snap["ttft_ms_count"] == n_req
    assert snap.get("itl_ms_count", 0) == n_tok - n_req
    for key in ("queue_wait_ms_p95", "ttft_ms_p95", "tick_prep_ms_p50",
                "tick_readback_ms_p50", "tick_deliver_ms_p50",
                "tick_wall_ms_mean"):
        assert snap[key] >= 0.0, key
    assert snap["ttft_ms_p95"] >= snap["queue_wait_ms_p95"]
    # one observation a productive tick in every phase's histogram, and the
    # phases' means add up to no more than the tick's wall
    hists = sched.metrics._registry.snapshot()["histograms"]
    n_ticks = hists["tick_wall_ms"]["count"]
    # (close() drains with one more tick that finds nothing to do: it has
    # a span, and only ``admit`` under it, and no place in the histograms)
    productive = {s["step"] for s in phases if s["kind"] != "admit"}
    assert n_ticks == hists["tick_host_ms"]["count"] == len(productive)
    assert len(ticks) == n_ticks + 1
    assert all(hists[f"tick_{k}_ms"]["count"] == n_ticks for k in TICK_PHASES)
    assert hists["tick_prep_ms"]["count"] == n_ticks
    total = sum(snap[f"tick_{k}_ms_mean"] for k in TICK_PHASES)
    assert 0.5 * snap["tick_wall_ms_mean"] < total <= snap["tick_wall_ms_mean"]
    # the child span of ``readback`` is no phase: the phases, the tick's
    # account and the histograms are what they were without it
    assert TICK_PHASES == ("admit", "prefill", "decode_prep", "decode_step",
                           "readback", "deliver")
    waits = [s for s in spans if s["kind"] == "readback_wait"]
    reads = [s for s in spans if s["kind"] == "readback"]
    assert len(waits) == len(reads) > 0
    assert all(s["parent"] == "readback" for s in waits)
    assert set(sched._phase_ms) <= set(TICK_PHASES)
    assert "tick_readback_wait_ms" not in hists
    assert not any("readback_wait" in key for key in snap)


@pytest.mark.parametrize("depth", [0, 1], ids=["sync", "async_ring_1"])
def test_readback_wait_names_the_tick_whose_step_it_drains(
    lm_and_params, mode_prompts, depth
):
    """One ``readback_wait`` a drained step, inside that step's ``readback``
    and around its first read only; both carry ``for_step``, the tick of the
    ``decode_step`` whose output they drain: the same tick at depth 0, the
    tick before on a ring of depth 1 (the endgame drains its own)."""
    from pytorch_distributed_training_tpu.telemetry import (
        SpanRecorder,
        set_recorder,
    )

    model, params = lm_and_params
    rec = set_recorder(SpanRecorder(ring=4096))
    try:
        sched = _paged_sched(model, params, async_depth=depth)
        _sched_results(sched, mode_prompts)
        sched.close()
    finally:
        set_recorder(None)
    spans = rec.recent()
    steps = {s["step"] for s in spans if s["kind"] == "decode_step"}
    waits = [s for s in spans if s["kind"] == "readback_wait"]
    reads = [s for s in spans if s["kind"] == "readback"]
    # every dispatched step is drained once, by one read and one wait
    assert sorted(s["for_step"] for s in waits) == sorted(steps)
    assert [(s["step"], s["for_step"]) for s in reads] == [
        (s["step"], s["for_step"]) for s in waits]
    for wait, read in zip(waits, reads):
        assert wait["parent"] == "readback"
        assert read["t"] <= wait["t"]
        assert wait["t"] + wait["ms"] / 1e3 <= read["t"] + read["ms"] / 1e3 + 1e-6
    lag = [s["step"] - s["for_step"] for s in waits]
    if depth == 0:
        assert lag == [0] * len(waits)
    else:
        # steady state drains the tick before; the last tick dispatches
        # nothing and drains what is left, so it may drain its own neighbour
        assert set(lag) <= {0, 1} and lag.count(1) >= len(lag) - 1
        assert lag[0] == 1


def test_loop_idle_only_where_the_loop_sleeps(lm_and_params):
    """The running loop emits ``loop_idle`` while its queue is empty and
    none between two back-to-back productive ticks."""
    from pytorch_distributed_training_tpu.serving.scheduler import (
        ContinuousScheduler,
    )
    from pytorch_distributed_training_tpu.telemetry import (
        SpanRecorder,
        set_recorder,
    )

    model, params = lm_and_params
    rec = set_recorder(SpanRecorder(ring=4096))
    try:
        sched = ContinuousScheduler(
            model, params, slots=4, block_size=4, num_blocks=24,
            batch_buckets=[4], seq_buckets=[8], max_new_tokens=6,
            temperature=0.0, eos_id=None, start=True,
        )
        time.sleep(0.05)  # nothing queued: the loop sleeps
        fut = sched.submit(np.asarray([3, 4, 5], np.int32))
        assert fut.result(timeout=120)["gen_len"] == 6
        sched.close()
    finally:
        set_recorder(None)
    spans = rec.recent()
    idles = [s for s in spans if s["kind"] == "loop_idle"]
    assert idles and all(s["parent"] is None for s in idles)
    productive = {s["step"] for s in spans if s["kind"] == "decode_step"}
    ticks = sorted((s for s in spans
                    if s["kind"] == "tick" and s["step"] in productive),
                   key=lambda s: s["t"])
    assert len(ticks) >= 5
    # the first sleep ends when the request arrives, before its first tick
    assert min(s["t"] for s in idles) < ticks[0]["t"]
    for a, b in zip(ticks, ticks[1:]):
        assert b["step"] == a["step"] + 1
        gap = (a["t"] + a["ms"] / 1e3, b["t"])
        assert not any(gap[0] <= s["t"] < gap[1] for s in idles), (a, b)


# what a prefill call is taken to cost -> the (rows, batch bucket, sequence
# bucket) of the calls that prefill a tick's admissions of 12 and 2 tokens
# under batch_buckets [1, 4, 8] and seq_buckets [8, 16]
_PREFILL_SPLITS = {
    # no estimate (an engine that was not warmed): the one call, as before
    "no_estimate": (None, [(2, 4, 16)]),
    # a call is mostly its dispatch: the batch is kept
    "batch_nearly_free": ((10.0, 0.01), [(2, 4, 16)]),
    # time follows the padded tokens: a call a row, in the order of arrival
    "by_padded_tokens": ((0.0, 35.0), [(1, 1, 16), (1, 1, 8)]),
}


@pytest.mark.parametrize("cost", sorted(_PREFILL_SPLITS))
def test_prefill_span_carries_the_stalled_rows_and_the_padded_size(
    lm_and_params, cost,
):
    """Three rows decoding when two more are admitted in one tick: one
    ``prefill`` span a CALL, each with its own ``rows``, ``tokens``,
    ``bucket``, ``reqs`` and ``padded_tokens`` = its batch bucket x its
    sequence bucket; the 3 rows that sat through the tick's prefills are
    ``stalled`` on its first call only; the first tick's prefills met no
    decoding row.  However the calls are grouped, every request's greedy
    tokens are those of the one-call path."""
    from pytorch_distributed_training_tpu.telemetry import (
        SpanRecorder,
        set_recorder,
    )

    model, params = lm_and_params
    estimate, want = _PREFILL_SPLITS[cost]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, VOCAB, n).astype(np.int32)
               for n in (5, 3, 6, 12, 2)]
    buckets = dict(slots=8, num_blocks=48, batch_buckets=[1, 4, 8],
                   seq_buckets=[8, 16], eos_id=None)

    def serve(sched):
        futs = [sched.submit(p) for p in prompts[:3]]
        sched.tick()
        sched.tick()
        futs += [sched.submit(p) for p in prompts[3:]]
        _run_scheduler_to_done(sched, futs)
        sched.close()
        return [f.result()["tokens"].tolist() for f in futs]

    one_call = serve(_paged_sched(model, params, **buckets))
    rec = set_recorder(SpanRecorder(ring=1024))
    try:
        sched = _paged_sched(model, params, **buckets)
        if estimate is not None:
            sched.set_prefill_cost(*estimate)
        assert serve(sched) == one_call
    finally:
        set_recorder(None)
    spans = [s for s in rec.recent() if s["kind"] == "prefill"]
    first = [s for s in spans if s["step"] == spans[0]["step"]]
    second = [s for s in spans if s["step"] == spans[-1]["step"]]
    assert len(first) + len(second) == len(spans)
    assert all(s["stalled"] == 0 for s in first)
    assert sum(s["rows"] for s in first) == 3
    assert sum(s["tokens"] for s in first) == 14
    assert [(s["rows"], s["padded_tokens"] // s["bucket"], s["bucket"])
            for s in second] == want
    assert [s["stalled"] for s in second] == [3] + [0] * (len(want) - 1)
    assert sum(s["tokens"] for s in second) == 14
    assert sorted(r for s in second for r in s["reqs"]) == [3, 4]
    if len(want) == 2:
        assert [s["tokens"] for s in second] == [12, 2]
        assert [s["reqs"] for s in second] == [[3], [4]]
        assert first == sorted(first, key=lambda s: s["reqs"])
    snap = sched.metrics.snapshot()
    assert snap["prefill_calls"] == len(spans)
    assert snap["prefill_multi_row_ticks"] == 2
    assert snap.get("prefill_split_ticks", 0) == (2 if len(want) == 2 else 0)
    assert ("prefill_call_fixed_ms" in snap) == (estimate is not None)
    if estimate is not None:
        assert (snap["prefill_call_fixed_ms"],
                snap["prefill_call_ms_per_ktoken"]) == estimate
    # the rows decoding waited ONCE, through all of the tick's calls.  What
    # they waited is the phase account of that tick, whose clock starts
    # after a call's span has opened and stops before it closes: never more
    # than the spans' sum (the one observation is its own p50, exact; a
    # span's ``ms`` is rounded to the microsecond), and most of it
    assert snap["prefill_stall_ms_count"] == 1
    spanned = sum(s["ms"] for s in second)
    assert 0.5 * spanned <= snap["prefill_stall_ms_p50"]
    assert snap["prefill_stall_ms_p50"] <= spanned + 0.0005 * len(second)


def test_draft_prefill_is_a_span_of_its_own_beside_the_target_calls(
    lm_and_params, mode_prompts
):
    """Beside a speculative draft a tick's prefill time is the target's
    calls, each a span with its ``bucket`` and ``padded_tokens``, and the
    draft pool's one call: a ``prefill`` span that says ``draft``, stalls
    no row again and carries neither (the benchmark's readers of prefill
    tokens pass it by)."""
    from pytorch_distributed_training_tpu.telemetry import (
        SpanRecorder,
        set_recorder,
    )

    model, params = lm_and_params
    rec = set_recorder(SpanRecorder(ring=1024))
    try:
        sched = _paged_sched(model, params, batch_buckets=[1, 4],
                             **_decode_bodies()["speculative"])
        sched.set_prefill_cost(0.0, 35.0)
        _sched_results(sched, mode_prompts)
        sched.close()
    finally:
        set_recorder(None)
    spans = [s for s in rec.recent() if s["kind"] == "prefill"]
    assert len({s["step"] for s in spans}) == 1
    target, draft = spans[:-1], spans[-1]
    assert [s["rows"] for s in target] == [1, 1, 1]
    assert all(s["padded_tokens"] == s["bucket"] == 8 for s in target)
    assert (draft["draft"], draft["rows"], draft["stalled"]) == (True, 3, 0)
    assert "bucket" not in draft and "padded_tokens" not in draft


@pytest.mark.parametrize("body", ["sync", "async_ring", "speculative"])
def test_tick_host_ms_is_the_wall_less_the_readback_phase(
    lm_and_params, mode_prompts, body
):
    """One clock: a tick's blocked time is its ``readback`` phase (and a
    fresh prefill's own read, which no phase times alone), so in a tick
    without a prefill ``tick_host_ms`` is the wall less that phase."""
    model, params = lm_and_params
    sched = _paged_sched(model, params, **_decode_bodies()[body])
    seen = []
    record_tick = sched.metrics.record_tick
    record_phases = sched.metrics.record_tick_phases

    def spy_tick(host_ms):
        seen.append({"host_ms": host_ms})
        record_tick(host_ms)

    def spy_phases(wall_ms, phase_ms):
        seen[-1].update(wall_ms=wall_ms, phases=dict(phase_ms),
                        prefill_read_ms=sched._tick_block_s * 1e3)
        record_phases(wall_ms, phase_ms)

    sched.metrics.record_tick = spy_tick
    sched.metrics.record_tick_phases = spy_phases
    _sched_results(sched, mode_prompts)
    sched.close()
    plain = [t for t in seen if "prefill" not in t["phases"]]
    assert len(plain) < len(seen)
    # (a ring's first ticks dispatch and drain nothing: no readback, all host)
    # (and a speculative round commits up to three tokens: few ticks)
    assert any(t["phases"].get("readback", 0.0) > 0.0 for t in plain)
    for t in plain:
        assert t["prefill_read_ms"] == 0.0
        assert t["host_ms"] == pytest.approx(
            t["wall_ms"] - t["phases"].get("readback", 0.0), abs=1e-9)
    for t in seen:
        if "prefill" in t["phases"]:
            assert t["host_ms"] == pytest.approx(max(
                t["wall_ms"] - t["phases"].get("readback", 0.0)
                - t["prefill_read_ms"], 0.0), abs=1e-9)


def test_prefill_stall_counts_only_prefills_that_met_decoding_rows(
    lm_and_params,
):
    """Scripted: two requests prefill into an empty engine (nothing waits:
    no stall); a third is admitted while the long one is still decoding
    (one stall, as long as that tick's prefill phase)."""
    from pytorch_distributed_training_tpu.telemetry import (
        SpanRecorder,
        set_recorder,
    )

    model, params = lm_and_params
    rng = np.random.default_rng(5)
    p_long = rng.integers(2, VOCAB, 6).astype(np.int32)
    p_short = rng.integers(2, VOCAB, 3).astype(np.int32)
    p_queued = rng.integers(2, VOCAB, 4).astype(np.int32)
    rec = set_recorder(SpanRecorder(ring=1024))
    try:
        sched = _paged_sched(
            model, params, slots=2, batch_buckets=[2], eos_id=None,
        )
        futs = [
            sched.submit(p_long), sched.submit(p_short, max_new_tokens=2),
            sched.submit(p_queued),
        ]
        _run_scheduler_to_done(sched, futs)
        snap = sched.metrics.snapshot()
        sched.close()
    finally:
        set_recorder(None)
    prefills = [s for s in rec.recent() if s["kind"] == "prefill"]
    assert [s["reqs"] for s in prefills] == [[0, 1], [2]]
    assert snap["prefill_stall_ms_count"] == 1
    hist = sched.metrics._registry.snapshot()["histograms"]["prefill_stall_ms"]
    # the histogram holds the phase account of that tick: the span, seen
    # from inside it, is a few microseconds shorter
    assert hist["max"] == pytest.approx(prefills[1]["ms"], rel=0.2, abs=0.05)
    # the queued request waited for the short one's slot, the others did not
    waits = {s["req"]: s["admitted_at"] - s["t"]
             for s in rec.recent() if s["kind"] == "request"}
    assert waits[2] > max(waits[0], waits[1])
    assert snap["queue_wait_ms_count"] == 3


def test_fleet_aggregate_does_not_sum_percentiles():
    from pytorch_distributed_training_tpu.serving.metrics import (
        aggregate_snapshots,
    )

    a = {"requests": 2, "ttft_ms_p95": 10.0, "itl_ms_p50": 3.0,
         "queue_wait_ms_count": 2}
    b = {"requests": 3, "ttft_ms_p95": 30.0, "itl_ms_p50": 4.0,
         "queue_wait_ms_count": 3}
    out = aggregate_snapshots({"r0": a, "r1": b})
    assert out["requests"] == 5 and out["queue_wait_ms_count"] == 5
    assert out["ttft_ms_p95"] == 30.0  # the worst replica bounds the fleet
    assert "itl_ms_p50" not in out


def test_decode_step_carries_the_trace_scopes(lm_and_params):
    """Inside the decode program the paged attention and the sampling are
    named, so a trace tells the pool gather from the rest of the step."""
    import re

    model, params = lm_and_params
    sched = _paged_sched(model, params)
    lowered = sched._fns.decode_step.lower(
        *pool_program_args(sched, "decode_step.carried"))
    sched.close()
    names = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))
    assert [n for n in names if "/attn/paged_attention/" in n]
    assert "jit(decode_step)/sample" in names
    assert [n for n in names if "loss_head/head" in n]  # the logits matmul
    # the ONE decode program: every operation of the compiled
    # program is named under jit(decode_step), which is how a trace's
    # readers find a decode step's scopes (the ``op_name`` an ``.xplane.pb``
    # keeps; benchmark/decode_scopes.py::DECODE)
    ops = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
    ops = {n for n in ops if "jit(" in n}  # (a parameter is named by itself)
    assert len(ops) > 20
    assert not [n for n in ops if not n.startswith("jit(decode_step)/")]


# --------------------------------------------------------------------- #
# the pool is donated: each program consumes the pool it is given


def donated_leaves(program, *args) -> int:
    """How many arguments of the lowered program are marked for XLA to
    reuse: aliased to an output, or offered as a donor."""
    text = program.lower(*args).as_text()
    return text.count("tf.aliasing_output") + text.count("jax.buffer_donor")


def pool_program(sched, name):
    """``decode_step.carried`` is the one decode program under the ring's
    arguments."""
    return getattr(sched._fns, name.partition(".")[0])


def pool_program_args(sched, name):
    """Arguments, in order, with which the scheduler calls ``_fns.<name>``
    (no request active: every row rides at position -1, the step of
    ``step_inputs`` with no live row): ``decode_step`` with every row said
    to be fresh, as where the host holds each live row's token,
    ``decode_step.carried`` as a ring that holds steps does (no row fresh:
    each is fed the carried token)."""
    W, T = sched.slots_n, sched.table_blocks
    _, prev, pos, tables, keys, gen_idx, aids = sched._step_inputs(())
    tokens = np.zeros((W, 8), np.int32)
    positions = np.full((W, 8), -1, np.int32)
    oob = np.full((W,), sched._kv.num_blocks * sched._kv.block_size, np.int32)
    return {
        "prefill": (sched.params, sched._pool, tokens, positions, tables,
                    np.zeros((W,), np.int32), keys, gen_idx, aids),
        "decode_step": (sched.params, sched._pool, sched._zero_carry(),
                        np.ones((W,), bool), prev, pos, tables, keys, gen_idx,
                        aids),
        "decode_step.carried": (sched.params, sched._pool,
                                sched._zero_carry(), np.zeros((W,), bool),
                                prev, pos, tables, keys, gen_idx, aids),
        "verify": (sched.params, sched._pool, tokens, positions,
                   np.zeros((W, T), np.int32), aids),
        "copy_rows": (sched._pool, oob, oob),
    }[name]


POOL_PROGRAMS = ["prefill", "decode_step", "decode_step.carried", "verify",
                 "copy_rows"]


@pytest.mark.parametrize("name", POOL_PROGRAMS)
def test_every_pool_leaf_is_donated_to_the_program(lm_and_params, name):
    """ONE rule: a program that returns the pool consumes the pool it was
    given, so its scatter writes the caller's buffers and not a copy."""
    model, params = lm_and_params
    sched = _paged_sched(model, params)
    n_leaves = len(jax.tree_util.tree_leaves(sched._pool))
    assert n_leaves >= 4  # a K and a V leaf a layer, two layers
    args = pool_program_args(sched, name)
    assert donated_leaves(pool_program(sched, name), *args) == n_leaves
    # ... and running it leaves the caller without the pool it passed
    out = pool_program(sched, name)(*args)
    new_pool = out if name == "copy_rows" else out[1 if name == "verify" else 2]
    sched.close()
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(sched._pool))
    assert not any(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(new_pool))


def _warm_engine_cfg(**scheduler_more):
    return {
        "dataset": {"name": "synthetic_text", "n_classes": VOCAB},
        "model": {"name": "TransformerLM", "embed_dim": 32, "depth": 2,
                  "num_heads": 4, "max_len": 32},
        "serving": {
            "dtype": "float32", "max_batch_size": 4, "max_delay_ms": 2,
            "batch_buckets": [4], "seq_buckets": [8], "max_new_tokens": 4,
            "temperature": 0.0,
            "scheduler": dict({"enabled": True, "slots": 4, "block_size": 4,
                               "num_blocks": 32, "prefix_cache": False},
                              **scheduler_more),
        },
    }


@pytest.mark.parametrize("mode", ["async_ring", "speculative"])
def test_warmup_hands_the_scheduler_its_pool_back(mode):
    """The warm-up runs the donating programs on the scheduler's own pool:
    what it leaves in ``_pool`` (and ``_draft_pool``) is alive, holds what
    it held, and the gauge says how much of it the programs update in
    place: all of it."""
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = _warm_engine_cfg()
    if mode == "speculative":
        cfg["serving"]["speculative"] = {"enabled": True, "k": 2}
    prompt = np.asarray([4, 8, 15, 16, 23], np.int32)
    with InferenceEngine.from_config(cfg) as engine:
        sched = engine.scheduler
        before = engine.submit(prompt).result(timeout=120)["tokens"]
        assert "pool_aliased_bytes" not in engine.metrics.snapshot()
        engine.warmup()
        pools = [sched._pool] + (
            [sched._draft_pool] if mode == "speculative" else [])
        for pool in pools:
            leaves = jax.tree_util.tree_leaves(pool)
            assert leaves and not any(leaf.is_deleted() for leaf in leaves)
        assert engine.warmup()["programs"] == 0  # idempotent, pool intact
        warm = engine.compile_count()
        after = engine.submit(prompt).result(timeout=120)["tokens"]
        np.testing.assert_array_equal(after, before)
        assert engine.compile_count() == warm
        pool_bytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(sched._pool))
        assert engine.metrics.snapshot()["pool_aliased_bytes"] == pool_bytes > 0


def test_warmup_is_refused_beside_queued_work(lm_and_params):
    """A warm-up consumes the pool from the caller's thread: with a request
    queued or in a slot a tick could meet the donated pool, so it is
    refused, and allowed again once the scheduler is empty."""
    model, params = lm_and_params
    sched = _paged_sched(model, params)
    sched.require_idle()
    fut = sched.submit(np.asarray([3, 4, 5], np.int32))
    with pytest.raises(RuntimeError, match="warm-up consumes the scheduler's pool"):
        sched.require_idle()
    sched.tick()  # admitted: in a slot, no longer queued
    with pytest.raises(RuntimeError, match="before traffic"):
        sched.require_idle()
    _run_scheduler_to_done(sched, [fut])
    sched.require_idle()
    sched.close()


# --------------------------------------------------------------------- #
# a request's sampling key is a host row: one program a decode tick


class _Spy:
    """Stands in for one program of ``_PagedFns``: files the arguments of
    every call, then calls it (``_cache_size`` and ``lower`` pass through)."""

    def __init__(self, fn, name, calls, rewrite=None):
        self._fn, self._name, self._calls = fn, name, calls
        self._rewrite = rewrite

    def __call__(self, *args):
        self._calls.append((self._name, args))
        if self._rewrite is not None:
            args = self._rewrite(self._name, args)
        return self._fn(*args)

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


# per program: where ``row_keys`` sits, and the first argument the TICK
# builds (before it: params, the pool and, for the decode step, the carried
# token, which is a step's output, or the zeros the ring starts from, and
# stays on the device)
_KEYS_AT = {"prefill": 6, "decode_step": 7}
_HOST_FROM = {"prefill": 2, "decode_step": 3, "verify": 2, "copy_rows": 1}


def _spy_on(sched, rewrite=None):
    calls = []
    for fns in (sched._fns, sched._draft_fns):
        if fns is not None:
            for name in _HOST_FROM:
                setattr(fns, name, _Spy(getattr(fns, name), name, calls, rewrite))
    return calls


def _assert_host_built(calls):
    """Every argument a tick built is ONE ``numpy`` array, the key rows
    ``uint32 [batch rows, 2]``: nothing was built on the device a slot."""
    assert calls
    for name, args in calls:
        for a in args[_HOST_FROM[name]:]:
            assert type(a) is np.ndarray, (name, type(a))
        if name in _KEYS_AT:
            keys = args[_KEYS_AT[name]]
            assert keys.dtype == np.uint32, (name, keys.dtype)
            assert keys.shape == (args[_KEYS_AT[name] + 1].shape[0], 2), name


@pytest.mark.parametrize("body", ["sync", "async_ring", "speculative"])
def test_a_tick_hands_the_programs_host_arrays_only(
    lm_and_params, mode_prompts, body
):
    model, params = lm_and_params
    sched = _paged_sched(model, params, seed=5, **_decode_bodies()[body])
    calls = _spy_on(sched)
    results = _sched_results(sched, mode_prompts)
    assert all(r["gen_len"] >= 1 for r in results)
    _assert_host_built(calls)
    step = "verify" if body == "speculative" else "decode_step"
    assert {"prefill", step} <= {name for name, _ in calls}
    # where the host holds every live row's token (depth 0, a draft's
    # steps) the mask names exactly the live rows; a ring that holds steps
    # names only the rows it has nothing in flight for
    steps = [(args[3], args[5] >= 0) for name, args in calls
             if name == "decode_step"]
    assert all(m.dtype == bool and not (m & ~live).any() for m, live in steps)
    if body == "async_ring":
        assert steps[0][0].any()
        assert not all((m == live).all() for m, live in steps)
    else:
        assert all((m == live).all() and live.any() for m, live in steps)
    # the prefill's rows: request i's key is fold_in(PRNGKey(seed), i),
    # made once at submit; a padding row rides the pad key
    keys = next(a for n, a in calls if n == "prefill")[_KEYS_AT["prefill"]]
    base = jax.random.PRNGKey(5)
    for i in range(3):
        np.testing.assert_array_equal(
            keys[i], np.asarray(jax.random.fold_in(base, i)))
    np.testing.assert_array_equal(keys[3], np.asarray(jax.random.PRNGKey(0)))
    sched.close()


def test_probe_and_replay_hand_the_programs_host_arrays_only(
    lm_and_params, mode_prompts, plain_sched_results
):
    """The supervisor's two paths beside the tick: the bisect's probe and
    the restart's chunked replay of delivered tokens."""
    model, params = lm_and_params
    sched = _paged_sched(model, params)
    calls = _spy_on(sched)
    delivered = plain_sched_results[0][1]["tokens"]
    assert len(delivered) >= 3
    # (greedy: any key replays the stream; a replay must name one)
    fut = sched.submit(mode_prompts[1], replay_tokens=list(delivered[:2]),
                       rng=jax.random.PRNGKey(3))
    # admitted through _replay (a prefill, then the second delivered token
    # by a decode chunk), and the tick's own decode step behind it
    sched.tick()
    assert [n for n, _ in calls] == ["prefill", "decode_step", "decode_step"]
    sched._decode_probe([r for r in sched._slots if r is not None])
    assert len(calls) == 4
    _run_scheduler_to_done(sched, [fut])
    _assert_host_built(calls)
    np.testing.assert_array_equal(fut.result()["tokens"], delivered)
    sched.close()


@pytest.mark.parametrize("mode", ["async_ring"])
def test_warmup_hands_the_programs_the_ticks_kinds_of_argument(mode):
    """A warm-up BEFORE any traffic, then prefills and decode ticks: the
    program count stays where the warm-up left it, so each program's jit
    cache holds the one entry the ticks hit and nothing compiles (or is
    laid out anew) while requests are served."""
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = _warm_engine_cfg()
    rng = np.random.default_rng(5)
    with InferenceEngine.from_config(cfg) as engine:
        assert engine.scheduler._async_depth == 1
        # one prefill bucket and ONE decode step, whoever calls it
        n = 2
        assert engine.scheduler._fns.decode_step._cache_size() == 0
        assert engine.warmup()["programs"] == n
        assert engine.scheduler._fns.decode_step._cache_size() == 1
        assert engine.compile_count() == n
        calls = _spy_on(engine.scheduler)
        futs = [
            engine.submit(rng.integers(2, VOCAB, ln).astype(np.int32))
            for ln in (3, 8, 5, 2, 7)
        ]
        assert sum(f.result(timeout=120)["gen_len"] for f in futs) > 5
        names = [name for name, _ in calls]
        assert names.count("prefill") >= 1 and names.count("decode_step") >= 3
        assert engine.compile_count() == n
        assert engine.scheduler._fns.decode_step._cache_size() == 1


@pytest.mark.parametrize(
    "batch_buckets, seq_buckets, timed",
    [
        # the smallest program and the longest of its row count
        # (the engine rounds a batch bucket up to the devices it runs on)
        ([8, 16], [8, 16], [(8, 8), (8, 16)]),
        # one sequence bucket: the next batch bucket instead
        ([8, 16], [8], [(8, 8), (16, 8)]),
        # a grid of one program has nothing to choose between
        ([8], [8], []),
    ],
    ids=["along_seq", "along_batch", "one_program"],
)
def test_warmup_times_two_prefill_programs_and_tells_the_scheduler(
    batch_buckets, seq_buckets, timed
):
    """The estimate a tick's admissions are grouped by is the engine's own
    observation: the warm-up runs two of its compiled prefill programs a
    second time, timed, on a full call's inputs (ids over the vocabulary at
    live positions), and hands the scheduler the line through them; an
    engine that was not warmed has none.  The timed calls compile nothing,
    write nothing into the pool and hand it back."""
    from pytorch_distributed_training_tpu.serving.engine import InferenceEngine

    cfg = _warm_engine_cfg()
    cfg["serving"].update(batch_buckets=batch_buckets, seq_buckets=seq_buckets)
    prompts = [np.asarray([4, 8, 15, 16, 23], np.int32),
               np.asarray([42, 7], np.int32)]
    with InferenceEngine.from_config(cfg) as engine:
        sched = engine.scheduler
        assert sched._prefill_cost is None
        assert "prefill_call_fixed_ms" not in engine.metrics.snapshot()
        before = [f.result(timeout=120)["tokens"]
                  for f in [engine.submit(p) for p in prompts]]
        calls = _spy_on(sched)
        held = jax.device_get(jax.tree_util.tree_leaves(sched._pool))
        assert any(leaf.any() for leaf in held)  # the requests' rows
        engine.warmup()
        ran = [args[2].shape for name, args in calls if name == "prefill"]
        grid = [(b, s) for b in batch_buckets for s in seq_buckets]
        assert ran == grid + timed
        for _, args in [c for c in calls if c[0] == "prefill"][len(grid):]:
            # a full call's work: ids over the vocabulary at live positions
            # (an expert layer skips padding); no block of the pool is named
            assert 0 <= args[2].min() and args[2].max() < VOCAB
            assert len(np.unique(args[2])) > 8
            assert (args[3] == np.arange(args[3].shape[1])).all()
            assert (args[4] == sched._kv.num_blocks).all()
            assert (args[5] == args[3].shape[1] - 1).all()
        for old, new in zip(held, jax.tree_util.tree_leaves(sched._pool)):
            np.testing.assert_array_equal(old, np.asarray(new))
        snap = engine.metrics.snapshot()
        if timed:
            assert tuple(sched._prefill_cost) == (
                snap["prefill_call_fixed_ms"], snap["prefill_call_ms_per_ktoken"])
            assert min(sched._prefill_cost) >= 0.0
        else:
            assert sched._prefill_cost is None
            assert "prefill_call_fixed_ms" not in snap
        warm = engine.compile_count()
        leaves = jax.tree_util.tree_leaves(sched._pool)
        assert leaves and not any(leaf.is_deleted() for leaf in leaves)
        after = [f.result(timeout=120)["tokens"]
                 for f in [engine.submit(p) for p in prompts]]
        for a, b in zip(after, before):
            np.testing.assert_array_equal(a, b)
        assert engine.compile_count() == warm


def test_sampled_streams_are_those_of_stacked_device_keys(
    lm_and_params, mode_prompts
):
    """A host row a request gives the program the key data that a
    ``jnp.stack`` of device-resident keys gave it: the sampled streams are
    bit for bit the same, for the scheduler's own key (``fold_in`` of its
    seed and the request's number) and for a caller's, typed, legacy or
    already on the host."""
    model, params = lm_and_params
    typed = jax.random.key(9)
    legacy = jax.random.fold_in(jax.random.PRNGKey(7), 1)
    on_host = np.asarray(jax.random.PRNGKey(21))
    prompts = [*mode_prompts, mode_prompts[0]]

    sched = _paged_sched(model, params, temperature=0.8, seed=11)
    got = _sched_results(
        sched, prompts,
        [{}, {"rng": legacy}, {"rng": typed}, {"rng": on_host}],
    )
    sched.close()

    def stacked(name, args):
        # what the programs were handed before: one device key a row,
        # expanded and concatenated on the device
        if name not in _KEYS_AT:
            return args
        at = _KEYS_AT[name]
        keys = jnp.stack([jnp.asarray(row) for row in args[at]])
        return (*args[:at], keys, *args[at + 1:])

    ref = _paged_sched(model, params, temperature=0.8, seed=3)
    _spy_on(ref, rewrite=stacked)
    want = _sched_results(
        ref, prompts,
        [{"rng": jax.random.fold_in(jax.random.PRNGKey(11), 0)},
         {"rng": legacy}, {"rng": jax.random.key_data(typed)},
         {"rng": jax.random.PRNGKey(21)}],
    )
    ref.close()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # the same prompt under two keys draws two streams: the keys count
    assert not np.array_equal(got[0]["tokens"], got[3]["tokens"])


def test_submit_refuses_what_is_not_one_key(lm_and_params):
    model, params = lm_and_params
    sched = _paged_sched(model, params)
    with pytest.raises(ValueError, match="ONE sampling key"):
        sched.submit(np.asarray([3, 4], np.int32),
                     rng=jax.random.split(jax.random.PRNGKey(0), 2))
    sched.close()
