"""Depth 0 against depth 1 of the scheduler's one decode body, over five
served families at their test widths: the same arrivals at both depths leave
the same tokens, the same ``finite`` flags a step and, bit for bit, the same
pool (token rows and, where a family carries them, the per-slot state or
the window layers' rings, which every row here wraps).

Both run the ONE ``decode_step`` program (serving/decode.py) from the one
body (``ContinuousScheduler._ring_step``); what differs is who knows a row's
last token: at depth 0 the host (each step is read in its own tick, so the
mask names every live row), at depth 1 the device (the carried output, with
the rows just prefilled spliced in).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nemotron_toy
import test_deepseek_v2 as deepseek_toy
import test_laguna as laguna_toy
import test_solar_open2 as solar_toy
from pytorch_distributed_training_tpu.models import get_model
from pytorch_distributed_training_tpu.serving.scheduler import ContinuousScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LM_VOCAB = 64


def _reference(name):
    path = os.path.join(ROOT, "benchmark", "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ring_reference_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _from_reference(ref, toy, family):
    """(model, the program's tree in float32, vocabulary) of a family whose
    weights the benchmark's reference makes."""
    host = jax.device_get(ref.make_params(7, ref.sizes_of(toy.CONFIG)))
    tree = jax.tree.map(
        lambda a: jnp.asarray(a).astype(jnp.float32), ref.to_checkpoint_tree(host))
    model = get_model(family, num_classes=toy.VOCAB, dtype=jnp.float32,
                      **toy.MODEL_KEYS)
    return model, tree, toy.VOCAB


def _transformer_lm():
    model = get_model("TransformerLM", num_classes=LM_VOCAB, embed_dim=32,
                      depth=2, num_heads=4, max_len=64)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, LM_VOCAB


FAMILIES = {
    "transformer_lm": _transformer_lm,
    "deepseek_v2": lambda: _from_reference(
        _reference("deepseek_v2"), deepseek_toy, "DeepseekV2"),
    "solar_open2": lambda: _from_reference(
        _reference("solar_open2"), solar_toy, "SolarOpen2"),
    "nemotron_h": lambda: _from_reference(
        nemotron_toy.load_reference(), nemotron_toy, "NemotronH"),
    "laguna": lambda: _from_reference(_reference("laguna"), laguna_toy, "Laguna"),
}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return FAMILIES[request.param]()


def _run(model, params, vocab, depth, temperature):
    """Two requests from the first tick, one of them short (it ends at its
    cap of 2 while the other decodes), a third admitted at the fourth tick
    while the first is mid-stream; every row ends at its cap (``eos_id``
    None).  Returns the tokens a request, what every decode step was handed
    and gave back, the pool as the last step left it, and the snapshot."""
    rng = np.random.default_rng(23)
    prompts = [rng.integers(2, vocab, n).astype(np.int32) for n in (5, 11, 7)]
    caps = [9, 2, 6]
    sched = ContinuousScheduler(
        model, params, slots=3, block_size=4, num_blocks=96, prefix_cache=False,
        batch_buckets=[1, 4], seq_buckets=[16], max_new_tokens=9,
        temperature=temperature, eos_id=None, seed=5, async_depth=depth,
        start=False,
    )
    steps = []
    real = sched._fns.decode_step

    def spied(*args):
        out = real(*args)
        steps.append((np.array(args[5]), np.asarray(out[0]), np.asarray(out[1])))
        return out

    spied._cache_size = real._cache_size
    sched._fns.decode_step = spied
    futures = [sched.submit(p, max_new_tokens=c) for p, c in zip(prompts[:2], caps)]
    for tick in range(64):
        if tick == 3:
            futures.append(sched.submit(prompts[2], max_new_tokens=caps[2]))
        sched.tick()
        if len(futures) == 3 and all(f.done() for f in futures):
            break
    tokens = [f.result(timeout=0)["tokens"] for f in futures]
    assert [len(t) for t in tokens] == caps
    pool = jax.tree.map(np.asarray, sched._pool)
    snapshot = sched.metrics.snapshot()
    programs = real._cache_size()
    sched.close()
    return tokens, steps, pool, snapshot, programs


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_the_ring_serves_what_the_sync_body_serves(family, temperature):
    # ("sync" = the body at depth 0: a step is read in the tick that sent it)
    model, params, vocab = family
    sync = _run(model, params, vocab, 0, temperature)
    ring = _run(model, params, vocab, 1, temperature)
    for a, b in zip(sync[0], ring[0]):
        np.testing.assert_array_equal(a, b)
    # step for step the same rows at the same positions, the same sampled
    # tokens and the same output guard's flags on the live rows
    assert len(sync[1]) == len(ring[1]) == 8  # the longest request's steps
    for (pos_a, tok_a, fin_a), (pos_b, tok_b, fin_b) in zip(sync[1], ring[1]):
        np.testing.assert_array_equal(pos_a, pos_b)
        live = pos_a >= 0
        assert live.any()
        np.testing.assert_array_equal(tok_a[live], tok_b[live])
        np.testing.assert_array_equal(fin_a[live], fin_b[live])
        assert fin_a[live].all()
    # ... and the pool, token rows and state leaves, is the same to the bit
    flat_a = jax.tree_util.tree_flatten_with_path(sync[2])[0]
    flat_b = jax.tree_util.tree_flatten_with_path(ring[2])[0]
    assert len(flat_a) == len(flat_b) > 0
    for (path, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
    # depth 1 did overlap, depth 0 did not, one decode program each
    assert sync[3]["decode_overlap_share"] == 0.0
    assert ring[3]["decode_overlap_share"] == 7 / 8  # all but the first
    assert sync[4] == ring[4] == 1
