"""Serving cells: ``InferenceEngine.from_config()`` -> ``ContinuousScheduler``
-> paged KV pool, fed by the benchmark's open-loop client and measured from
the client's side.

Weights are the benchmark's, made from the seed and handed over as a
checkpoint in the program's documented format (``serving.checkpoint``).  Once
the window has closed and the engine is freed, the plain reference runs once
over prompt + served tokens of a seeded sample of the finished requests (the
longest among them) and reads, at every generated position, how far the
served token's logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time

import numpy as np

from .. import loadgen
from .. import trace as trace_math
from ..common import Check, Run, load_module, merge, runtime_peak_bytes

COUNTERS = ("requests_poisoned", "engine_restarts", "failed_inflight", "timeouts")


def write_checkpoint(tree: dict, directory: str) -> None:
    import orbax.checkpoint as ocp

    manager = ocp.CheckpointManager(directory)
    try:
        manager.save(0, args=ocp.args.StandardSave({"params": tree}))
        manager.wait_until_finished()
    finally:
        manager.close()


def sample_requests(records, n: int, seed: int):
    """``n`` finished requests drawn from the seed, the longest in them."""
    done = [r for r in records if not loadgen.failed(r)]
    if not done:
        return []
    longest = max(done, key=lambda r: r.arrival.prompt_len + r.arrival.gen_len)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(int(seed)).permutation(len(rest))
    return [longest] + [rest[i] for i in order[: max(0, n - 1)]]


def logit_gaps(ref, params, heads, prompts, sample, chosen=None):
    """At every generated position of the sampled requests: how far below
    the reference's best logit lies the logit of the token that was served
    (``chosen=None``) or that the arithmetic ``chosen`` would have served.
    Returns the gaps, one array a request."""
    out = []
    for rec in sample:
        prompt = prompts[rec.arrival.index]
        seq = np.concatenate([prompt, rec.tokens[:-1]]).astype(np.int32)
        rows = np.asarray(ref.logits_for(params, seq, heads, "f32"))[len(prompt) - 1:]
        if chosen is None:
            tokens = np.asarray(rec.tokens)
        else:
            low = np.asarray(ref.logits_for(params, seq, heads, chosen))
            tokens = low[len(prompt) - 1:].argmax(axis=-1)
        out.append(rows.max(axis=-1) - rows[np.arange(len(rows)), tokens])
    return out


def gap_numbers(gaps):
    """The two numbers compared: the widest gap (one wrong token shows
    here) and the mean gap (steady from seed to seed; a lower precision
    flips more near-ties and shows here)."""
    flat = np.concatenate(gaps) if gaps else np.array([np.nan])
    return {
        "served_token_logit_gap_widest": float(flat.max()),
        "served_token_logit_gap_mean": float(flat.mean()),
    }, int(flat.size), float((flat > 0).mean())


def run(cell: dict, args, out_dir: str, ledger, t_start: float):
    import jax

    config, traffic = cell["config_file"], cell["traffic_file"]
    ref = load_module("reference", config["reference"])
    sizes = ref.sizes_of(config)
    seed = int(args.seed)

    params0 = jax.device_get(ref.make_params(seed, sizes))
    ckpt = os.path.join(out_dir, "checkpoint")
    write_checkpoint(ref.to_checkpoint_tree(params0), ckpt)
    cfg = merge(config["serve"], {"serving": traffic.get("serving", {})})
    cfg["serving"]["checkpoint"] = ckpt
    cfg["serving"]["seed"] = seed % 2147483647

    trace = loadgen.make_trace(traffic, float(args.seconds))
    prompts = loadgen.make_prompts(trace, traffic, int(config["vocab_size"]), seed)

    from pytorch_distributed_training_tpu.serving import InferenceEngine

    run_ = Run(cell=cell, kind="serve", seconds=args.seconds,
               chips=cell["chips"], out_dir=out_dir)
    with InferenceEngine.from_config(cfg) as engine:
        if engine.scheduler is None:
            raise RuntimeError("the configuration did not enable the scheduler")
        warm = engine.warmup()
        client = loadgen.OpenLoopClient(engine.submit, trace, prompts)
        lead = float(traffic.get("lead_in_s", 0.0))
        t0 = time.monotonic() + lead + 0.2
        client.start(t0)
        tracer = None
        if args.trace:
            tracer = threading.Thread(
                target=_trace_window, name="bench-tracer",
                # the LAST seconds of the window: stopping a trace holds the
                # interpreter for seconds, which must fall after the window
                args=(t0 + float(args.seconds) - float(traffic["trace_seconds"]),
                      float(traffic["trace_seconds"]),
                      os.path.join(out_dir, "trace")),
            )
            tracer.start()
        time.sleep(max(0.0, t0 - time.monotonic()))
        run_.compile_before = ledger.since()
        mark = ledger.mark()
        run_.setup_s = t0 - t_start
        time.sleep(max(0.0, t0 + float(args.seconds) - time.monotonic()))
        client.finish(t0 + float(args.seconds) + float(traffic["drain_s"]))
        run_.compile_inside = ledger.since(mark)
        if tracer:
            tracer.join()
        snapshot = engine.metrics.snapshot()
        programs = engine.compile_count()
        peak = runtime_peak_bytes()
    records = client.records
    run_.serve = {"records": records, "t0": t0, "snapshot": snapshot}
    counted = loadgen.counted(records)
    n_failed = sum(loadgen.failed(r) for r in counted)
    run_.notes.update(
        memory_peak_bytes=peak, engine_programs=programs,
        warmup_ms=warm["warmup_ms"], requests=len(counted),
        lead_in_requests=len(records) - len(counted),
        snapshot={k: v for k, v in snapshot.items() if not isinstance(v, dict)},
        errors=sorted({r.error for r in records if r.error})[:5],
        ttft_samples=len(counted),
        gap_samples=len(loadgen.gaps_ms(records)),
        # every stamp in the window, the lead-in's too: the manifest's metric
        # until PR 36, kept so that a run can be laid beside those records
        tokens_stamped_per_s=loadgen.tokens_per_s(records, t0, float(args.seconds)),
    )

    # the engine is closed and freed: now the reference
    del engine, client
    gc.collect()
    t_ref = time.monotonic()
    limits = config["limits"]["serve"]
    check = Check()
    sample = sample_requests(counted, int(traffic["sample_requests"]), seed)
    params = jax.tree.map(jax.numpy.asarray, params0)
    numbers, positions, flipped = gap_numbers(
        logit_gaps(ref, params, sizes["H"], prompts, sample))
    for name, value in numbers.items():
        check.add(
            name, value, limits[name],
            f"over {positions} generated positions of {len(sample)} requests; "
            f"{flipped:.4f} of them not the reference's first choice",
        )
    if args.control and sample:
        # limit-setting only: at every position of the same prompts and
        # tokens, the gap of the token the lower precision puts first
        lower, _, flipped = gap_numbers(logit_gaps(
            ref, params, sizes["H"], prompts, sample, config["control_mode"]))
        for name, value in lower.items():
            print("control " + json.dumps({
                "compared": name, "value": value,
                "note": f"token the {config['control_mode']} reference puts "
                        f"first; {flipped:.4f} of positions differ",
            }), flush=True)
    vocab = int(config["vocab_size"])
    check.require(
        "tokens_in_vocabulary",
        all(r.tokens.min() >= 0 and r.tokens.max() < vocab
            for r in counted if r.tokens is not None and len(r.tokens)),
    )
    check.require("no_failed_request", n_failed == 0,
                  f"{n_failed} of {len(counted)} failed: {run_.notes['errors']}")
    check.require(
        "no_compile_inside_window", run_.compile_inside["programs"] == 0,
        f"programs built or read back inside the window: {run_.compile_inside}",
    )
    bad = {c: snapshot.get(c) for c in COUNTERS if snapshot.get(c)}
    check.require("serving_counters_zero", not bad, f"{bad}")
    run_.notes["reference_s"] = time.monotonic() - t_ref

    if args.trace:
        path = trace_math.find_xplane(os.path.join(out_dir, "trace"))
        if path:
            run_.trace = trace_math.reduce(trace_math.load(path))
            run_.notes["xplane"] = path
    return run_, check, len(counted), n_failed


def _trace_window(start: float, seconds: float, directory: str) -> None:
    """A few seconds of the window, traced in the serving process itself."""
    import jax

    # without the python tracer: with it the scheduler's tick read 8.6-11.6 ms
    # for 5.5 and a cell at 0.8 x its knee fell over the knee (PR 23); the
    # runtime's own host events still name the idle gaps
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    time.sleep(max(0.0, start - time.monotonic()))
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
