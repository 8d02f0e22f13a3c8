"""The one sweep that fixes a serving cell's offered rate: the same mix at a
few fixed rates, one engine, one process, and for each rate what the client
saw.  The knee is the highest rate at which the backlog does not grow: time
to first token in the last quarter of the window no worse than in the first,
and the queue drains as the window closes.  Run on the chip by hand:

    python benchmark/sweep.py --workload lm271m.serve.steady --seed 1 \\
        --seconds 20 --rates 4 6 8 10 12

The cell's traffic file then carries 0.8 x the knee as ``rate_rps``; the
benchmark itself never searches.  README.md records what this gave.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".xla_cache"))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--rates", type=float, nargs="+", required=True)
    args = parser.parse_args(argv)

    import jax

    from benchmark import loadgen
    from benchmark.common import device_info, load_cell, load_module, merge
    from benchmark.drivers.serve import write_checkpoint

    device = device_info()
    if device["platform"] != "tpu":
        print(f"refused: no TPU ({device})", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    config, traffic = cell["config_file"], cell["traffic_file"]
    ref = load_module("reference", config["reference"])
    out_dir = os.path.join(ROOT, "run", "benchmark", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    ckpt = os.path.join(out_dir, f"checkpoint-{args.seed}")
    if not os.path.isdir(ckpt):
        params = jax.device_get(ref.make_params(args.seed, ref.sizes_of(config)))
        write_checkpoint(ref.to_checkpoint_tree(params), ckpt)
        del params
    cfg = merge(config["serve"], {"serving": traffic.get("serving", {})})
    cfg["serving"]["checkpoint"] = ckpt

    from pytorch_distributed_training_tpu.serving import InferenceEngine

    with InferenceEngine.from_config(cfg) as engine:
        engine.warmup()
        for rate in args.rates:
            mix = dict(traffic, rate_rps=rate)
            trace = loadgen.make_trace(mix, args.seconds)
            prompts = loadgen.make_prompts(
                trace, mix, int(config["vocab_size"]), args.seed
            )
            client = loadgen.OpenLoopClient(engine.submit, trace, prompts)
            t0 = time.monotonic() + float(mix.get("lead_in_s", 0.0)) + 0.2
            client.start(t0)
            time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
            depth_at_close = engine.depth()
            client.finish(t0 + args.seconds + float(mix["drain_s"]))
            records = loadgen.counted(client.records)
            ttft = loadgen.ttft_ms(client.records)
            quarter = max(1, len(ttft) // 4)
            gaps = loadgen.gaps_ms(client.records)
            finished = [r.finished for r in client.records if r.finished]
            print("sweep " + json.dumps({
                "rate_rps": rate, "requests": len(records),
                "failed": sum(loadgen.failed(r) for r in records),
                "ttft_p50_ms": loadgen.percentile(ttft, 50),
                "ttft_p95_ms": loadgen.percentile(ttft, 95),
                "ttft_first_quarter_p50_ms": statistics.median(ttft[:quarter]),
                "ttft_last_quarter_p50_ms": statistics.median(ttft[-quarter:]),
                "itl_p50_ms": loadgen.percentile(gaps, 50),
                "itl_p95_ms": loadgen.percentile(gaps, 95),
                "itl_p99_ms": loadgen.percentile(gaps, 99),
                "tokens_per_s": loadgen.tokens_per_s(client.records, t0, args.seconds),
                "offered_tokens_per_s": sum(r.arrival.gen_len for r in records) / args.seconds,
                "queue_depth_at_close": depth_at_close,
                "drain_s": (max(finished) - (t0 + args.seconds)) if finished else None,
                "generator_lag_p95_ms": loadgen.percentile(loadgen.lag_ms(client.records), 95),
            }), flush=True)
            time.sleep(1.0)
        print("sweep_snapshot " + json.dumps({
            k: v for k, v in engine.metrics.snapshot().items()
            if not isinstance(v, dict)
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
