"""Synthetic open-loop serving demo / smoke entrypoint.

    python -m pytorch_distributed_training_tpu.serving \
        --config config/serve-lm.yml [--requests 32] [--log-dir /tmp/serve]

Builds an :class:`.engine.InferenceEngine` from the config, fires
``--requests`` synthetic requests at it open-loop (LM: random prompts of
varying length within the seq buckets; classification: random images),
waits on every future, and reports p50/p99 latency, max queue depth, and
items/sec through the repo's logging funnel — the final line is one JSON
object.  Compiled programs persist where
``utils.enable_compile_cache`` says (``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.xla_cache``), so a relaunch skips the bucket-grid compiles.
"""
from __future__ import annotations

import argparse
import json
import sys
from functools import partial

import numpy as np

from ..config_parsing import get_serve_cfg, get_train_logger
from ..logger import MultiProcessLoggerListener
from ..utils import enable_compile_cache
from .engine import InferenceEngine


def _synthetic_payloads(cfg, engine: InferenceEngine, n: int, seed: int):
    rng = np.random.default_rng(seed)
    vocab = cfg["dataset"]["n_classes"]
    if engine.is_lm:
        max_prompt = engine.seq_buckets[-1]
        for _ in range(n):
            ln = int(rng.integers(1, max_prompt + 1))
            yield rng.integers(0, vocab, ln).astype(np.int32)
    else:
        size = engine.image_size
        for _ in range(n):
            yield rng.integers(0, 256, (size, size, 3)).astype(np.uint8)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m pytorch_distributed_training_tpu.serving",
        description="serve a checkpoint against a synthetic request stream",
    )
    parser.add_argument("--config", required=True, help="serve-*.yml path")
    parser.add_argument("--requests", type=int, default=32)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--log-dir", default="/tmp/pdt-serve")
    args = parser.parse_args(argv)

    cfg = get_serve_cfg(args.config)
    listener = MultiProcessLoggerListener(
        partial(get_train_logger, args.log_dir, "serve"), "spawn"
    )
    logger = listener.get_logger()
    try:
        with InferenceEngine.from_config(cfg, logger=logger) as engine:
            # SIGTERM -> graceful drain (stop admitting, finish in-flight
            # under resilience.drain_deadline_ms, then close) — the
            # orchestrated-shutdown path, wired here because signal
            # handlers must install from the main thread
            engine.install_drain_handler()
            modes = [m for m, on in engine.serving_modes.items() if on]
            logger.info(
                "engine up: task=%s batch_buckets=%s seq_buckets=%s modes=%s",
                "lm" if engine.is_lm else "image",
                engine.batch_buckets,
                engine.seq_buckets if engine.is_lm else "-",
                "+".join(modes) if modes else "baseline",
            )
            futures = [
                engine.submit(p)
                for p in _synthetic_payloads(cfg, engine, args.requests, args.seed)
            ]
            for fut in futures:
                fut.result(timeout=300)
            snap = engine.metrics.log_summary(logger)
            snap["compile_count"] = engine.compile_count()
        logger.info("served %d requests, %d XLA programs compiled",
                    args.requests, snap["compile_count"])
        print(json.dumps({"serving": snap}))
        return 0
    finally:
        listener.stop()


if __name__ == "__main__":
    # process-global JAX config belongs to the process entry, not to main():
    # tests call main() in-process and must not inherit a cache directory
    enable_compile_cache()
    sys.exit(main())
