"""One process, one cell, once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration and traffic files by
name, the driver by the traffic's ``kind``, and each metric's reader by the
metric's name: no table of cells or metrics lives here.  Refuses to run when
JAX finds no TPU or another number of chips than the cell asks for.  The last
line of the output is the result: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, in a traced run, ``breakdown``.

``--dry`` (the benchmark's own tests only) drives the same control flow on
the CPU at the sizes of a ``*.dry`` cell; it reports ``correct: false``
whatever it finds, names the device it ran on, and measures nothing.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
# one fixed place for compiled programs, inside the checkout, unless the
# launcher names another: the program follows the same rule
# (utils.enable_compile_cache), and the path is part of the cache's key
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".xla_cache"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--dry", action="store_true",
                        help="tests only: control flow on the CPU, never correct")
    parser.add_argument("--control", action="store_true",
                        help="limit-setting only: also read the lower-precision control")
    return parser.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark.common import (
        CompileLedger, device_info, load_cell, load_module,
    )

    cell = load_cell(args.workload)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = device_info()
    if not args.dry and (
        device["platform"] != "tpu" or device["count"] != cell["chips"]
    ):
        print(
            f"refused: {args.workload} needs {cell['chips']} TPU chip(s), JAX "
            f"found {device['count']} x {device['platform']} "
            f"({device['kind']}); the benchmark never falls back",
            file=sys.stderr,
        )
        return 2
    print("run " + json.dumps({
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "dry": args.dry, "device": device,
        "compile_cache": os.environ["JAX_COMPILATION_CACHE_DIR"],
        "traffic": cell["traffic_file"],
    }), flush=True)

    out_dir = os.path.join(ROOT, "run", "benchmark", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    ledger = CompileLedger()
    kind = cell["traffic_file"]["kind"]
    driver = importlib.import_module(f"benchmark.drivers.{kind}")
    run, check, attempted, failed = driver.run(cell, args, out_dir, ledger, T_START)
    run.device = device

    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for meta in wanted:
        value = load_module("metrics", meta["name"]).read(run)
        if value is not None:
            metrics[meta["name"]] = {"value": float(value), "unit": meta["unit"]}
    check.print()
    print("notes " + json.dumps(run.notes, default=str), flush=True)
    device_out = dict(device, memory_peak_bytes=int(run.notes["memory_peak_bytes"]))
    result = {
        "correct": bool(check.correct and not args.dry),
        "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": device_out,
    }
    if args.trace and run.trace and run.trace.get("devices"):
        device_out["busy_s"] = run.trace["busy_s"]
        device_out["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace["device_ops"][:10],
            "idle_gaps": run.trace["idle_gaps"][:10],
        }
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
