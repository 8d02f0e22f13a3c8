"""CLI: ``python -m pytorch_distributed_training_tpu.analysis``.

Exit code 0 when no unsuppressed (and non-baselined) findings remain,
1 otherwise — the tier-1 gate keys off it.

Examples::

    python -m pytorch_distributed_training_tpu.analysis
    python -m pytorch_distributed_training_tpu.analysis --format json
    python -m pytorch_distributed_training_tpu.analysis \
        --rules trace-purity,donation-safety --verbose
    python -m pytorch_distributed_training_tpu.analysis \
        --write-baseline .pdt-baseline.json
    python -m pytorch_distributed_training_tpu.analysis --collectives
    python -m pytorch_distributed_training_tpu.analysis --schema
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from . import (
    ALL_PASSES,
    extract_collective_sequences,
    extract_schema,
    render_json,
    render_text,
    run,
    schema_as_json,
    write_baseline,
)
from .core import collect_modules


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="pdt-analyze",
        description="static analysis: trace purity, lock discipline, "
        "collective order, donation safety, repo conventions, inferred-"
        "lockset thread safety, resource lifecycles, config schema",
    )
    parser.add_argument(
        "--root",
        type=Path,
        default=None,
        help="package root to analyze (default: the installed "
        "pytorch_distributed_training_tpu tree)",
    )
    parser.add_argument(
        "--rules",
        default=None,
        help="comma-separated rule subset: "
        + ",".join(cls.rule for cls in ALL_PASSES),
    )
    parser.add_argument("--format", choices=("text", "json"), default="text")
    parser.add_argument(
        "--baseline", type=Path, default=None, help="baseline JSON to subtract"
    )
    parser.add_argument(
        "--write-baseline",
        type=Path,
        default=None,
        metavar="PATH",
        help="write current unsuppressed findings as a baseline and exit 0",
    )
    parser.add_argument(
        "--verbose", action="store_true", help="also list suppressed/baselined"
    )
    parser.add_argument(
        "--collectives",
        action="store_true",
        help="print the per-family collective-order extraction and exit",
    )
    parser.add_argument(
        "--schema",
        action="store_true",
        help="print the generated config schema (accepted keys, types, "
        "defaults per section) as JSON and exit",
    )
    args = parser.parse_args(argv)

    if args.schema:
        root = args.root or Path(__file__).resolve().parent.parent
        modules = collect_modules(Path(root), Path(root).parent)
        print(json.dumps(schema_as_json(extract_schema(modules)), indent=2))
        return 0

    if args.collectives:
        root = args.root or Path(__file__).resolve().parent.parent
        seqs = extract_collective_sequences(root)
        for family in sorted(seqs):
            print(f"family {family}:")
            for builder, calls in seqs[family].items():
                print(f"  {builder}:")
                for c in calls:
                    print(f"    {c.op}({c.axis})  [{c.function}:{c.line}]")
        return 0

    rules = args.rules.split(",") if args.rules else None
    result = run(package_root=args.root, rules=rules, baseline=args.baseline)

    if args.write_baseline is not None:
        write_baseline(args.write_baseline, result.unsuppressed)
        print(
            f"wrote baseline with {len(result.unsuppressed)} finding(s) "
            f"to {args.write_baseline}"
        )
        return 0

    if args.format == "json":
        print(render_json(result))
    else:
        print(render_text(result, verbose=args.verbose))
    return 0 if not result.unsuppressed else 1


if __name__ == "__main__":
    sys.exit(main())
