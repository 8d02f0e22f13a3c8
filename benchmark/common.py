"""What every driver of the benchmark shares: files found by name, the
compile ledger, the device's description, and the comparison arithmetic
that decides ``correct``."""
from __future__ import annotations

import collections
import copy
import importlib.util
import json
import os
import statistics
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str):
    with open(path) as fp:
        return json.load(fp)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by name: adding one is adding
    a file.  Names may hold dots (``device_idle_pct.train``)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = merge(out[key], val)
        else:
            out[key] = copy.deepcopy(val)
    return out


def load_cell(name: str) -> dict:
    """One entry of ``BENCHMARK.json``'s workloads with its configuration
    file, its traffic file, and the metrics it reports."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    cell = dict(cells[name])
    configs = {c["name"]: c for c in manifest["configs"]}
    cell["config_file"] = load_json(
        os.path.join(ROOT, configs[cell["config"]]["file"])
    )
    cell["traffic_file"] = load_json(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )

    def mine(metrics):
        return [
            m for m in metrics
            if "workloads" not in m or name in m["workloads"]
        ]

    cell["end_to_end"] = mine(manifest["end_to_end"])
    cell["per_layer"] = mine(manifest["per_layer"])
    return cell


# ------------------------------------------------------------ compile ledger
# Copied from chip_smoke.py::CompileLedger (see PERF.md, Open questions).

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}


class CompileLedger:
    """Counts from JAX's own monitoring events: programs built or read back
    from the cache, seconds in trace / lower / backend, cache hits, misses."""

    def __init__(self):
        import jax

        self.totals = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event in _EVENTS:
            self.totals[_EVENTS[event]] += 1

    def _on_duration(self, event, duration, **_):
        if event in _DURATIONS:
            self.totals[_DURATIONS[event]] += duration
            if event.endswith("backend_compile_duration"):
                self.totals["programs"] += 1

    def mark(self):
        return collections.Counter(self.totals)

    def since(self, mark=None) -> dict:
        mark = mark or collections.Counter()
        d = {k: self.totals[k] - mark[k] for k in self.totals}
        return {
            "compile_s": sum(d.get(k, 0.0) for k in _DURATIONS.values()),
            "backend_compile_s": d.get("backend_compile_s", 0.0),
            "programs": int(d.get("programs", 0)),
            "cache_requests": int(d.get("cache_requests", 0)),
            "cache_hits": int(d.get("cache_hits", 0)),
            "cache_misses": int(d.get("cache_misses", 0)),
        }


# ------------------------------------------------------------------- device

def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def runtime_peak_bytes() -> int:
    """The runtime's own high-water mark on the fullest chip.  On the v5e
    runtime it counts live buffers and not a running program's temporaries,
    so drivers also hold the compiler's account against it."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ]
    return int(max(peaks, default=0))


def program_bytes(compiled) -> int:
    """What one compiled program needs on a device while it runs, by the
    compiler's ``memory_analysis()``: arguments + outputs + temporaries,
    less what is aliased."""
    mem = compiled.memory_analysis()
    return int(
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )


# ----------------------------------------------------------------- compare

def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float]):
    """Over the leaves, the largest gap between the program's norm and the
    reference's (not the norm of their difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (some gradients are all but zero).  Returns ``(gap, leaf)``."""
    if set(program) != set(reference):
        raise ValueError(
            f"leaves differ: {sorted(set(program) ^ set(reference))[:6]}"
        )
    floor = statistics.median(reference.values())
    worst, where = 0.0, None
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, floor, 1e-30)
        if gap != gap:  # NaN is the worst there is
            return gap, leaf
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


@dataclass
class Check:
    """The numbers compared, each beside its limit, printed in every run."""

    rows: List[dict] = field(default_factory=list)

    def add(self, name: str, value: float, limit: float, note: str = ""):
        ok = bool(value <= limit)  # NaN fails
        self.rows.append({
            "compared": name, "value": value, "limit": limit, "ok": ok,
            "note": note,
        })
        return ok

    def require(self, name: str, ok: bool, note: str = ""):
        self.rows.append({
            "compared": name, "value": 0.0 if ok else 1.0, "limit": 0.0,
            "ok": bool(ok), "note": note,
        })
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self) -> None:
        for row in self.rows:
            print("compared " + json.dumps(row), flush=True)


@dataclass
class Run:
    """What one run leaves for the metric readers."""

    cell: dict
    kind: str
    seconds: float
    chips: int
    out_dir: str
    setup_s: float = float("nan")
    spans: List[dict] = field(default_factory=list)
    window: Optional[dict] = None      # t0, t1, steps, samples (train)
    compile_before: Optional[dict] = None
    compile_inside: Optional[dict] = None
    trace: Optional[dict] = None       # benchmark/trace.py's reduction
    serve: Optional[dict] = None       # client records + engine snapshot
    samples_per_step: int = 0
    device: dict = field(default_factory=dict)
    notes: Dict[str, Any] = field(default_factory=dict)
