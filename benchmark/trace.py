"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics need: device busy and idle time, device time per program, the
Mosaic (Pallas) and collective sums, and for ``breakdown`` the device
operations that took most time and the longest idle gaps, named by the
program span the host was in.

Read with ``jax.profiler.ProfileData`` and numpy alone.  Times are seconds.  On a TPU
every device is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event an executed HLO operation and whose line ``XLA Modules`` holds one
event an executed program (``jit_<name>(<fingerprint>)``).
"""
from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# An ``XLA Ops`` event is named by its whole HLO instruction, operands and
# all (``%jvp__.1 = (f32[...]) custom-call(...), custom_call_target=
# "tpu_custom_call", ...``).  ``load`` keeps the instruction's own name and
# puts its kind in front: ``mosaic:%jvp__.1``, ``collective:%all-gather.3``.
# The kernels pass no ``name=`` today, so flash attention and fused CE are
# told apart by nothing but their operands (PERF.md, the tracing list).
_MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_COLLECTIVE_OP = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\("
)
MOSAIC = re.compile(r"^mosaic:")
COLLECTIVE = re.compile(r"^collective:")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def op_name(hlo: str) -> str:
    """The compact, tagged name of one ``XLA Ops`` event."""
    short, _, rest = hlo.partition(" = ")
    if _MOSAIC_TARGET in rest:
        return "mosaic:" + short
    if _COLLECTIVE_OP.search(rest):
        return "collective:" + short
    return short

Interval = Tuple[float, float]


def find_xplane(directory: str) -> Optional[str]:
    paths = sorted(glob.glob(
        os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return paths[-1] if paths else None


def load(path: str) -> dict:
    """``{plane: {line: [(name, start_s, duration_s), ...]}}`` for every
    plane of the file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, list]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            is_ops = bool(DEVICE_PLANE.match(plane.name)) and line.name == OPS_LINE
            for event in line.events:
                name = op_name(event.name) if is_ops else event.name
                events.append((name, event.start_ns / 1e9, event.duration_ns / 1e9))
    return planes


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def program_name(module_event: str) -> str:
    """``jit_train_step(1234567)`` -> ``jit_train_step``."""
    return _FINGERPRINT.sub("", module_event)


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gaps(gaps: List[Interval], frames: List[tuple]) -> Dict[str, float]:
    """What the host was doing in each idle gap, as ``{name: seconds}``: a
    gap takes the name of the SHORTEST host event that covers at least half
    of it (the outer frames of the loop cover every gap and say nothing).
    The host's plane can hold millions of events (the runtime logs every
    piece of a batch it transposes for the device), so the search is one
    numpy pass a gap over the events long enough to cover any of them."""
    import numpy as np

    named: Dict[str, float] = {}
    if not gaps:
        return named
    need_min = 0.5 * min(end - start for start, end in gaps)
    kept = [f for f in frames if f[2] >= need_min]
    starts = np.array([f[1] for f in kept], dtype=np.float64)
    durs = np.array([f[2] for f in kept], dtype=np.float64)
    ends = starts + durs
    for start, end in gaps:
        name = "no_host_frame"
        overlap = np.minimum(ends, end) - np.maximum(starts, start)
        covering = np.flatnonzero(overlap >= 0.5 * (end - start))
        if covering.size:
            name = kept[covering[np.argmin(durs[covering])]][0]
        named[name] = named.get(name, 0.0) + (end - start)
    return named


def reduce(planes: dict, top: int = 10) -> dict:
    """The reduction.  The window runs from the first to the last device
    event: the profiler's own start and stop, during which the host blocks,
    are outside it."""
    devices = {
        name: lines for name, lines in planes.items() if DEVICE_PLANE.match(name)
    }
    every = [
        (start, start + dur)
        for lines in devices.values() for line in (OPS_LINE, MODULES_LINE)
        for _, start, dur in lines.get(line, [])
    ]
    if not devices or not every:
        return {"devices": 0}
    window = (min(s for s, _ in every), max(e for _, e in every))
    # every host thread's events: the runtime's own and the annotations the
    # drivers write (a line is named after its thread, so no name is special)
    frames = [e for line in planes.get(HOST_PLANE, {}).values() for e in line]
    busy_per_device, gaps = [], []
    op_time: Dict[str, float] = {}
    mosaic_s = collective_s = exposed_s = 0.0
    programs: Dict[str, List[float]] = {}
    for lines in devices.values():
        ops = lines.get(OPS_LINE, [])
        busy = union((s, s + d) for _, s, d in ops)
        busy_per_device.append(sum(e - s for s, e in busy))
        edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
        compute = union(
            (s, s + d) for name, s, d in ops if not COLLECTIVE.match(name)
        )
        for name, start, dur in ops:
            op_time[name] = op_time.get(name, 0.0) + dur
            if MOSAIC.search(name):
                mosaic_s += dur
            if COLLECTIVE.match(name):
                collective_s += dur
                hidden = sum(
                    _overlap((start, start + dur), iv) for iv in compute
                )
                exposed_s += dur - hidden
        for name, _, dur in lines.get(MODULES_LINE, []):
            programs.setdefault(program_name(name), []).append(dur)
    n = len(devices)
    # the longest gaps carry the idle time; naming each of thousands of
    # microsecond gaps between operations would cost more than it tells:
    # at most 200 are named, each at least a thousandth of all idle time
    gaps = sorted((g for g in gaps if g[1] > g[0]), key=lambda g: g[0] - g[1])
    floor = 1e-3 * sum(end - start for start, end in gaps)
    long_gaps = [g for g in gaps[:200] if g[1] - g[0] >= floor]
    named_gaps = {
        name: secs / n for name, secs in name_gaps(long_gaps, frames).items()
    }
    rest = sum(end - start for start, end in gaps[len(long_gaps):]) / n
    if rest > 0:
        named_gaps["gaps_between_operations"] = rest

    def ranked(table):
        return [
            [name, secs] for name, secs in
            sorted(table.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {
        "devices": n,
        "window_s": window[1] - window[0],
        "busy_s": sum(busy_per_device) / n,
        "mosaic_s": mosaic_s / n,
        "collective_s": collective_s / n,
        "collective_exposed_s": exposed_s / n,
        "programs": {
            name: {
                "count": len(durs) // n or len(durs),
                "total_s": sum(durs) / n,
                "median_s": statistics.median(durs),
            }
            for name, durs in programs.items()
        },
        "device_ops": ranked({k: v / n for k, v in op_time.items()}),
        "idle_gaps": ranked(named_gaps),
    }


def main_program(reduced: dict, hint: str = "") -> Optional[str]:
    """The program with most device time among those whose name holds
    ``hint``: the step of a training run, the decode step of a server."""
    table = {
        name: rec for name, rec in reduced.get("programs", {}).items()
        if hint in name
    }
    if not table:
        return None
    return max(table, key=lambda name: table[name]["total_s"])


def idle_pct(reduced: Optional[dict]) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device,
    averaged over the chips used."""
    if not reduced or not reduced.get("devices") or not reduced["window_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def program_median_ms(reduced: Optional[dict], hint: str = "") -> Optional[float]:
    """Median device time of one execution of ``main_program(hint)``."""
    if not reduced or not reduced.get("devices"):
        return None
    name = main_program(reduced, hint)
    return reduced["programs"][name]["median_s"] * 1e3 if name else None


def summary(planes: dict, top: int = 25) -> dict:
    """What a person looks at before writing code against a trace: planes,
    lines, event counts, the most frequent names, the time base."""
    out = {}
    for plane, lines in planes.items():
        out[plane] = {}
        for line, events in lines.items():
            names: Dict[str, float] = {}
            for name, _, dur in events:
                names[name] = names.get(name, 0.0) + dur
            out[plane][line] = {
                "events": len(events),
                "first_start_s": min((s for _, s, _ in events), default=None),
                "top": sorted(names.items(), key=lambda kv: -kv[1])[:top],
            }
    return out


def cut(planes: dict, start_s: float, end_s: float) -> dict:
    """The part of every event that lies inside ``[start_s, end_s)``: how
    the recorded chip traces under ``tests/data`` were cut to size."""
    def clip(event):
        name, start, dur = event
        lo, hi = max(start, start_s), min(start + dur, end_s)
        return (name, lo, hi - lo) if hi > lo else None

    return {
        plane: {
            line: [c for c in map(clip, events) if c]
            for line, events in lines.items()
        }
        for plane, lines in planes.items()
    }


if __name__ == "__main__":
    import json
    import sys

    _planes = load(find_xplane(sys.argv[1]) or sys.argv[1])
    print(json.dumps(summary(_planes), indent=1))
