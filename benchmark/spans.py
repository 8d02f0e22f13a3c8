"""The timed window of a training run, read from the program's own span
file (``telemetry/spans.py``: kind, step, monotonic start ``t``, ``ms``).

A step that prints its loss ends in a host read of it (a ``device_block``
span nested in its ``step_dispatch``): at that instant every step so far is
done on the device.  The window runs from the end of such a step to the end
of a later one, so the steps between them are all the work of exactly that
time, however far the host had run ahead in between.
"""
from __future__ import annotations

import json
import statistics
from typing import Dict, List, Optional


def read_spans(path: str) -> List[dict]:
    with open(path) as fp:
        return [json.loads(line) for line in fp if line.strip()]


def by_step(spans: List[dict]) -> Dict[int, Dict[str, dict]]:
    """``{step: {kind: span}}`` for the per-step kinds.  A kind recorded
    twice in a step keeps the sum of its durations and the first start."""
    table: Dict[int, Dict[str, dict]] = {}
    for span in spans:
        if span.get("step") is None:
            continue
        slot = table.setdefault(int(span["step"]), {})
        if span["kind"] in slot:
            slot[span["kind"]]["ms"] += span["ms"]
        else:
            slot[span["kind"]] = {"t": float(span["t"]), "ms": float(span["ms"])}
    return table


def _end(span: dict) -> float:
    return span["t"] + span["ms"] / 1e3


def find_window(spans: List[dict], warmup_steps: int, seconds: float
                ) -> Optional[dict]:
    """The window: opens at the end of the first synced step at or after
    ``warmup_steps``, closes at the end of the first synced step that ends
    ``seconds`` or more later.  ``None`` when the run's steps ended before
    the window was full (the run is then not correct; never a shorter
    window)."""
    table = by_step(spans)
    synced = sorted(
        s for s, kinds in table.items()
        if "device_block" in kinds and "step_dispatch" in kinds
    )
    opening = next((s for s in synced if s >= warmup_steps), None)
    if opening is None:
        return None
    t0 = _end(table[opening]["step_dispatch"])
    for step in synced:
        t1 = _end(table[step]["step_dispatch"])
        if step > opening and t1 - t0 >= seconds:
            return {
                "first_step": opening + 1, "last_step": step,
                "steps": step - opening, "t0": t0, "t1": t1,
                "seconds": t1 - t0,
            }
    return None


def per_step_ms(spans: List[dict], window: dict, kind: str,
                minus: Optional[str] = None) -> List[float]:
    """Duration of ``kind`` in each step of the window, less the nested
    ``minus`` span where the step has one."""
    table = by_step(spans)
    out = []
    for step in range(window["first_step"], window["last_step"] + 1):
        kinds = table.get(step, {})
        if kind not in kinds:
            continue
        ms = kinds[kind]["ms"]
        if minus and minus in kinds:
            ms -= kinds[minus]["ms"]
        out.append(ms)
    return out


def median_ms(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None
