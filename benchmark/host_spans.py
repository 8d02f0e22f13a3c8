"""The program's own spans inside a traced run, with their fields.

``telemetry/spans.py::span(kind, **fields)`` also opens a
``TraceAnnotation``: in a trace every span is an event of the host plane
named by its kind, its fields the event's stats.  That is how a reader gets
the counts of the TRACED seconds (rows live in a decode step, experts that
got a token, rows and bucket of a prefill) beside the device time of the same
seconds, where ``ServingMetrics.snapshot()`` holds the whole run's means.

A hand-made trace (``.json.gz``) lists them under ``"spans"``:
``{kind: [{field: value, ..., "start_s": .., "end_s": ..}, ...]}``.  Under a
program that emits no such span, as a parent commit may not, the list is
empty.
"""
import functools
import gzip
import json

from benchmark import trace


@functools.lru_cache(maxsize=2)
def _by_kind(path: str, kinds: tuple) -> dict:
    found = {kind: [] for kind in kinds}
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as fp:
            listed = json.load(fp).get("spans", {})
        return {kind: [dict(s) for s in listed.get(kind, [])] for kind in kinds}
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name in found:
                    start = event.start_ns / 1e9
                    found[event.name].append(dict(
                        event.stats, start_s=start,
                        end_s=start + event.duration_ns / 1e9))
    return found


# every kind a reader asks for, so that one pass over the file serves all
KINDS = ("prefill", "decode_step", "moe_counts")


def spans(path: str, kind: str) -> list:
    """The spans of ``kind`` in the trace at ``path``, each a dict of its
    fields with ``start_s`` and ``end_s`` on the clock of the trace's device
    operations."""
    return _by_kind(path, KINDS)[kind]


def mean_field(path: str, kind: str, field: str):
    """Mean of one numeric field over the spans of ``kind`` that carry it;
    None where there is none."""
    values = [float(s[field]) for s in spans(path, kind) if field in s]
    return sum(values) / len(values) if values else None
