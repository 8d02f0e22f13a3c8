"""Device time of one scope of the program inside the PREFILL programs of a
traced run, and the bucket tokens those programs ran: what the per-layer
metrics of the chunked scan read.

An operation's ``op_name`` starts with the program it belongs to
(``jit(prefill)/...``), so the decode steps are left out by name.  The tokens
come from the program's own ``prefill`` spans in the same trace
(``benchmark/host_spans.py``; fields ``rows``, ``bucket``): a call ran
``batch bucket x sequence bucket`` tokens, the batch bucket being the
configuration's smallest that holds the span's rows.  Only prefills whose span lies WHOLE in the trace count, time
and tokens alike (the device runs a prefill inside its span, which ends with
the read of its token): one cut by the trace's edge has no span, and its
operations are left out.  Under a program that has no such scope, as a
parent commit has not, nothing matches and the reader returns None.
"""
from benchmark import host_spans, xplane

PREFILL = "jit(prefill)"


def prefill_calls(path: str):
    """``[(rows, bucket, start_s, end_s), ...]`` of the ``prefill`` spans in
    a trace, on the clock of its device operations."""
    return [
        (int(s["rows"]), int(s["bucket"]), s["start_s"], s["end_s"])
        for s in host_spans.spans(path, "prefill")
        if "rows" in s and "bucket" in s
    ]


def seconds_and_tokens(run, scope: str):
    """``(device seconds under scope in the whole prefills of the trace,
    bucket tokens they ran, padding included)``, or ``None`` where there is
    nothing to read."""
    ops = xplane.ops_of(run)
    if not ops or not run.trace or not run.trace.get("devices"):
        return None
    calls = prefill_calls(run.notes["xplane"])
    buckets = sorted(run.cell["config_file"]["serve"]["serving"]["batch_buckets"])
    tokens = sum(
        next((b for b in buckets if rows <= b), buckets[-1]) * bucket
        for rows, bucket, _, _ in calls
    )
    inside = xplane.in_scope(scope)
    total = xplane.seconds(ops, lambda op: (
        PREFILL in op.scope and inside(op)
        and any(start <= op.start_s < end for _, _, start, end in calls)
    ))
    if not total or not tokens:
        return None
    return total, tokens
