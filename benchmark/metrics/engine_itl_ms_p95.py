"""95th percentile of every gap between two consecutive pushes of one request,
pooled, on the scheduler's own stamps (``ServingMetrics.snapshot()``): the
engine's side of the client's ``serve_itl_p95_ms``; the lead-in's requests
are in it."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("itl_ms_p95")
