"""95th percentile of submit to the first token pushed, one a request, on the
scheduler's own stamps (``ServingMetrics.snapshot()``): the engine's side of
the client's ``ttft_p95_ms``, which counts from the DUE time and so adds the
generator's lag; the lead-in's requests are in it."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("ttft_ms_p95")
