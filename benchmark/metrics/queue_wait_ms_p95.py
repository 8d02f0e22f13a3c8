"""95th percentile of submit to first admission into a slot, one a request,
on the scheduler's own stamps (``ServingMetrics.snapshot()``); the lead-in's
requests are in it."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("queue_wait_ms_p95")
