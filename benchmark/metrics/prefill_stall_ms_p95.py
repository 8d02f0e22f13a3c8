"""95th percentile of the prefill phase of those ticks that had rows already
decoding: what their next token waited beyond a plain decode step
(``ServingMetrics.snapshot()``)."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("prefill_stall_ms_p95")
