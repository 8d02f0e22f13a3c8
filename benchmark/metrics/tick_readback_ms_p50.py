"""Median over productive ticks of the phase ``readback``: the host blocked
on the decode step's sampled tokens (``ServingMetrics.snapshot()``)."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("tick_readback_ms_p50")
