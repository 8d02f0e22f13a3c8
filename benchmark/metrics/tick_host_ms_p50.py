"""Median host time of one scheduler tick, from the program's own
``ServingMetrics.snapshot()``."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("tick_host_ms_p50")
