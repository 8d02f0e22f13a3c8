"""Median over productive ticks of the host work that stands before the decode
dispatch: the phases ``admit`` + ``decode_prep`` of one tick
(``ServingMetrics.snapshot()``)."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("tick_prep_ms_p50")
