"""Prompt tokens prefilled per second of prefill time, from the program's
own ``ServingMetrics.snapshot()``."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("prefill_tokens_per_sec")
