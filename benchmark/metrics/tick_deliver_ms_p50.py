"""Median over productive ticks of the phase ``deliver``: pushing each row's
token, the client's ``on_token``, retiring finished rows
(``ServingMetrics.snapshot()``)."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("tick_deliver_ms_p50")
