"""Median, over all requests due in the window, of due time to the client's
receipt of the first token: the steadier companion of ``ttft_p95_ms``."""
import math

from benchmark import loadgen

META = {"source": "host_clock"}


def read(run):
    if not run.serve:
        return None
    value = loadgen.percentile(loadgen.ttft_ms(run.serve["records"]), 50)
    return value if value is not None and math.isfinite(value) else None
