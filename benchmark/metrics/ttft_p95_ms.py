"""95th percentile, over ALL requests due in the window, of the time from
when the request was due to the client's receipt of its first token.  A
failed request misses any limit (it counts as infinitely late).

A per-layer reading, not an end-to-end metric: at 0.8 x the knee it spread
18% between runs of one code and read five times higher whenever a
transient slowdown let the queue build (PERF.md, PR 23), so no bound of at
most 10% could hold it."""
import math

from benchmark import loadgen

META = {"source": "host_clock"}


def read(run):
    if not run.serve:
        return None
    value = loadgen.percentile(loadgen.ttft_ms(run.serve["records"]), 95)
    return value if value is not None and math.isfinite(value) else None
