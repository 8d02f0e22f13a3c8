"""Share of the traced window in which no operation ran on the device:
1 - union of the operations' intervals over the window, averaged over the
chips used (``trace.idle_pct``).  One reader a kind of cell, because the
cells of a kind report different end-to-end metrics."""
from benchmark import trace

META = {"source": "device_trace"}


def read(run):
    return trace.idle_pct(run.trace)
