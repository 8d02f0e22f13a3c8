"""Median time the loader's producer takes to assemble one batch (span
``batch_assemble``: fetch the samples, stack, normalise), over the batches
assembled inside the window.  It runs beside the loop, so it bounds the rate
(a batch every so many ms) without being part of a step's time."""
import statistics

META = {"source": "program_span"}


def read(run):
    w = run.window
    if not w:
        return None
    made = [s["ms"] for s in run.spans
            if s["kind"] == "batch_assemble" and w["t0"] <= s["t"] < w["t1"]]
    return statistics.median(made) if made else None
