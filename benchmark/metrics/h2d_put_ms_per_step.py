"""The host's time in ``Runner._put_batch`` (span ``h2d_put``: building the
sharded device arrays of one batch, the runtime's re-layout of the host
buffer included), summed over the window and divided by its steps."""
META = {"source": "program_span"}


def read(run):
    w = run.window
    if not w:
        return None
    puts = [s["ms"] for s in run.spans
            if s["kind"] == "h2d_put" and w["t0"] <= s["t"] < w["t1"]]
    return sum(puts) / w["steps"] if puts else None
