"""The loop's wait for the loader's next host batch (span ``loader_wait``:
``data/loader.py``'s queue, ``data/worker_pool.py``'s results), summed over
the window and divided by its steps.  With ``h2d_put_ms_per_step`` it
accounts for ``data_wait_ms_per_step``, inside which both nest."""
META = {"source": "program_span"}


def read(run):
    w = run.window
    if not w:
        return None
    waits = [s["ms"] for s in run.spans
             if s["kind"] == "loader_wait" and w["t0"] <= s["t"] < w["t1"]]
    return sum(waits) / w["steps"] if waits else None
