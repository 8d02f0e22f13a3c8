"""Process start to the first instant of the window: loading, weights,
compilation or reading programs back, warm-up.  The copies the comparison
takes out of the program and the reference's own run are not counted."""
META = {"source": "host_clock"}


def read(run):
    return run.setup_s if run.setup_s == run.setup_s else None
