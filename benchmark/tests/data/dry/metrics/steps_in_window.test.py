"""Test only: a per-layer metric added as a file."""
META = {"source": "program_span"}


def read(run):
    return run.window["steps"] if run.window else None
