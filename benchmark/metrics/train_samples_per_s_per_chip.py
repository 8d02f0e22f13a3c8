"""Samples (sequences or images) through whole optimizer steps, per second
per chip, loader running: all the steps of the window over all its time."""
META = {"source": "host_clock"}


def read(run):
    w = run.window
    if not w:
        return None
    return w["steps"] * run.samples_per_step / w["seconds"] / run.chips
