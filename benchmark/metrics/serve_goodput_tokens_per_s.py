"""Output tokens of the requests due in the window (and not failed) that the
client received inside the window, per second of the window; every token by
its own time stamp, the lead-in's requests left out."""
from benchmark import loadgen

META = {"source": "host_clock"}


def read(run):
    if not run.serve:
        return None
    return loadgen.goodput_tokens_per_s(
        run.serve["records"], run.serve["t0"], run.seconds)
