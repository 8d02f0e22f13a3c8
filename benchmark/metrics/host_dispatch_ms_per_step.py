"""Median over the window's steps of ``step_dispatch`` less the
``device_block`` nested in it: the host's own work to launch a step."""
from benchmark import spans

META = {"source": "program_span"}


def read(run):
    if not run.window:
        return None
    return spans.median_ms(
        spans.per_step_ms(run.spans, run.window, "step_dispatch", "device_block")
    )
