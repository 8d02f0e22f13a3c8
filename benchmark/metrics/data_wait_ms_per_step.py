"""Median over the window's steps of the ``data_wait`` span: how long the
loop waited for the loader."""
from benchmark import spans

META = {"source": "program_span"}


def read(run):
    if not run.window:
        return None
    return spans.median_ms(spans.per_step_ms(run.spans, run.window, "data_wait"))
