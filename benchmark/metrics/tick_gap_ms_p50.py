"""Median gap between two back-to-back productive ticks of the traced
seconds, the loop asleep in none of them (``benchmark/tick_spans.py``)."""
from benchmark import tick_spans

META = {"source": "program_span"}


def read(run):
    return tick_spans.tick_gap_ms_p50(run)
