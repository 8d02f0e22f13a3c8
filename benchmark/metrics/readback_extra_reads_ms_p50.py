"""Median over the traced, paired decode steps of ``readback`` end less
``readback_wait`` end: the guard's read and the expert counts', which wait
for no device work (``benchmark/tick_spans.py``)."""
from benchmark import tick_spans

META = {"source": "program_span"}


def read(run):
    return tick_spans.part_ms_p50(run, "extra_reads")
