"""Of the tokens the traced prefill calls ran, the share that is padding to
the batch and sequence buckets (``benchmark/tick_spans.py``)."""
from benchmark import tick_spans

META = {"source": "program_span"}


def read(run):
    return tick_spans.prefill_padding_pct(run)
