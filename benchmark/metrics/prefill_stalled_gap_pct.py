"""Of the token gaps of the traced seconds, the share with a prefill in it:
rows that sat through a prefill over rows stepped
(``benchmark/tick_spans.py``).  Over 5 the 95th percentile gap is a
prefill's step, under 5 a plain tick."""
from benchmark import tick_spans

META = {"source": "program_span"}


def read(run):
    return tick_spans.prefill_stalled_gap_pct(run)
