"""Median over the traced, paired decode steps of ``readback_wait`` end less
(the device ends the step): from the device finishing to the host holding its
tokens.  The end is the step's begin (``decode_launch_lag_ms_p50``) plus the
DURATION of its module event, found by ``run_id``; the launch's own latency is
thereby inside this lag, and ``notice_ms_p50`` in the run's ``notes`` (the end
to the runtime's ``CompleteCallbacks``) bounds it (``benchmark/tick_spans.py``)."""
from benchmark import tick_spans

META = {"source": "device_trace"}


def read(run):
    return tick_spans.part_ms_p50(run, "return_lag")
