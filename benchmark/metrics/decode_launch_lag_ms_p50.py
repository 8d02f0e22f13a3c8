"""Median over the traced, paired decode steps of (the device begins the
step) less (``decode_step`` span start): from the host entering the dispatch
to the device beginning.  The device begins when the runtime has enqueued the
program (the end of its ``DoEnqueueProgram`` event, on the host's clock) and
the run before it is over; the device plane's own stamps are not on the
host's clock and are not used (``benchmark/tick_spans.py``)."""
from benchmark import tick_spans

META = {"source": "device_trace"}


def read(run):
    return tick_spans.part_ms_p50(run, "launch_lag")
