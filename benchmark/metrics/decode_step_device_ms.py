"""Median device time of one execution of the decode-step program, found
by the program name the trace's module line carries."""
from benchmark import trace

META = {"source": "device_trace"}


def read(run):
    return trace.program_median_ms(run.trace, "decode")
