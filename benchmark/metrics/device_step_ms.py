"""Median device time of one execution of the step program: the program
with most device time in the traced steps."""
from benchmark import trace

META = {"source": "device_trace"}


def read(run):
    return trace.program_median_ms(run.trace)
