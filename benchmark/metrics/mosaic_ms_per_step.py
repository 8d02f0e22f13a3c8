"""Summed device time of the Mosaic (Pallas) custom calls, per execution
of the step program, in the traced steps."""
from benchmark import trace

META = {"source": "device_trace"}


def read(run):
    if not run.trace or not run.trace.get("devices"):
        return None
    name = trace.main_program(run.trace)
    if not name or not run.trace["mosaic_s"]:
        return None
    return run.trace["mosaic_s"] / run.trace["programs"][name]["count"] * 1e3
