"""Median over the window's steps of (``step_dispatch`` less the nested
``device_block``) less the span's ``cpu_ms``: what the loop's thread spent
neither computing nor waiting for the device.  ``cpu_ms`` is the thread's own
CPU time inside the span (``telemetry/spans.py::span(cpu=True)``), and a
thread blocked on the device, a lock or a core burns none, so the nested
``device_block`` is taken from the wall alone.  From the span FILE of the
window, as ``host_dispatch_ms_per_step``; None under a program whose
``step_dispatch`` carries no ``cpu_ms``."""
from benchmark import spans

META = {"source": "program_span"}


def read(run):
    if not run.window:
        return None
    cpu_ms = {}
    for span in run.spans:
        if span.get("kind") == "step_dispatch" and "cpu_ms" in span:
            step = int(span["step"])
            cpu_ms[step] = cpu_ms.get(step, 0.0) + float(span["cpu_ms"])
    table = spans.by_step(run.spans)
    values = []
    for step in range(run.window["first_step"], run.window["last_step"] + 1):
        kinds = table.get(step, {})
        if step in cpu_ms and "step_dispatch" in kinds:
            blocked = kinds.get("device_block", {"ms": 0.0})["ms"]
            values.append(kinds["step_dispatch"]["ms"] - blocked - cpu_ms[step])
    return spans.median_ms(values)
