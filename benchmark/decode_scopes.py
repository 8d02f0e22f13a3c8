"""Device time of one scope of the program inside the DECODE program, a
step: what the per-layer metrics of the expert layer and of the latent
attention read.

An operation's ``op_name`` starts with the program it belongs to
(``jit(decode_step)/...``), so the prefills that fall between decode steps
are left out by name, and the count of steps is the decode program's own
executions in the trace (``benchmark/trace.py``).  Under a program that has
no such scope, as a parent commit has not, nothing matches and the reader
returns None.
"""
from benchmark import trace, xplane

DECODE = "jit(decode_step)"


def seconds_and_steps(run, scope: str):
    """``(device seconds under scope in decode steps, decode steps)``, or
    ``None`` where there is nothing to read."""
    ops = xplane.ops_of(run)
    if not ops or not run.trace or not run.trace.get("devices"):
        return None
    program = trace.main_program(run.trace, "decode_step")
    inside = xplane.in_scope(scope)
    total = xplane.seconds(ops, lambda op: DECODE in op.scope and inside(op))
    if not program or not total:
        return None
    return total, run.trace["programs"][program]["count"]


def ms_per_decode_step(run, scope: str):
    found = seconds_and_steps(run, scope)
    return found[0] / found[1] * 1e3 if found else None
