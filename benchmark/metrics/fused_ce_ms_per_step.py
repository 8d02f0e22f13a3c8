"""Device time of the fused cross-entropy kernels (Pallas calls named
``fused_ce_fwd`` and ``fused_ce_bwd``) per execution of the step program, in
the traced steps."""
from benchmark import xplane

META = {"source": "device_trace"}


def read(run):
    return xplane.ms_per_step(run, xplane.kernel("fused_ce_"))
