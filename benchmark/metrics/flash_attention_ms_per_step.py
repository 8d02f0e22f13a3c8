"""Device time of the flash-attention kernels (Pallas calls named
``flash_*``: forward, fused backward, or its two-kernel form) per execution
of the step program, in the traced steps."""
from benchmark import xplane

META = {"source": "device_trace"}


def read(run):
    return xplane.ms_per_step(run, xplane.kernel("flash_"))
