"""Device time of the operations under the scope ``optimizer`` (clipping,
the update, the EMA) per execution of the step program.  Where XLA fuses a
weight's update into the matmul that makes its gradient, the fusion counts
on the side of the instruction that gave it its ``op_name``."""
from benchmark import xplane

META = {"source": "device_trace"}


def read(run):
    return xplane.ms_per_step(run, xplane.in_scope("optimizer"))
