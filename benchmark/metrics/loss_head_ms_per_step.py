"""Device time of the operations under the scope ``loss_head`` (final norm,
logits matmul, cross-entropy), forward and transpose, per execution of the
step program.  A fusion that XLA made across the scope's edge counts on the
side of the instruction that gave the fusion its ``op_name``."""
from benchmark import xplane

META = {"source": "device_trace"}


def read(run):
    return xplane.ms_per_step(run, xplane.in_scope("loss_head"))
