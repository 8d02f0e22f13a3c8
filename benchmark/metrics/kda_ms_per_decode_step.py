"""Device time of the KDA layers (everything under the scope ``kda``: the
projections and convolutions, the gates, the one-step state update, the
output norm, gate and projection; and the gather and scatter of the slots'
state around them) inside the decode program, summed over the layers, per
decode step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "kda")
