"""Device time of the Gated DeltaNet layers (everything under the scope
``gdn``: the projections and convolutions, the decay, the update's rate and
the output gate, the one-step state update, the output norm, gate and
projection) inside the decode program, summed over the layers, per decode
step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "gdn")
