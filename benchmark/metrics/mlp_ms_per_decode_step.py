"""Device time of the dense feed-forward layers (everything under the scope
``mlp``: the gate and up projections in one product, ``silu(gate) * up``,
the down projection) inside the decode program, summed over the layers, per
decode step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "mlp")
