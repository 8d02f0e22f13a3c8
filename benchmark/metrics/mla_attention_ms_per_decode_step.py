"""Device time of the latent attention (everything under the scope
``mla_attention``: projections, the pool's scatter and gather, the absorbed
scores and values) inside the decode program, summed over the layers, per
decode step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "mla_attention")
