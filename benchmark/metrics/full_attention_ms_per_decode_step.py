"""Device time of the full layers' attention proper (everything under the
scope ``full_attention``: the pool's scatter, the paged kernel's walk of each
row's block table, scores, softmax and weighted sum; not the projections,
the rotary term or the gate) inside the decode program, per decode step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "full_attention")
