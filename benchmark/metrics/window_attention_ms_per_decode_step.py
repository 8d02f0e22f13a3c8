"""Device time of the window layers' attention proper (everything under the
scope ``window_attention``: the write of the step's row into the slot's ring,
scores over the kept positions, softmax and weighted sum; not the
projections, the rotary term or the gate) inside the decode program, per
decode step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "window_attention")
