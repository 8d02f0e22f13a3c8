"""Device time of the grouped-query attention layer (everything under the
scope ``gqa_attention``: projections, the pool's scatter and block-table
gather, scores over groups of query heads, the output gate and projection)
inside the decode program, per decode step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "gqa_attention")
