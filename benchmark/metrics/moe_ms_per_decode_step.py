"""Device time of the expert layers (everything under the scope ``moe``:
router, sort, the grouped products, the shared expert, the combine) inside
the decode program, summed over the layers, per decode step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "moe")
