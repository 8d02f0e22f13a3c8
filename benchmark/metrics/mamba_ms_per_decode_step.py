"""Device time of the Mamba-2 layers (everything under the scope ``mamba``:
the input projection, the convolution, the one-step state update, the gated
norm and the output projection) inside the decode program, summed over the
layers, per decode step."""
from benchmark import decode_scopes

META = {"source": "device_trace"}


def read(run):
    return decode_scopes.ms_per_decode_step(run, "mamba")
