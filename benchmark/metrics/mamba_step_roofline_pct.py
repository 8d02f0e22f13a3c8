"""The one-step state update's share of its roofline in decode steps: the
least time the chip could take to read and write the float32 state and the
convolution rows of the rows that were LIVE (``kernels/mamba2.py``) over the
device time of the operations under ``mamba_step`` in the decode program.
Both come from the traced seconds: the live rows are the mean ``active`` of
the program's ``decode_step`` spans inside the trace
(``benchmark/host_spans.py``), not the whole run's occupancy, as
``kda_step_roofline_pct`` counts.  Today's step is as wide as the slots and
moves every slot's state whichever rows are live, so at a third of the slots
live the share reads a third of what the memory's rate would give; a step
over the live rows alone could not read above 100.  The convolution rows
are updated under ``mamba_conv``, outside the timed scope: their bytes (0.1%
of the state's) are in the count and their time is not, which lifts the
share by that thousandth.  The bound is memory."""
from benchmark import decode_scopes, host_spans
from benchmark.kernels import mamba2
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    found = decode_scopes.seconds_and_steps(run, "mamba_step")
    if not found:
        return None
    live = host_spans.mean_field(run.notes["xplane"], "decode_step", "active")
    if live is None:
        return None
    least_s = (
        mamba2.state_bytes_per_step(run.cell["config_file"], live)
        / peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (found[0] / found[1])
