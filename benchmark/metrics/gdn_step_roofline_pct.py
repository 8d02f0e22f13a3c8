"""The one-step state update's share of its roofline in decode steps: the
least time the chip could take to read and write the float32 state of the
rows that were LIVE (``kernels/gated_delta.py``) over the device time of the
operations under ``gdn_step`` in the decode program.  Both come from the
traced seconds: the live rows are the mean ``active`` of the program's
``decode_step`` spans inside the trace (``benchmark/host_spans.py``), not the
whole run's occupancy.  Up to half the slots live the step walks the live
rows; past it, it takes one pass over ALL slots (``ops/state_rows.py``) and
reads lower by the dead rows' share.  The bytes are the state's logical
ones and the device lays a row out a third larger (192 lanes padded to
256), so the share stands under 100 whatever the step does.  The bound is
memory."""
from benchmark import decode_scopes, host_spans
from benchmark.kernels import gated_delta
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    found = decode_scopes.seconds_and_steps(run, "gdn_step")
    if not found:
        return None
    live = host_spans.mean_field(run.notes["xplane"], "decode_step", "active")
    if live is None:
        return None
    least_s = (
        gated_delta.state_bytes_per_step(run.cell["config_file"], live)
        / peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (found[0] / found[1])
