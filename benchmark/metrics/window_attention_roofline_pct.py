"""The window layers' attention's share of its roofline in decode steps: the
least time the chip could take to read the positions a window layer KEEPS of
the live rows (``kernels/window_attention.py``: ``min(L, window)`` a row,
4,096 B each at the published widths) over the device time of the operations
under ``window_attention`` in the decode program, whatever implements them.
Both come from the traced seconds: the kept positions are the mean
``window_keys`` of the program's ``decode_step`` spans inside the trace
(``benchmark/host_spans.py``).  Under a program without the scope or the
field, as a parent commit, the reader returns None.  The bound is memory."""
from benchmark import decode_scopes, host_spans
from benchmark.kernels import window_attention
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    found = decode_scopes.seconds_and_steps(run, "window_attention")
    if not found:
        return None
    keys = host_spans.mean_field(run.notes["xplane"], "decode_step", "window_keys")
    if keys is None:
        return None
    least_s = (
        window_attention.window_bytes_per_step(run.cell["config_file"], keys)
        / peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / (found[0] / found[1])
