"""The fused cross-entropy kernels' share of their memory roofline: the
bytes the two kernels must move in a step (``kernels/fused_ce.py``, from the
configuration's shapes) at the chip's HBM peak, over the kernels' device
time a step."""
from benchmark import xplane
from benchmark.kernels.fused_ce import bytes_per_step
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    ms = xplane.ms_per_step(run, xplane.kernel("fused_ce_"))
    if not ms:
        return None
    moved = bytes_per_step(run.cell["config_file"], run.cell["traffic_file"])
    least_ms = moved / run.chips / peaks_for(run.device["kind"])["hbm_bytes_per_s"] * 1e3
    return 100.0 * least_ms / ms
