"""The flash-attention kernels' share of their compute roofline: the
operations causal attention must do in a step (``kernels/flash.py``, from
the configuration's shapes) at the chip's bf16 peak, over the kernels'
device time a step."""
from benchmark import xplane
from benchmark.kernels.flash import causal_flops_per_step
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    ms = xplane.ms_per_step(run, xplane.kernel("flash_"))
    if not ms:
        return None
    flops = causal_flops_per_step(run.cell["config_file"], run.cell["traffic_file"])
    least_ms = flops / run.chips / peaks_for(run.device["kind"])["bf16_flops"] * 1e3
    return 100.0 * least_ms / ms
