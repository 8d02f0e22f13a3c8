"""Model FLOP/s utilization: the run's rate of samples times the operations
one sample requires (forward and backward, recomputed operations not
counted; the function the configuration names under ``benchmark/kernels``)
over chips times the bf16 peak of the device kind."""
from benchmark.common import load_module
from benchmark.kernels.peaks import peaks_for

META = {"source": "host_clock"}


def read(run):
    w = run.window
    if not w or run.device.get("platform") != "tpu":
        return None
    config = run.cell["config_file"]
    flops = load_module("kernels", config["flops_per_sample"]).flops_per_sample(
        config, run.cell["traffic_file"]
    )
    rate = w["steps"] * run.samples_per_step / w["seconds"]
    peak = peaks_for(run.device["kind"])["bf16_flops"] * run.chips
    return 100.0 * rate * flops / peak
