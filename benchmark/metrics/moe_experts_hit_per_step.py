"""Experts of a layer that received a token in a decode step, of the
layer's ``n_routed_experts``: the mean of the program's own count
(``ServingMetrics``: ``moe_experts_hit``, summed over the expert layers, one
observation a productive tick) over the number of expert layers."""
from benchmark.kernels import moe_gmm

META = {"source": "program_counter"}


def read(run):
    hit = (run.serve or {}).get("snapshot", {}).get("moe_experts_hit_mean")
    if hit is None:
        return None
    return hit / moe_gmm.expert_layers(run.cell["config_file"])
