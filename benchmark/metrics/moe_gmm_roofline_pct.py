"""The grouped products' share of their roofline in decode steps: the least
time the chip could take for what a step must move and do
(``kernels/moe_gmm.py``: the weights of the experts that got a token, as the
program counted them, and the rows of the token-expert pairs) over the
device time of the operations under ``moe_gmm`` in the decode program.

The counts are the run's own: ``moe_experts_hit_mean`` and the mean share of
slots live (``slot_occupancy_mean``) of ``ServingMetrics.snapshot()``, over
all productive ticks of the run, of which the traced seconds are the last.
The bound is memory at this cell's 32 rows a step."""
from benchmark import decode_scopes
from benchmark.kernels import moe_gmm
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    found = decode_scopes.seconds_and_steps(run, "moe_gmm")
    snapshot = (run.serve or {}).get("snapshot", {})
    hit = snapshot.get("moe_experts_hit_mean")
    live = snapshot.get("slot_occupancy_mean")
    if not found or hit is None or live is None:
        return None
    config = run.cell["config_file"]
    slots = int(config["serve"]["serving"]["scheduler"]["slots"])
    pairs = live * slots * int(config["num_experts_per_tok"])
    peaks = peaks_for(run.device["kind"])
    least_s = max(
        moe_gmm.bytes_per_step(config, hit, pairs) / peaks["hbm_bytes_per_s"],
        moe_gmm.flops_per_step(config, pairs) / peaks["bf16_flops"],
    )
    return 100.0 * least_s / (found[0] / found[1])
