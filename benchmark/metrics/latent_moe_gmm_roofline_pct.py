"""The grouped products' share of their roofline in decode steps of a model
whose experts live in a LATENT (``moe_latent_size``) and of which this chip
holds a share: the least time the chip could take for what a step must move
and do (``kernels/latent_moe_gmm.py``: two matrices of latent x width a held
expert hit) over the device time of the operations under ``moe_gmm`` in the
decode program.

Counts and time are of the same traced seconds, as
``moe_gmm_held_roofline_pct`` takes them: the experts that got a token are
the mean ``hit`` of the program's ``moe_counts`` spans inside the trace
(``serving/scheduler.py::_record_moe``, one a decode step, summed over the
expert layers; ``benchmark/host_spans.py``), and the token-expert pairs are
counted at their least, one a hit expert (their rows are under 1% of the
bytes).  Under a program without the span or the scope this reader returns
None.  The bound is memory."""
from benchmark import decode_scopes, host_spans
from benchmark.kernels import latent_moe_gmm
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    found = decode_scopes.seconds_and_steps(run, "moe_gmm")
    if not found:
        return None
    hit = host_spans.mean_field(run.notes["xplane"], "moe_counts", "hit")
    if hit is None:
        return None
    config = run.cell["config_file"]
    peaks = peaks_for(run.device["kind"])
    least_s = max(
        latent_moe_gmm.bytes_per_step(config, hit, hit) / peaks["hbm_bytes_per_s"],
        latent_moe_gmm.flops_per_step(config, hit) / peaks["bf16_flops"],
    )
    return 100.0 * least_s / (found[0] / found[1])
