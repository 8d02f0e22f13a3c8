"""The grouped products' share of their roofline in decode steps of a model
that holds a SHARE of each layer's experts (``experts_held``): the least
time the chip could take for what a step must move and do
(``kernels/moe_gmm.py``) over the device time of the operations under
``moe_gmm`` in the decode program.

Counts and time are of the same traced seconds.  The experts that got a
token are the program's own count among the experts it HOLDS, summed over
the expert layers: the mean ``hit`` of its ``moe_counts`` spans inside the
trace (``serving/scheduler.py::_record_moe``, one a decode step;
``benchmark/host_spans.py``).  The token-expert pairs are counted at their
least: one a hit expert (a share computes the pairs that fall on its experts,
not rows x experts a token; their rows are under 1% of the bytes).
``moe_gmm_roofline_pct`` takes both counts from the whole run's
``snapshot()`` and read 101-115% in a cell whose traced seconds were emptier
than its run; under a program without the span this reader returns None.
The bound is memory."""
from benchmark import decode_scopes, host_spans
from benchmark.kernels import moe_gmm
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
    pairs = hit / moe_gmm.expert_layers(config)
    peaks = peaks_for(run.device["kind"])
    least_s = max(
        moe_gmm.bytes_per_step(config, hit, pairs) / peaks["hbm_bytes_per_s"],
        moe_gmm.flops_per_step(config, pairs) / peaks["bf16_flops"],
    )
    return 100.0 * least_s / (found[0] / found[1])
