"""The chunked scan's share of the RECURRENCE's roofline in prefill: the
least time the chip could take for the recurrence's own work on the bucket
tokens the traced prefill programs ran (``kernels/kda.py``: 6 d_k d_v
operations a token a head; the rows of q, k, v, decay and output) over the
device time under ``kda_scan``.  Whatever implements the scan is judged by
the same count, so the share cannot pass 100%; a chunked form in plain XLA
does several times the operations and reads low."""
from benchmark import prefill_scopes
from benchmark.kernels import kda
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    found = prefill_scopes.seconds_and_tokens(run, "kda_scan")
    if not found:
        return None
    config = run.cell["config_file"]
    peaks = peaks_for(run.device["kind"])
    least_s = found[1] * max(
        kda.scan_flops_per_token(config) / peaks["bf16_flops"],
        kda.scan_bytes_per_token(config) / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / found[0]
