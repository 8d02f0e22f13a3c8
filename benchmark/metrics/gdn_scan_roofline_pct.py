"""The chunked scan's share of the RECURRENCE's roofline in prefill: the
least time the chip could take for the recurrence's own work on the bucket
tokens the traced prefill programs ran (``kernels/gated_delta.py``: 6 d_k d_v
operations a token a head; the rows of q, k, v, the decay and the output)
over the device time under ``gdn_scan``.  Whatever implements the scan is
judged by the same count (``kernels/kda.py``'s, so that the two scans can be
laid side by side), so the share cannot pass 100%; a chunked form in plain
XLA does several times the operations and reads low."""
from benchmark import prefill_scopes
from benchmark.kernels import gated_delta
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    found = prefill_scopes.seconds_and_tokens(run, "gdn_scan")
    if not found:
        return None
    config = run.cell["config_file"]
    peaks = peaks_for(run.device["kind"])
    least_s = found[1] * max(
        gated_delta.scan_flops_per_token(config) / peaks["bf16_flops"],
        gated_delta.scan_bytes_per_token(config) / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / found[0]
