"""The chunked scan's share of the RECURRENCE's roofline in prefill: the
least time the chip could take for the recurrence's own work on the bucket
tokens the traced prefill programs ran (``kernels/mamba2.py``: 5 x head_dim
x state_size operations a token a head; the rows of x, y, dt, B and C) over
the device time under ``mamba_scan``.  Whatever implements the scan is
judged by the same count, so the share cannot pass 100%; the chunked form
does other operations than these (products over a chunk's positions) and
forms a chunk's decays on the vector unit, and reads lower."""
from benchmark import prefill_scopes
from benchmark.kernels import mamba2
from benchmark.kernels.peaks import peaks_for

META = {"source": "device_trace"}


def read(run):
    found = prefill_scopes.seconds_and_tokens(run, "mamba_scan")
    if not found:
        return None
    config = run.cell["config_file"]
    peaks = peaks_for(run.device["kind"])
    least_s = found[1] * max(
        mamba2.scan_flops_per_token(config) / peaks["bf16_flops"],
        mamba2.scan_bytes_per_token(config) / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / found[0]
