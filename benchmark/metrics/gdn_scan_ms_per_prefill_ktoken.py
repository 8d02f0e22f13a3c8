"""Device time of the chunked scalar-decay delta-rule scan (the operations
under ``gdn_scan``, all linear layers) in the prefill programs of the traced
seconds, per 1,000 bucket tokens those programs ran
(``benchmark/prefill_scopes.py``: padding counts, the program computes it)."""
from benchmark import prefill_scopes

META = {"source": "device_trace"}


def read(run):
    found = prefill_scopes.seconds_and_tokens(run, "gdn_scan")
    return found[0] * 1e3 / (found[1] / 1e3) if found else None
