"""Device time of the chunked delta-rule scan (the operations under
``kda_scan``, all KDA layers) in the prefill programs of the traced seconds,
per 1,000 bucket tokens those programs ran (``benchmark/prefill_scopes.py``:
padding counts, the program computes it)."""
from benchmark import prefill_scopes

META = {"source": "device_trace"}


def read(run):
    found = prefill_scopes.seconds_and_tokens(run, "kda_scan")
    return found[0] * 1e3 / (found[1] / 1e3) if found else None
