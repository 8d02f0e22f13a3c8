"""95th percentile of all gaps between consecutive tokens of one request,
pooled over the requests due in the window, on the client's side."""
from benchmark import loadgen

META = {"source": "host_clock"}


def read(run):
    if not run.serve:
        return None
    return loadgen.percentile(loadgen.gaps_ms(run.serve["records"]), 95)
