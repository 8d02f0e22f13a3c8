"""How late after its due time the load generator really submitted a
request, 95th percentile: a starved generator must not read as a fast
server."""
from benchmark import loadgen

META = {"source": "host_clock"}


def read(run):
    if not run.serve:
        return None
    return loadgen.percentile(loadgen.lag_ms(run.serve["records"]), 95)
