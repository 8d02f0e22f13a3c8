"""How uneven a decode step's routing was: the largest count of tokens at
one expert over the mean count of an expert (live rows x experts a token /
experts), a layer; the median over the productive ticks, from the program's
own ``ServingMetrics.snapshot()``."""
META = {"source": "program_counter"}


def read(run):
    return (run.serve or {}).get("snapshot", {}).get("moe_load_max_over_mean_p50")
