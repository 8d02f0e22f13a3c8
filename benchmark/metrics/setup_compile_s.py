"""Seconds JAX spent tracing, lowering and compiling (or reading back from
the persistent cache) before the window, from its monitoring events."""
META = {"source": "program_counter"}


def read(run):
    return (run.compile_before or {}).get("compile_s")
