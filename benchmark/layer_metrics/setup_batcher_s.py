"""``nns_llm_setup_seconds{phase="batcher"}``: the ContinuousBatcher built
(arena, tables, the jitted programs wrapped; none compiled yet)."""
from benchmark.lib import setup_gauges


def read(ctx):
    return setup_gauges.read("batcher")
