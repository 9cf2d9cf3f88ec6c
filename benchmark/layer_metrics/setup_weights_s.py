"""``nns_llm_setup_seconds{phase="weights"}``: the zoo model opened, its
weights drawn from the seed."""
from benchmark.lib import setup_gauges


def read(ctx):
    return setup_gauges.read("weights")
