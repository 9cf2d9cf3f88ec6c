"""``nns_llm_setup_seconds{phase="first_token"}``: batcher built to the first
token of any request: program builds or cache reads, first prefill and pump."""
from benchmark.lib import setup_gauges


def read(ctx):
    return setup_gauges.read("first_token")
