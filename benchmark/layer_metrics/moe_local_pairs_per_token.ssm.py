"""(token, expert) pairs that fell on the experts held here, per token and
layer, over the decode steps of the traced window (the ``nns.moe.routing``
instants), for the Granite-4.0-H configuration. 10 picks x 36 of 72 outputs =
5.0 expected."""
from benchmark.lib import shapes_granite_hybrid as sg


def read(ctx):
    c = sg.counters(ctx) if sg.shape_of(ctx["sizes"]) else None
    return c["local_pairs"] / c["tokens"] if c and c["tokens"] else None
