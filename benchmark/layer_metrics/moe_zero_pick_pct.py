"""Share of the router's picks that chose an identity (zero-compute) expert
over the decode steps of the traced window: 256 of 768 outputs, so about a
third under random weights."""
from benchmark.lib import shapes_longcat as sl


def read(ctx):
    r = sl.routing(ctx)
    return 100.0 * r["zero_picks"] / r["picks"] if r and r["picks"] else None
