"""The largest ``gap_ms`` of the traced window's ``nns.pump.harvest`` spans: the
longest a decoding slot waited from one harvest of its tokens to the next. In
a closed-loop cell, one decode launch plus the prompt programs a pump runs
before it. None where no harvest carries ``gap_ms``."""
from benchmark.lib import host_stalls


def read(ctx):
    gaps = host_stalls.harvest_gaps(ctx)
    return max((b - a) * 1e-6 for a, b in gaps) if gaps else None
