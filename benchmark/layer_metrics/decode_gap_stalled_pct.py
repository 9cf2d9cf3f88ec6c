"""Among the traced window's decode gaps (``gap_ms`` of ``nns.pump.harvest``)
longer than 1.5 x their median: the part of their excess over the median that
``nns.host.stall`` intervals of kind ``machine`` cover, / that excess. Near 100
where the long gaps are the machine's freezes, 0 where they are the program's
own (a prompt program between two pumps), and 0 where no gap is long: none of
the tail is the machine's (the count of long gaps is logged). None where no
harvest carries ``gap_ms`` (a program without it)."""
from benchmark.lib import host_stalls


def read(ctx):
    return host_stalls.stalled_share_of_long_gaps(
        host_stalls.harvest_gaps(ctx), host_stalls.stall_intervals(ctx))
