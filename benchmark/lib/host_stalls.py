"""The host's stalls and the gaps a decoding slot waits, in the traced window.

The program's stall sentinel (nnstreamer_tpu/trace.py ``StallSentinel``)
writes the instant ``nns.host.stall`` when it wakes ``late_ms`` late: the
stall is [event - late_ms, event] on the trace's clock, ``kind`` ``machine``
where nothing of the process ran over it. ``nns.host.watch`` is its
heartbeat, once a second. ``nns.pump.harvest`` carries ``gap_ms``, the time
since the previous harvest that left a slot decoding: the gap is
[start - gap_ms, start]. Events come from ``benchmark/lib/host_spans.py``; a
program without them (the parent of the change that added them) gives none,
and every reader then returns None.
"""

from __future__ import annotations

import statistics
from typing import List, Optional

from benchmark.lib import host_spans
from benchmark.lib.host_spans import Interval

STALL, WATCH, HARVEST = "nns.host.stall", "nns.host.watch", "nns.pump.harvest"


def watched(ctx: dict) -> bool:
    """Whether the sentinel ran in the window (a heartbeat lies in it)."""
    return bool(host_spans.spans(ctx, (WATCH,)))


def stall_intervals(ctx: dict, kind: str = "machine") -> List[Interval]:
    """(start_ns, end_ns) of every ``nns.host.stall`` of ``kind`` in the window."""
    return [(e["end_ns"] - e["stats"]["late_ms"] * 1e6, e["end_ns"])
            for e in host_spans.spans(ctx, (STALL,))
            if e["stats"].get("kind") == kind and "late_ms" in e["stats"]]


def harvest_gaps(ctx: dict) -> List[Interval]:
    """(start_ns, end_ns) of the gap before every ``nns.pump.harvest`` in the
    window that carries ``gap_ms``, in time order."""
    return sorted((e["start_ns"] - e["stats"]["gap_ms"] * 1e6, e["start_ns"])
                  for e in host_spans.spans(ctx, (HARVEST,))
                  if "gap_ms" in e["stats"])


def stalled_share_of_long_gaps(gaps: List[Interval], stalls: List[Interval],
                               long_over: float = 1.5) -> Optional[float]:
    """Among the gaps longer than ``long_over`` x their median: the part of
    their excess over the median that ``stalls`` cover (at most the excess,
    a gap at a time), over that excess, in %; 0 where no gap is long. None
    where there are no gaps."""
    if not gaps:
        return None
    med = statistics.median(b - a for a, b in gaps)
    long = [(a, b) for a, b in gaps if b - a > long_over * med]
    excess = sum(b - a - med for a, b in long)
    covered = sum(min(host_spans.overlap_seconds([g], stalls) * 1e9, g[1] - g[0] - med)
                  for g in long)
    host_spans.log(f"decode gaps: {len(gaps)}, median {med * 1e-6:.3f} ms, "
                   f"{len(long)} longer than {long_over} x the median, excess "
                   f"{excess * 1e-6:.3f} ms, of it under machine stalls "
                   f"{covered * 1e-6:.3f} ms")
    return 100.0 * covered / excess if long else 0.0
