"""The program's own spans in the traced window, on the device trace's clock.

The program (nnstreamer_tpu/trace.py: ``trace.span`` / ``trace.instant``)
writes ``nns.*`` events onto the profiler's host plane, one line per thread,
with its attributes as the event's stats. This module finds the run's trace,
reads those events clipped to the window between the benchmark's two marks,
and says how much of the device's idle time each kind of span covers.

``ctx`` carries neither the cell nor the trace's directory, so the trace is
the newest under ``chiprun_out/bench/*/trace`` and is REFUSED unless its two
marks are the ones the driver reduced (``ctx["trace"]["window_ns"]``): a stale
directory must not lend its spans to another run. A program without spans
(the parent commit of the PR that added them) gives no events, and every
reader then returns None: the metric is left out of the line.

``python benchmark/checks/check_host_spans.py`` checks this module.
"""

from __future__ import annotations

import bisect
import glob
import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from benchmark.lib import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The pump side, outermost first. Every name and not the outermost alone: a span
# that was open when the profiler started, or still open when it stopped, is not
# recorded, but the phases it held inside the window are.
PUMP = ("nns.llm.pump", "nns.llm.harvest", "nns.pump", "nns.pump.prefill",
        "nns.pump.admit", "nns.pump.prepare", "nns.pump.launch", "nns.pump.wait",
        "nns.pump.harvest")
EMIT = ("nns.llm.emit",)
PREFIX = "nns."
PREFILL_MODULES = "jit_nns_prefill"

Interval = Tuple[float, float]
_parsed: Dict[Tuple[float, float], Optional[dict]] = {}


def newest_trace(root: str = ROOT) -> Optional[str]:
    """The newest ``.xplane.pb`` any cell's run left under ``root``."""
    files = glob.glob(os.path.join(
        root, "chiprun_out", "bench", "*", "trace", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def parse(path: str, window_ns: Interval) -> Optional[dict]:
    """-> {"events": [{"name", "line", "start_ns", "end_ns", "stats"}] of every
    ``nns.*`` event of every host line, clipped to the window (an instant has
    start == end), "launches": [(module name, start_ns, end_ns)] of device 0's
    "XLA Modules" line inside the window}, or None if the file's two marks are
    not ``window_ns``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    if xplane._marks(data) != tuple(window_ns):
        return None
    lo, hi = window_ns
    events: List[dict] = []
    launches: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if not ev.name.startswith(PREFIX):
                        continue
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e < lo or s > hi:
                        continue
                    events.append({
                        "name": ev.name, "line": f"{plane.name}/{i}:{line.name}",
                        "start_ns": max(s, lo), "end_ns": min(e, hi),
                        "stats": dict(ev.stats)})
        elif plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    launches = [(xplane.module_kind(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events
                                if e.start_ns >= lo and e.start_ns < hi]
    return {"events": events, "launches": launches}


def load(ctx: dict) -> Optional[dict]:
    """This run's parsed spans (cached for the other readers), or None where
    there is no trace of this window to read."""
    window = tuple(ctx["trace"]["window_ns"])
    if window not in _parsed:
        path = newest_trace()
        got = parse(path, window) if path else None
        if path and got is None:
            print(f"[host_spans] {path}: its marks are not this run's window "
                  f"{window}; refused", file=sys.stderr, flush=True)
        _parsed[window] = got
    return _parsed[window]


def spans(ctx: dict, names: Iterable[str]) -> List[dict]:
    got = load(ctx)
    names = set(names)
    return [e for e in got["events"] if e["name"] in names] if got else []


def mean_stat(ctx: dict, name: str, stat: str) -> Tuple[Optional[float], int]:
    """Mean of one attribute over the window's events of one name, and the
    count it is a mean of (None, 0 where no event carries it)."""
    xs = [e["stats"][stat] for e in spans(ctx, (name,)) if stat in e["stats"]]
    return (sum(xs) / len(xs) if xs else None), len(xs)


def _segments(events: List[dict], label_of) -> List[Tuple[float, float, str]]:
    """Cut time at every start and end of ``events`` and label each piece by
    ``label_of(the events that cover it)``; pieces it labels None are dropped.
    -> disjoint (start, end, label), sorted."""
    cuts = sorted({t for e in events for t in (e["start_ns"], e["end_ns"])})
    out = []
    for s, t in zip(cuts, cuts[1:]):
        mid = (s + t) / 2
        label = label_of([e for e in events if e["start_ns"] <= mid < e["end_ns"]])
        if label is not None:
            out.append((s, t, label))
    return out


def _idle_by_label(gaps: List[Interval], segs) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` under each label of the disjoint ``segs``: one
    bisection a gap (a trace holds a gap between any two device ops)."""
    starts = [s for s, _, _ in segs]
    out: Dict[str, float] = {}
    for a, b in gaps:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            s, t, label = segs[i]
            if t > a:
                out[label] = out.get(label, 0.0) + min(t, b) - max(s, a)
            i += 1
    return out


def overlap_seconds(gaps: List[Interval], cover: List[Interval],
                    minus: List[Interval] = ()) -> float:
    """Seconds (of ns intervals) of ``gaps`` that ``cover`` covers and
    ``minus`` does not. Overlapping intervals of ``cover`` count once."""
    events = [{"start_ns": s, "end_ns": e, "name": n}
              for n, ivs in (("cover", cover), ("minus", minus)) for s, e in ivs]

    def label(covering):
        names = {e["name"] for e in covering}
        return "in" if names == {"cover"} else None

    return _idle_by_label(gaps, _segments(events, label)).get("in", 0.0) * 1e-9


def _idle_gaps(ctx: dict) -> List[Interval]:
    got = load(ctx)
    if "gaps" not in got:
        lo, hi = ctx["trace"]["window_ns"]
        got["gaps"] = xplane.gaps(ctx["trace"]["busy_intervals_ns"], lo, hi)
    return got["gaps"]


def idle_overlap(ctx: dict, names: Iterable[str],
                 minus: Iterable[str] = ()) -> Optional[float]:
    """Seconds of the device's idle gaps in the window that spans named
    ``names`` cover and spans named ``minus`` do not: where spans of two
    threads overlap, the pump side (``PUMP``) goes first, then ``EMIT``
    (``idle_overlap(ctx, EMIT, minus=PUMP)``), and what neither covers is
    unspanned. None where the trace holds no program span at all."""
    got = load(ctx)
    if not got or not got["events"]:
        return None
    iv = lambda ns: [(e["start_ns"], e["end_ns"]) for e in spans(ctx, ns)]  # noqa: E731
    return overlap_seconds(_idle_gaps(ctx), iv(names), iv(minus))


def idle_by_innermost(ctx: dict) -> Optional[Dict[str, float]]:
    """The idle gaps split by the innermost span that covers each instant:
    among the spans of the pump side (``PUMP``) the one opened last, else
    ``nns.llm.emit``, else ``unspanned``. The full table behind the three
    shares, in seconds."""
    got = load(ctx)
    if not got or not got["events"]:
        return None
    events = [e for e in spans(ctx, PUMP + EMIT) if e["end_ns"] > e["start_ns"]]

    def innermost(covering):
        inner = [e for e in covering if e["name"] not in EMIT]
        if inner:
            return max(inner, key=lambda e: e["start_ns"])["name"]
        return EMIT[0] if covering else None

    gaps = _idle_gaps(ctx)
    out = {k: v * 1e-9 for k, v in
           _idle_by_label(gaps, _segments(events, innermost)).items()}
    out["unspanned"] = sum(b - a for a, b in gaps) * 1e-9 - sum(out.values())
    return out


def decode_launches(ctx: dict) -> List[Tuple[int, float]]:
    """(slots live, device milliseconds) of every decode launch that lies
    whole in the window: each ``nns.pump.launch`` span paired with the next
    decode module to start on the device after it opened."""
    got = load(ctx)
    if not got:
        return []
    names = set(ctx["trace_names"].get("decode", ()))
    lo, hi = ctx["trace"]["window_ns"]
    dev = sorted((s, e) for n, s, e in got["launches"] if n in names and e <= hi)
    out = []
    for sp in sorted(spans(ctx, ("nns.pump.launch",)), key=lambda e: e["start_ns"]):
        nxt = next(((s, e) for s, e in dev if s >= sp["start_ns"]), None)
        if nxt and "active" in sp["stats"]:
            out.append((int(sp["stats"]["active"]), (nxt[1] - nxt[0]) * 1e-6))
            dev.remove(nxt)
    return out


def log(msg: str) -> None:
    print(f"[host_spans] {msg}", file=sys.stderr, flush=True)
