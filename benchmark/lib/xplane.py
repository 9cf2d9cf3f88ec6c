"""Reduction from a profiler trace (``.xplane.pb``) to the numbers the
per-layer readers use: the busy union of device events, device time and
launch count per XLA module, device time per XLA op, and the idle gaps.

Read with nothing but jax (``jax.profiler.ProfileData``). The program
carries no ``named_scope`` or ``TraceAnnotation``, so the only names here
are the ones XLA gives: modules are ``jit_<function>(<program id>)`` on a
device plane's "XLA Modules" line, ops sit on its "XLA Ops" line. The
traced window is bounded by two ``TraceAnnotation`` marks the benchmark
itself writes (``MARK_BEGIN``/``MARK_END``) on the host plane, which shares
the device planes' clock.

``python benchmark/checks/check_xplane.py`` checks this module against the
small recorded trace kept beside it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

MARK_BEGIN = "bench_window_begin"
MARK_END = "bench_window_end"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals (any unit)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """The idle gaps of [lo, hi) not covered by the intervals."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _marks(data) -> Tuple[Optional[float], Optional[float]]:
    lo = hi = None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARK_BEGIN:
                    lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                elif ev.name == MARK_END:
                    hi = ev.start_ns if hi is None else max(hi, ev.start_ns)
    return lo, hi


def _host_xla_events(data):
    """CPU rehearsals only: the CPU client's op events stand in for a device
    plane, grouped into modules by their ``hlo_module`` stat."""
    mods, ops = [], []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_XLAPjRtCpuClient"):
                continue
            for e in line.events:
                st = dict(e.stats)
                if "hlo_module" in st:
                    row = (e.start_ns, e.start_ns + e.duration_ns)
                    ops.append((e.name,) + row)
                    mods.append((st["hlo_module"],) + row)
    return mods, ops


def reduce_trace(path: str, allow_host: bool = False) -> dict:
    """-> {"window_s", "window_ns": (lo, hi), "marks_found", "devices": n,
    "busy_s" (mean over devices), "modules": {name: [seconds, launches]}
    (a launch cut by the window's edge counts as the share inside),
    "ops": {name: seconds}, "busy_intervals_ns": [...] of device 0,
    "lines": {plane: [line names]}}. Module and op seconds are summed over
    devices and divided by the device count."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lo, hi = _marks(data)
    marks_found = lo is not None and hi is not None and hi > lo
    per_device = []
    lines_seen: Dict[str, List[str]] = {}
    for plane in data.planes:
        if not _DEVICE.match(plane.name):
            continue
        mods, ops = [], []
        names = []
        for line in plane.lines:
            names.append(line.name)
            if line.name == "XLA Modules":
                mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            elif line.name == "XLA Ops":
                ops = [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
        lines_seen[plane.name] = names
        per_device.append((mods, ops))
    if not per_device and allow_host:
        per_device = [_host_xla_events(data)]
        lines_seen["/host:CPU"] = ["tf_XLAPjRtCpuClient/*"]
    if not per_device:
        raise ValueError(f"{path}: no /device:TPU:<n> plane in the trace")
    if not marks_found:
        every = [t for mods, ops in per_device for ev in (mods + ops)
                 for t in ev[1:]]
        if not every:
            raise ValueError(f"{path}: no device event in the trace")
        lo, hi = min(every), max(every)

    def clip(evs):
        """Events cut to the window, each with the share of it that is
        inside: a launch cut by the window's edge counts as that share of a
        launch, so seconds / launches stays the mean length of a whole one."""
        return [(n, max(s, lo), min(e, hi),
                 (min(e, hi) - max(s, lo)) / (e - s) if e > s else 1.0)
                for n, s, e in evs if e > lo and s < hi]

    n_dev = len(per_device)
    modules: Dict[str, List[float]] = {}
    op_s: Dict[str, float] = {}
    busy = 0.0
    busy0: List[Tuple[float, float]] = []
    for i, (mods, ops) in enumerate(per_device):
        mods, ops = clip(mods), clip(ops)
        for n, s, e, share in mods:
            row = modules.setdefault(n, [0.0, 0])
            row[0] += (e - s) * 1e-9 / n_dev
            row[1] += share / n_dev
        for n, s, e, _ in ops:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9 / n_dev
        iv = [(s, e) for _, s, e, _ in (ops or mods)]
        busy += union_seconds(iv) * 1e-9 / n_dev
        if i == 0:
            busy0 = iv
    return {
        "window_s": (hi - lo) * 1e-9, "window_ns": (lo, hi),
        "marks_found": marks_found, "devices": n_dev, "busy_s": busy,
        "modules": modules, "ops": op_s, "busy_intervals_ns": busy0,
        "lines": lines_seen,
    }


def op_name(text: str) -> str:
    """A TPU op event carries its whole HLO line, ``%while.50 = (...) ...``:
    keep the instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%")[:64]


def module_kind(name: str) -> str:
    """Strip the program id: ``jit_impl(123)`` -> ``jit_impl``."""
    return _MODULE_ID.sub("", name)


def top(rows: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(rows.items(), key=lambda kv: -kv[1])[:n]]
