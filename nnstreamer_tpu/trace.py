"""Tracing / profiling: chrome-trace spans + device profiler integration.

The reference has no in-tree tracer — it leans on GstShark/NNShark/
HawkTracer (tools/tracing/README.md, tools/profiling/README.md; SURVEY.md
§5.1), whose common output is chrome://tracing JSON. This module brings
that capability in-tree:

- ``Tracer``: lock-protected bounded event buffer; ``span()`` context
  manager and ``complete()`` record "X" (complete) events per element/
  frame, ``instant()`` marks points, ``counter()`` tracks gauges (queue
  depths). ``save()`` atomically writes the Chrome Trace Event Format
  JSON that chrome://tracing / Perfetto load directly (the HawkTracer
  workflow, no external daemon).
- Lanes are labeled: each OS thread gets a stable small tid (first-seen
  order, never truncated-ident collisions) and ``to_chrome_trace()``
  emits chrome ``thread_name``/``process_name`` metadata so Perfetto
  shows element/service-thread names instead of bare numbers.
- The buffer is bounded (``max_events``, drop-oldest): soak runs keep a
  sliding window instead of growing without bound;
  ``dropped_events`` counts what the window lost.
- Distributed correlation: a Tracer carries a process label and a
  wall-clock anchor, and :func:`merge` folds several processes' trace
  docs (client + query server) into ONE timeline, shifting each by its
  anchor so cross-host spans line up. Frame identity rides the
  ``frame_id`` meta the edge layer propagates (edge/serialize.py).
- The executor records one span per frame per node when tracing is
  enabled (pipeline/executor.py Node.stat), giving the per-element
  timeline NNShark's per-element CPU/proctime view provides.
- ``device_profile()``: wraps ``jax.profiler.trace`` — the XPlane/
  TensorBoard capture for on-device (TPU) timing, the XLA-world analogue
  of GstShark's proctime tracer.
- ``span()`` / ``instant()`` (module level): the program's own spans on
  the DEVICE trace's clock. Each enters a ``jax.profiler.TraceAnnotation``,
  so while any profiler session runs (``device_profile``, ``nns-launch
  --profile``, the benchmark's ``--trace 1`` window) the span is an event
  on the trace's host plane beside the device planes, its attributes the
  event's stats; with a ``Tracer`` enabled the same call also records the
  chrome "X"/"i" event. With neither, an annotation the profiler ignores
  (under a microsecond). ``SPAN_CATALOG`` lists every ``nns.*`` name.

Enable via ``trace.enable()`` / ``nns-launch --trace out.json``; env knob
``NNS_TRACE`` (path) mirrors the reference's GST_DEBUG_DUMP_DOT_DIR-style
opt-in (nnstreamer_conf env > ini > default priority, SURVEY.md §5.6).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

_lock = threading.Lock()
_tracer: Optional["Tracer"] = None

# drop-oldest window: ~100 MB of JSON at worst, hours of steady-state
# pipeline spans — a soak run records a sliding window, not a leak
DEFAULT_MAX_EVENTS = 500_000


class Tracer:
    def __init__(
        self,
        process: Optional[str] = None,
        max_events: int = DEFAULT_MAX_EVENTS,
        pid: Optional[int] = None,
    ) -> None:
        self._lock = threading.Lock()
        self._max = max(1, int(max_events))
        self._events: deque = deque(maxlen=self._max)
        self.dropped_events = 0
        self._t0 = time.perf_counter()
        # wall-clock anchor paired with the perf_counter epoch: merge()
        # uses the DIFFERENCE of anchors across processes, so absolute
        # wall accuracy only needs to hold to NTP-ish precision
        self._wall_t0 = time.time()
        self._pid = os.getpid() if pid is None else int(pid)
        self.process = process or f"pid{self._pid}"
        # stable small tids: ident → 1,2,3... in first-seen order. The
        # old `get_ident() & 0xFFFF` truncation collided unrelated
        # threads into one Perfetto lane.
        self._tids: Dict[int, int] = {}
        self._tid_names: Dict[int, str] = {}

    # -- recording ---------------------------------------------------------
    def _ts_us(self, t: Optional[float] = None) -> float:
        return ((t if t is not None else time.perf_counter()) - self._t0) * 1e6

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)  # GIL-atomic fast path
        if tid is None:
            with self._lock:
                tid = self._tids.get(ident)
                if tid is None:
                    tid = len(self._tids) + 1
                    self._tids[ident] = tid
                    self._tid_names[tid] = threading.current_thread().name
        return tid

    def set_process(self, name: str) -> None:
        """Label this process's lanes (shows as the Perfetto process
        name; merge() keys the combined timeline on it)."""
        self.process = name

    def _append(self, ev: Dict) -> None:
        with self._lock:
            if len(self._events) >= self._max:
                self.dropped_events += 1
            self._events.append(ev)

    def complete(
        self, name: str, cat: str, t_start: float, dur_s: float, args: Optional[Dict] = None
    ) -> None:
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": self._ts_us(t_start),
            "dur": dur_s * 1e6,
            "pid": self._pid,
            "tid": self._tid(),
        }
        if args:
            ev["args"] = args
        self._append(ev)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "element", **args):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.complete(name, cat, t0, time.perf_counter() - t0, args or None)

    def batch(
        self, name: str, t_start: float, dur_s: float, *, batch: int,
        bucket: int, wait_s: float, **extra
    ) -> None:
        """Batch-assembly span (micro-batching, pipeline/batching.py):
        one "X" event per batched invoke carrying the batch size, the
        padded bucket it dispatched as, the pad waste that padding cost,
        and how long the collector waited for stragglers — the three
        numbers that explain where batched throughput (or latency) went."""
        waste = 100.0 * (bucket - batch) / bucket if bucket else 0.0
        self.complete(
            name, "batch", t_start, dur_s,
            {
                "batch": batch,
                "bucket": bucket,
                "wait_ms": round(wait_s * 1000.0, 3),
                "pad_waste_pct": round(waste, 2),
                **extra,
            },
        )

    def fault(self, name: str, action: str, exc=None, **extra) -> None:
        """Fault-layer event (pipeline/faults.py): one instant marker per
        retry/drop/route/stall so the timeline shows where the error
        policies worked and what they cost."""
        args = {"action": action, **extra}
        if exc is not None:
            args["error"] = type(exc).__name__
        self.instant(name, cat="fault", **args)

    def san(self, name: str, code: str, **extra) -> None:
        """Sanitizer finding (pipeline/sanitize.py): one instant marker
        per NNS-S diagnostic so spec violations, accounting leaks, lock
        cycles and thread leaks land on the same timeline as the frames
        that caused them."""
        self.instant(name, cat="san", code=code, **extra)

    def instant(self, name: str, cat: str = "event", **args) -> None:
        self._append(
            {
                "name": name, "cat": cat, "ph": "i", "s": "t",
                "ts": self._ts_us(), "pid": self._pid,
                "tid": self._tid(),
                "args": args or {},
            }
        )

    def counter(self, name: str, **values: float) -> None:
        self._append(
            {
                "name": name, "cat": "counter", "ph": "C",
                "ts": self._ts_us(), "pid": self._pid, "tid": 0,
                "args": values,
            }
        )

    # -- output ------------------------------------------------------------
    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def _metadata_events(self) -> List[Dict]:
        """Chrome "M" metadata: process_name + one thread_name per lane,
        synthesized at export (not stored) so the recording buffer holds
        only real events and events() stays metadata-free."""
        meta = [{
            "name": "process_name", "ph": "M", "ts": 0, "pid": self._pid,
            "tid": 0, "args": {"name": self.process},
        }]
        with self._lock:
            names = dict(self._tid_names)
        for tid, tname in sorted(names.items()):
            meta.append({
                "name": "thread_name", "ph": "M", "ts": 0,
                "pid": self._pid, "tid": tid, "args": {"name": tname},
            })
        return meta

    def to_chrome_trace(self) -> Dict:
        return {
            "traceEvents": self._metadata_events() + self.events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "process": self.process,
                "pid": self._pid,
                "wall_t0_s": self._wall_t0,
                "dropped_events": self.dropped_events,
            },
        }

    def save(self, path: str) -> None:
        """Atomic write (tmp + rename): a crash mid-dump — or a reader
        polling the file during a soak run — never sees a torn JSON."""
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped_events = 0


def merge(docs: Sequence[Dict]) -> Dict:
    """Fold several processes' chrome-trace docs into ONE timeline.

    Each doc carries its wall-clock anchor (``otherData.wall_t0_s``);
    events shift by the anchor delta against the earliest doc, so a
    client span and the server span it caused line up on one axis
    (client + tensor_query server traces merge into the end-to-end
    view examples/query_offload.py needed). Docs without an anchor
    merge unshifted. Colliding pids (containers, pid reuse) are
    remapped so lanes never interleave across processes.
    """
    anchors = [
        (d.get("otherData") or {}).get("wall_t0_s") for d in docs
    ]
    known = [a for a in anchors if a is not None]
    base = min(known) if known else 0.0
    events: List[Dict] = []
    processes = []
    assigned_pids: set = set()
    for doc, anchor in zip(docs, anchors):
        shift_us = ((anchor - base) * 1e6) if anchor is not None else 0.0
        other = doc.get("otherData") or {}
        if other.get("process"):
            processes.append(other["process"])
        doc_pids = {
            e.get("pid") for e in doc.get("traceEvents", [])
            if e.get("pid") is not None
        }
        remap = {}
        for pid in sorted(doc_pids, key=str):
            new = pid
            while new in assigned_pids:
                new = (new if isinstance(new, int) else 0) + 100_000
            remap[pid] = new
            assigned_pids.add(new)
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = ev["ts"] + shift_us
            if ev.get("pid") in remap:
                ev["pid"] = remap[ev["pid"]]
            events.append(ev)
    events.sort(key=lambda e: (e.get("ts", 0), e.get("ph") != "M"))
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"merged_processes": processes},
    }


def enable() -> Tracer:
    """Install (or return) the global tracer; executor nodes start
    recording as soon as this exists."""
    global _tracer
    with _lock:
        if _tracer is None:
            _tracer = Tracer()
        return _tracer


def disable() -> None:
    global _tracer
    with _lock:
        _tracer = None


_env_checked = False


def get() -> Optional[Tracer]:
    """Active tracer or None (the hot-path check: one global read).
    The ``NNS_TRACE`` env opt-in is resolved on the FIRST miss only —
    this runs per frame per node at multi-kfps, and an environ lookup
    each call is a measurable slice of the executor's frame budget."""
    t = _tracer
    if t is None:
        global _env_checked
        if not _env_checked:
            _env_checked = True
            if os.environ.get("NNS_TRACE"):
                t = enable()
    return t


# -- program spans on the device trace's clock -------------------------------

# name → (layer, the interval or moment it covers, attributes). Held to the
# code and to docs/observability.md by analysis/selfcheck.span_self_check;
# the names are what benchmark/lib/host_spans.py and its readers look for.
SPAN_CATALOG: Dict[str, Tuple[str, str, str]] = {
    "nns.llm.submit": (
        "elements and executor",
        "_LlmServer.submit, whole call on the sink's thread: arrival at the "
        "element to slot claimed, the back-pressure pumps included",
        "prompt_tokens",
    ),
    "nns.llm.admitted": (
        "elements and executor",
        "instant at the end of a submit that claimed a slot",
        "rid, slot_wait_ms (arrival to slot claimed), retries (submits "
        "refused for want of a free slot)",
    ),
    "nns.llm.pump": (
        "batcher",
        "_LlmServer.pump, whole call: one batcher pump and the harvest of "
        "its tokens into the out queue",
        "pending (requests in flight at entry)",
    ),
    "nns.llm.harvest": (
        "elements and executor",
        "in pump, the locked block that streams partials and finished "
        "requests into the out queue",
        "",
    ),
    "nns.llm.emit": (
        "elements and executor",
        "LlmServerSrc.generate: first pop that returns a frame after a pump "
        "to the pop that finds the queue empty; one span per burst, held "
        "open across generate calls, so it covers the executor's push of "
        "every frame downstream",
        "frames (length of the out queue when the burst opens)",
    ),
    "nns.pump": (
        "batcher",
        "ContinuousBatcher.step / step_pump / spec_step / spec_pump, whole "
        "call",
        "n_steps (tokens per slot this launch may emit), active (slots live "
        "at entry), prefill_q (jobs waiting at entry)",
    ),
    "nns.pump.prefill": (
        "batcher",
        "_advance_prefill: the HOST side of chunked prefill (a bucket's "
        "programs run asynchronously; before each further bucket of one "
        "span the host waits for the last one's outputs, so one bucket's "
        "logits and stage are on the device at a time)",
        "prefill_q (jobs waiting at entry), buckets = programs (prompt "
        "programs of any kind this span launched: bucket, chunk, packed), "
        "prompts (jobs whose prefill those programs completed: above "
        "programs where queued prompts shared a packed bucket), activated "
        "(jobs it finalized); all but the first set as it closes",
    ),
    "nns.pump.admit": (
        "batcher",
        "_apply_pending: the gathered read of the queued admissions' first "
        "tokens and their splice into the slot state (one packed transfer, "
        "one launch of jit_nns_admit)",
        "admitted (requests spliced in this span; set as it closes)",
    ),
    "nns.pump.prepare": (
        "batcher",
        "step_pump's locked block that builds the launch's arguments "
        "(decode room, pump state, block tables)",
        "",
    ),
    "nns.pump.launch": (
        "batcher",
        "the call of the jitted decode program (dispatch only)",
        "active (slots live at the launch), live_blocks (paged: arena "
        "blocks its attention reads per step and layer, the sum over "
        "active slots of ceil(fill / block-size); 0 under the slot layout), "
        "state_bytes (resident per-slot state of the live lanes over the "
        "family's slot leaves; 0 for a family with none)",
    ),
    "nns.pump.wait": (
        "batcher",
        "the one [B, n] device-to-host read of the emitted tokens: the "
        "host waits here while the device decodes",
        "",
    ),
    "nns.pump.harvest": (
        "batcher",
        "commit of the carried state and _harvest_rows_locked, to return",
        "",
    ),
    "nns.moe.routing": (
        "expert layer",
        "instant per harvested decode pump of a routed-expert family "
        "(models/longcat.py, models/kimi_linear.py, "
        "models/granite_hybrid.py): the router's counters, "
        "summed on the device over the pump's steps and expert layers and "
        "carried home by the pump's one readback",
        "tokens (live token x layer evaluations), local_pairs (pairs on the "
        "experts held here), experts_hit (distinct held experts with a "
        "token, per layer and step), zero_picks (identity experts chosen; "
        "only where the family has them), picks (tokens x top-k)",
    ),
    "nns.state.update": (
        "KDA layers, SSM layers",
        "instant per harvested decode pump of a family with per-slot "
        "recurrent state (models/kimi_linear.py, models/granite_hybrid.py), "
        "beside nns.moe.routing and from the same readback",
        "slot_layers ((live lane, state layer) updates, summed over the "
        "pump's steps), bytes (state those updates read and wrote: "
        "slot_layers x 2 x one layer's float32 state of one slot, from the "
        "family's own config: heads x d_k x d_v x 4, or heads x d_head x "
        "d_state x 4)",
    ),
    "nns.req.submit": (
        "batcher",
        "instant: SLOLedger.submit, the request has a slot and a record",
        "rid",
    ),
    "nns.req.prefill_start": (
        "batcher",
        "instant: SLOLedger.prefilling, the request reached the head of "
        "the prefill queue (again after a preemption)",
        "rid, queue_ms (submit to here; the first time only)",
    ),
    "nns.req.admitted": (
        "batcher",
        "instant: SLOLedger.admitted, prefill done and the slot active",
        "rid, prefill_ms (prefill start, or submit, to here)",
    ),
    "nns.req.first_token": (
        "batcher",
        "instant: SLOLedger.first_token",
        "rid, ttft_ms",
    ),
    "nns.req.done": (
        "batcher",
        "instant: SLOLedger.finished",
        "rid, tokens, tpot_ms, preemptions",
    ),
}

_annotation = None  # jax.profiler.TraceAnnotation, imported at first use


def _annotate(name: str, attrs: Dict):
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(name, **attrs)


class span:
    """``with trace.span("nns.pump", n_steps=8): ...`` — one interval on
    the profiler's host plane (and in the chrome trace when a ``Tracer``
    is on). Attributes are ints, floats or short strings computed from
    host state only: reading a device array for one would be a sync on
    the hot path. Most are known when the span opens; a count of what the
    span did (``nns.pump.admit``'s ``admitted``) is added with ``set``
    before it closes. A span held open across calls (``nns.llm.emit``)
    uses ``__enter__`` and ``close`` directly, both on the same thread."""

    __slots__ = ("_name", "_attrs", "_ann", "_t0")

    def __init__(self, name: str, **attrs) -> None:
        self._name, self._attrs = name, attrs

    def __enter__(self) -> "span":
        self._ann = _annotate(self._name, self._attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter() if get() is not None else None
        return self

    def set(self, **attrs) -> None:
        """More attributes for the open span, each name once."""
        self._attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def close(self) -> None:
        self._ann.__exit__(None, None, None)
        t = get()
        if t is not None and self._t0 is not None:
            t.complete(self._name, "span", self._t0,
                       time.perf_counter() - self._t0, self._attrs or None)

    def __exit__(self, *exc) -> None:
        self.close()


def instant(name: str, **attrs) -> None:
    """One moment on the same two timelines as :class:`span`."""
    with _annotate(name, attrs):
        pass
    t = get()
    if t is not None:
        t.instant(name, cat="span", **attrs)


@contextlib.contextmanager
def device_profile(logdir: str):
    """On-device (TPU/XLA) profile capture → TensorBoard/XProf logdir.
    The XPlane-level complement to the host-side chrome trace."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
