"""Chunked-prefill admission, watermark accounting, per-request SLOs.

Three serving-scheduler concerns the paged batcher delegates here:

- **Chunked prefill** (:class:`PrefillJob`): a paged submit() never
  prefill-stalls the decode plane. The prompt becomes a job; each
  step/pump spends a budget of ``prompt_len`` buckets on the queue,
  front job first, before decoding. ``prefill_chunks=0`` (the default)
  reads the budget off the queue, max(1, jobs waiting at the pump's
  start): every waiting request is admitted in the pump that finds it,
  a long prompt alone still advances ONE bucket a pump (pinned by
  tests/test_kv_paged.py), and a decoding request's wait a pump is
  bounded by the buckets of the jobs queued, not by one bucket.
  ``prefill_chunks=N`` caps a pump at N buckets: the operator's bound
  on the largest stall.
- **Watermark admission + preemption-by-eviction**: a finished prefill
  only activates when the pool can cover its blocks AND one decode-
  growth block per live request (the watermark) — otherwise it waits,
  so admission can never thrash the decode plane. Decode growth itself
  preempts the youngest other request on exhaustion
  (:func:`choose_victim`): its blocks are freed (shared prefix blocks
  survive in the pool's cached tier) and it re-enters the prefill queue
  to be re-prefilled from whatever prefix still matches — never an OOM.
- **SLO ledger** (:class:`SLOLedger`): per-request queue / prefill /
  TTFT / TPOT wall stamps, surfaced through ``nns-top --requests``, the
  ``nns_request_queue_ms`` / ``nns_request_ttft_ms`` /
  ``nns_request_tpot_ms`` histograms, and one ``nns.req.*`` instant per
  state change on the profiler's timeline (nnstreamer_tpu/trace.py).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from nnstreamer_tpu import trace


@dataclass
class PrefillJob:
    """One admission working its way through chunked prefill.

    ``tokens`` is the FULL known context (prefix + prompt for a fresh
    request; prompt + already-generated tokens for a preempted one being
    re-prefilled — ``known_first`` then carries the pending token, so no
    re-sampling happens and the resumed stream is exactly the original).
    ``base`` is the first position not yet covered (matched prefix
    tokens start it past 0); ``cpos`` tracks chunking progress."""

    slot: int
    req: Any  # models/serving._Request
    tokens: Any  # np.ndarray int32 — full context to (re)prefill
    known_first: Optional[int] = None
    base: int = 0                 # positions < base came from the match
    cpos: int = 0                 # positions < base+cpos are staged
    stage: Any = None             # (ks, vs) staging cache, lazily built
    logits_row: Any = None        # final chunk's last-token logits
    matched_full: List[int] = field(default_factory=list)
    matched_partial: Optional[int] = None
    n_partial: int = 0
    resumed: bool = False
    # set by the sharing-degradation fallback: staging restarts WITHOUT
    # re-matching (re-adopting the same prefix would undo the degrade
    # and livelock the queue head)
    no_rematch: bool = False

    @property
    def fill(self) -> int:
        return int(self.tokens.shape[0])

    def done_staging(self) -> bool:
        return self.base + self.cpos >= self.fill


def choose_victim(slots, active, needy_slot: int) -> Optional[int]:
    """Preemption victim: the YOUNGEST (highest rid) active request
    other than the one needing room — it has the least sunk prefill/
    decode work and the best chance of a prefix hit on re-admission
    (its own prompt blocks just went into the cached tier). None when
    the needy slot is the only active one."""
    best = None
    best_rid = -1
    for s, req in enumerate(slots):
        if req is None or not active[s] or s == needy_slot:
            continue
        if req.rid > best_rid:
            best, best_rid = s, req.rid
    return best


@dataclass
class SLORecord:
    rid: int
    t_submit: float
    deadline_s: Optional[float] = None
    t_prefill: Optional[float] = None    # head of the prefill queue (latest)
    t_admit: Optional[float] = None      # prefill done, slot active
    t_first: Optional[float] = None      # first token materialized
    t_done: Optional[float] = None
    n_tokens: int = 0
    preemptions: int = 0
    # queued | prefilling | decoding | done | migrated (extracted and
    # re-hosted on a peer batcher — terminal HERE; the adopting side
    # opens a fresh record that finishes the request)
    state: str = "queued"

    def view(self) -> Dict[str, Any]:
        ttft = tpot = None
        if self.t_first is not None:
            ttft = (self.t_first - self.t_submit) * 1000.0
        if (self.t_done is not None and self.t_first is not None
                and self.n_tokens > 1):
            tpot = ((self.t_done - self.t_first)
                    / (self.n_tokens - 1)) * 1000.0
        queue_ms = None
        if self.t_admit is not None:
            queue_ms = (self.t_admit - self.t_submit) * 1000.0
        out = {
            "state": self.state,
            "queue_ms": queue_ms,
            "ttft_ms": ttft,
            "tpot_ms": tpot,
            "tokens": self.n_tokens,
            "preemptions": self.preemptions,
        }
        if self.deadline_s is not None:
            remaining = self.deadline_s - (time.perf_counter()
                                           - self.t_submit)
            out["deadline_s"] = round(remaining, 3)
        return out


class SLOLedger:
    """Bounded per-request SLO accounting. Single-writer under the
    batcher's state lock; emits the queue/TTFT/TPOT histograms through
    the obs registry resolved once at construction (the FaultGate
    discipline). The one place a request changes state, so also the one
    place its ``nns.req.*`` instants are written: a request's events
    share its ``rid``."""

    def __init__(self, keep: int = 1024, obs_registry=None):
        self._recs: "OrderedDict[int, SLORecord]" = OrderedDict()
        self._keep = keep
        self._obs = obs_registry
        self.preemptions_total = 0

    def submit(self, rid: int, deadline_s: Optional[float] = None
               ) -> SLORecord:
        rec = SLORecord(rid, time.perf_counter(), deadline_s=deadline_s)
        self._recs[rid] = rec
        while len(self._recs) > self._keep:
            self._recs.popitem(last=False)
        trace.instant("nns.req.submit", rid=rid)
        return rec

    def _get(self, rid: int) -> Optional[SLORecord]:
        return self._recs.get(rid)

    def prefilling(self, rid: int) -> None:
        rec = self._get(rid)
        if rec is None or rec.state != "queued":
            return
        rec.state = "prefilling"
        now = time.perf_counter()
        attrs = {}
        if rec.t_prefill is None:  # a re-prefill after preemption queued once
            attrs["queue_ms"] = (now - rec.t_submit) * 1000.0
            if self._obs is not None:
                self._obs.histogram("nns_request_queue_ms").observe(
                    max(attrs["queue_ms"], 1e-6)
                )
        rec.t_prefill = now
        trace.instant("nns.req.prefill_start", rid=rid, **attrs)

    def admitted(self, rid: int) -> None:
        rec = self._get(rid)
        if rec is not None:
            rec.t_admit = time.perf_counter()
            rec.state = "decoding"
            trace.instant(
                "nns.req.admitted", rid=rid,
                prefill_ms=(rec.t_admit - (rec.t_prefill or rec.t_submit))
                * 1000.0,
            )

    def first_token(self, rid: int) -> None:
        rec = self._get(rid)
        if rec is not None and rec.t_first is None:
            rec.t_first = time.perf_counter()
            ttft_ms = (rec.t_first - rec.t_submit) * 1000.0
            trace.instant("nns.req.first_token", rid=rid, ttft_ms=ttft_ms)
            if self._obs is not None:
                self._obs.histogram("nns_request_ttft_ms").observe(
                    max(ttft_ms, 1e-6)
                )

    def record(self, rid: int) -> Optional[SLORecord]:
        """The live record for ``rid`` (migration reads the deadline and
        preemption count to ship with the span), or None if evicted."""
        return self._get(rid)

    def migrated(self, rid: int) -> None:
        """The request was extracted and re-hosted elsewhere: terminal
        for THIS ledger (the peer's record carries it to done)."""
        rec = self._get(rid)
        if rec is not None:
            rec.t_done = time.perf_counter()
            rec.state = "migrated"

    def preempted(self, rid: int) -> None:
        rec = self._get(rid)
        self.preemptions_total += 1
        if rec is not None:
            rec.preemptions += 1
            rec.state = "queued"

    def finished(self, rid: int, n_tokens: int) -> None:
        rec = self._get(rid)
        if rec is None:
            return
        rec.t_done = time.perf_counter()
        rec.n_tokens = n_tokens
        rec.state = "done"
        if rec.t_first is None:  # one-token requests: first IS done
            rec.t_first = rec.t_done
        tpot = 0.0
        if n_tokens > 1:
            tpot = (rec.t_done - rec.t_first) / (n_tokens - 1) * 1000.0
            if self._obs is not None:
                self._obs.histogram("nns_request_tpot_ms").observe(
                    max(tpot, 1e-6)
                )
        trace.instant("nns.req.done", rid=rid, tokens=n_tokens,
                      tpot_ms=tpot, preemptions=rec.preemptions)

    def view(self, extra: Optional[Dict[int, Dict]] = None
             ) -> Dict[int, Dict[str, Any]]:
        out = {}
        for rid, rec in self._recs.items():
            row = rec.view()
            if extra and rid in extra:
                row.update(extra[rid])
            out[rid] = row
        return out

    def snapshot(self) -> dict:
        return {
            "preemptions_total": self.preemptions_total,
            "records": [
                {
                    "rid": r.rid,
                    "t_submit": r.t_submit,
                    "deadline_s": r.deadline_s,
                    "t_admit": r.t_admit,
                    "t_first": r.t_first,
                    "t_done": r.t_done,
                    "n_tokens": r.n_tokens,
                    "preemptions": r.preemptions,
                    "state": r.state,
                }
                for r in self._recs.values()
            ],
        }

    def restore(self, snap: dict) -> None:
        self.preemptions_total = int(snap.get("preemptions_total", 0))
        self._recs = OrderedDict()
        for d in snap.get("records", []):
            rec = SLORecord(
                int(d["rid"]), float(d["t_submit"]),
                deadline_s=d.get("deadline_s"),
            )
            rec.t_admit = d.get("t_admit")
            rec.t_first = d.get("t_first")
            rec.t_done = d.get("t_done")
            rec.n_tokens = int(d.get("n_tokens", 0))
            rec.preemptions = int(d.get("preemptions", 0))
            rec.state = str(d.get("state", "queued"))
            self._recs[rec.rid] = rec
