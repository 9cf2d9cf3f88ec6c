"""One general traffic generator. A mix is a data file of parameters
(``benchmark/traffic/<mix>.json``); this module turns it, a seed and a
length into requests. Every seed gets the SAME multiset of lengths and
arrival gaps (stratified quantiles of the mix's distributions), in another
order, and its own token ids: so two seeds do the same work and differ only
in its order.

Mix keys:
  arrivals      "open_poisson" (needs rate_rps) | "closed_loop" (needs
                clients_per_slot; a client sends its next request when its
                last one finished)
  prompt_len    {"dist": "log_uniform"|"uniform", "lo": .., "hi": ..}
  output_len    the same
  schedule_seed the one shuffle of gaps and lengths that every seed rotates
  ramp_s        seconds of the same traffic before the window opens
                (counted as set-up, not measured)
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass
class Request:
    rid: int
    due: Optional[float]  # seconds from the window's start; None = closed loop
    prompt: np.ndarray    # int32 token ids
    out_len: int


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws (mid-quantiles) of a length distribution."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if spec["dist"] == "log_uniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _lengths(mix: dict, n: int, seed: int):
    """The mix's fixed sequence of (prompt, output) lengths, started at the
    seed's place."""
    base = np.random.default_rng(int(mix.get("schedule_seed", 0)))
    p = base.permutation(_quantiles(mix["prompt_len"], n))
    o = base.permutation(_quantiles(mix["output_len"], n))
    k = seed % n
    return np.roll(p, -k), np.roll(o, -k)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=(n,), dtype=np.int64).astype(np.int32)


def open_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """Open loop: Poisson arrivals at ``rate_rps``. The window holds exactly
    one cycle of the mix's fixed sequence (n = rate x seconds requests, gaps
    scaled to fill it), started at the seed's place; the ramp before it
    replays the end of the same cycle. So every seed's window holds the same
    requests at the same distances, and only the seam moves."""
    rate = float(mix["rate_rps"])
    ramp = float(mix.get("ramp_s", 0.0))
    n = max(1, int(rate * seconds))
    rng = np.random.default_rng(seed)
    u = (np.arange(n) + 0.5) / n
    base = np.random.default_rng(int(mix.get("schedule_seed", 0)) + 1)
    gaps = np.roll(base.permutation(-np.log1p(-u)), -(seed % n))
    gaps *= seconds / gaps.sum()
    due = np.cumsum(gaps) - gaps[0] * 0.5
    p, o = _lengths(mix, n, seed)
    before = [i for i in range(n) if due[i] - seconds >= -ramp]
    order = [(due[i] - seconds, i) for i in before] + [(due[i], i) for i in range(n)]
    return [Request(rid, float(t), _tokens(rng, int(p[i]), vocab), int(o[i]))
            for rid, (t, i) in enumerate(order)]


class ClosedLoop:
    """Closed loop: ``clients`` requests outstanding; :meth:`next` hands out
    the next request of the seed's fixed list."""

    LIST = 4096

    def __init__(self, mix: dict, seed: int, n_slots: int, vocab: int):
        self.clients = int(mix["clients_per_slot"]) * n_slots
        self._rng = np.random.default_rng(seed)
        self._p, self._o = _lengths(mix, self.LIST, seed)
        self._vocab = vocab
        self._i = 0

    def next(self) -> Request:
        i = self._i
        self._i += 1
        k = i % self.LIST
        return Request(i, None, _tokens(self._rng, int(self._p[k]), self._vocab),
                       int(self._o[k]))
