"""Kernel dispatch accounting: which implementation each dual-path op
actually chose.

Every op with a Pallas TPU kernel and a jnp fallback (ops/image.py
crop/resize, ops/detection.py NMS, kv/block_attn.py block attention,
the serving model's attention constructors) resolves ``impl="auto"`` at
trace/build time. Until now that decision was invisible — a pipeline
could silently run the fallback on TPU (or vice versa) with nothing to
prove which kernel engaged. This module is the proof: each dispatch
site records its (op, impl) choice into a process-local tally that
``nns-xray --dispatch`` diffs around tiny probe invocations
(docs/chain-analysis.md "Kernel dispatch"), and tests pin.

Recording happens at TRACE time (inside the op wrapper, outside any
jit), so counts measure program builds, not per-frame calls — exactly
the "did the kernel engage" evidence wanted, at zero hot-path cost.
"""

from __future__ import annotations

import threading
from typing import Dict, Tuple

# -- dispatch tally ---------------------------------------------------------

class DispatchTally:
    """Process-local (op, impl) counters; every mutation under the one
    lock (the nns-san shared-counter discipline — dispatch sites run on
    whichever thread traces first)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: Dict[Tuple[str, str], int] = {}

    def record(self, op: str, impl: str) -> None:
        with self._lock:
            key = (str(op), str(impl))
            self._counts[key] = self._counts.get(key, 0) + 1

    def snapshot(self) -> Dict[Tuple[str, str], int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


tally = DispatchTally()

#: every op name a dual-path dispatch site records — the closed set
#: nns-kscope's registry↔tally agreement check (analysis/selfcheck.py
#: kscope_self_check) and bench.py's --capture-tpu schema enumerate.
#: Adding a dispatch site means adding its op here AND covering it from
#: a registered KernelSpec's ``ops`` tuple (ops/pallas/registry.py).
KNOWN_OPS = (
    "block_attention",
    "crop_and_resize",
    "decode_attention",
    "flash_attention",
    "kda_recurrence",
    "mla_attention",
    "nms",
    "resize_bilinear",
    "serving_attention",
    "ssm_recurrence",
)


def record(op: str, impl: str) -> None:
    """One dispatch decision: ``op`` resolved to ``impl`` ("pallas" or
    "jnp"/"xla"). Call at the branch point, with the RESOLVED impl —
    never "auto"."""
    tally.record(op, impl)


def engaged_impls(op: str, since: Dict[Tuple[str, str], int]) -> list:
    """Impls ``op`` dispatched to since the ``since`` snapshot, sorted
    (the nns-xray --dispatch measurement primitive)."""
    now = tally.snapshot()
    return sorted(
        impl
        for (o, impl), n in now.items()
        if o == op and n > since.get((o, impl), 0)
    )
