"""Whole-chain resident dataflow programs (ROADMAP item 1,
docs/chain-analysis.md "Compiled chains").

A :class:`~nnstreamer_tpu.pipeline.graph.Chain` — fused segments joined
by device-resident handoffs — runs by default as one service thread per
segment, one XLA dispatch per segment per frame. At multi-kfps rates
the executor is host-dispatch-bound, not compute-bound (the
StreamTensor lesson, PAPERS.md: compile the inter-stage FIFOs INTO the
dataflow program instead of mediating them on the host). This module
makes the chain itself the compile unit:

- :func:`decide_chain` — the ONE eligibility/verdict function shared by
  the executor (should this chain get a ``ChainNode``?), ``nns-xray``
  (the chain report's ``compiled`` column), and the ``NNS-W125`` lint
  (eligible but configured off) — three consumers, one decision, so
  they can never disagree. Eligibility reuses the same jaxpr walkers
  the W120–W124 passes run (analysis/xray.py): any hazard that would
  fire there blocks compilation here.
- :class:`ChainProgram` — traces ONE jitted program threading every
  stage's outputs into the next as on-device values, unrolled K frames
  per launch (``[executor] chain_unroll``, clamped by the W124
  transient-HBM bound from ``analysis/costmodel.chain_cost``), with
  donation carried across the whole chain via the existing
  ``_aliasable_argnums`` discipline. Identity ops contribute
  passthrough fns and collapse out of the trace; an all-identity chain
  never dispatches at all.

The per-node path stays the PARITY ORACLE (as the slot KV layout does for
block attention): :meth:`ChainProgram.process_frame_fallback`
serves a frame through each member segment's OWN program in order —
bitwise-identical to the member FusedNodes — and the executor's
``ChainNode`` latches onto it for any runtime hazard (device fault,
OOM at the last unroll rung, heterogeneous/renegotiated windows).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.pipeline.batching import default_buckets
from nnstreamer_tpu.pipeline.graph import (
    FusedSegment,
    jit_weights_as_arguments,
)
from nnstreamer_tpu.pipeline.transfer import (
    resolve_chain_mode,
    resolve_chain_unroll,
)

_log = get_logger("chain_program")


@dataclass(frozen=True)
class ChainDecision:
    """The shared compile verdict for one chain.

    ``eligible`` — a hazard-free multi-segment chain a single resident
    program can serve. ``reason`` — the FIRST blocking hazard/config
    when not eligible (the xray ``compiled`` column prints it).
    ``mode`` — the resolved ``chain-mode`` (member property over
    ``[executor] chain_mode``). ``unroll`` — frames per launch window,
    already clamped by the W124 bound. The executor compiles exactly
    when ``eligible and mode == "auto"``; nns-lint fires ``NNS-W125``
    exactly when ``eligible and mode == "off"``.
    """

    eligible: bool
    reason: Optional[str]
    mode: str
    unroll: int

    @property
    def compiles(self) -> bool:
        return self.eligible and self.mode == "auto"


def _gate_active(seg) -> bool:
    """Would the executor arm a per-frame error-policy gate for this
    segment? (Same participation rule as ``Node.make_fault_gate``: the
    element must DECLARE the fault surface.)"""
    pol = seg.fault_policy
    if pol is None or not getattr(pol, "active", False):
        return False
    elem = seg.first
    return "on-error" in type(elem).property_schema()


def _interior_external_consumer(plan, chain):
    """An element OUTSIDE the chain that consumes an interior handoff
    (a queue between two member segments also feeding a sink): the
    compiled program keeps interior values inside the trace, so such a
    consumer would starve — the chain must stay on the per-node path."""
    pipeline = plan.pipeline
    for a, b in zip(chain.segments, chain.segments[1:]):
        member = {id(op) for op in b.ops}
        frontier = [ln.dst for ln in pipeline.out_links(a.last)]
        seen: set = set()
        while frontier:
            e = frontier.pop()
            if id(e) in seen or id(e) in member:
                continue
            seen.add(id(e))
            if (
                getattr(type(e), "DEVICE_PASSTHROUGH", False)
                and plan.seg_of.get(e) is None
            ):
                frontier.extend(ln.dst for ln in pipeline.out_links(e))
                continue
            return e
    return None


def _interior_external_producer(plan, chain):
    """An element OUTSIDE the chain that FEEDS an interior entry point
    (a second producer into a downstream member segment, e.g. two
    branches funneled through one queue): the compiled program only
    services the chain head's input, so frames from the other producer
    would be lost — the chain must stay on the per-node path."""
    pipeline = plan.pipeline
    member = {id(op) for op in chain.ops}
    for seg in chain.segments[1:]:
        frontier = [ln.src for ln in pipeline.in_links(seg.first)]
        seen: set = set()
        while frontier:
            e = frontier.pop()
            if id(e) in seen or id(e) in member:
                continue
            seen.add(id(e))
            if (
                getattr(type(e), "DEVICE_PASSTHROUGH", False)
                and plan.seg_of.get(e) is None
            ):
                frontier.extend(ln.src for ln in pipeline.in_links(e))
                continue
            return e
    return None


def _hazard(chain) -> Optional[str]:
    """First W120–W124 finding that blocks whole-chain compilation —
    the SAME walkers the nns-xray passes run (analysis/xray.py), so the
    executor and the report can never disagree about a hazard. Identity
    segments skip the trace-based walks (nothing dispatches there)."""
    import importlib

    # the analysis package re-exports the xray() FUNCTION under the
    # same name as its module — resolve the module explicitly
    _x = importlib.import_module("nnstreamer_tpu.analysis.xray")
    from nnstreamer_tpu.analysis.costmodel import (
        chain_cost,
        configured_device_bound,
    )

    for seg in chain.segments:
        if seg.is_identity():
            continue
        try:
            jaxpr = _x.segment_jaxpr(seg)
        except Exception as exc:  # noqa: BLE001 — untraceable: no program
            return f"segment {seg.name} untraceable ({exc})"
        if jaxpr is None:
            return f"segment {seg.name} has a flexible input spec"
        prims = _x.host_callback_prims(jaxpr)
        if prims:
            return (
                f"NNS-W120 host callback `{prims[0]}` in segment "
                f"{seg.name}"
            )
        declared = None
        out_spec = seg.last.out_specs[0] if seg.last.out_specs else None
        if out_spec is not None and getattr(out_spec, "is_static", False):
            declared = tuple(t.dtype.np_dtype for t in out_spec)
        msgs = _x.dtype_findings(jaxpr, declared)
        if msgs:
            return f"NNS-W122 in segment {seg.name}: {msgs[0]}"
        if _x.cache_key_finding(seg) is not None:
            return f"NNS-W121 cache-key hazard in segment {seg.name}"
        if _x.donation_finding(seg) is not None:
            return f"NNS-W123 donation hazard in segment {seg.name}"
    bound = configured_device_bound()
    if bound is not None:
        cost = chain_cost(chain, open_backends=True)
        if cost.resident_bytes > bound:
            return (
                f"NNS-W124 resident {cost.resident_bytes} B over the "
                f"[plane] memory_per_device bound ({bound} B)"
            )
    return None


def _clamp_unroll(chain, unroll: int) -> int:
    """Shrink the unroll window until the chain's whole-window working
    set (params + per-frame peak transient × K) fits the declared
    device bound — the W124 discipline applied to the launch width
    (``analysis/costmodel.chain_cost``). No bound declared = the
    configured width stands."""
    from nnstreamer_tpu.analysis.costmodel import (
        chain_cost,
        configured_device_bound,
    )

    bound = configured_device_bound()
    if bound is None or unroll <= 1:
        return unroll
    try:
        cost = chain_cost(chain, open_backends=True)
    except Exception:  # noqa: BLE001 — no estimate: keep the config width
        return unroll
    per = max(1, cost.transient_bytes)
    while unroll > 1 and cost.params_bytes + per * unroll > bound:
        unroll //= 2
    return unroll


def decide_chain(plan, chain) -> ChainDecision:
    """The shared executor/xray/lint verdict for one chain (see
    :class:`ChainDecision`). Cheap checks run first; the jaxpr-walking
    hazard pass only runs for chains that structurally qualify."""
    mode = resolve_chain_mode(chain.ops)
    unroll = resolve_chain_unroll(chain.ops)
    if len(chain.segments) < 2:
        return ChainDecision(
            False, "single segment (the per-node path is already one "
            "program)", mode, unroll,
        )
    if os.environ.get("NNS_NO_FUSE", "").lower() in ("1", "true", "yes"):
        return ChainDecision(
            False, "NNS_NO_FUSE per-element oracle active", mode, unroll
        )
    for seg in chain.segments:
        cfg = seg.batch_config
        if cfg is not None and getattr(cfg, "active", False):
            return ChainDecision(
                False, f"micro-batching active on segment {seg.name}",
                mode, unroll,
            )
        if _gate_active(seg):
            return ChainDecision(
                False,
                f"per-frame error policy active on segment {seg.name}",
                mode, unroll,
            )
    for op in chain.ops:
        if getattr(op, "qos_sources", None):
            return ChainDecision(
                False, f"upstream QoS wired through {op.name}", mode,
                unroll,
            )
    if chain.segments[0]._negotiated_sig() is None and not all(
        seg.is_identity() for seg in chain.segments
    ):
        return ChainDecision(
            False, "flexible input spec at the chain head", mode, unroll
        )
    ext = _interior_external_consumer(plan, chain)
    if ext is not None:
        return ChainDecision(
            False,
            f"interior handoff also feeds {getattr(ext, 'name', ext)} "
            "outside the chain", mode, unroll,
        )
    ext = _interior_external_producer(plan, chain)
    if ext is not None:
        return ChainDecision(
            False,
            f"interior segment also fed by {getattr(ext, 'name', ext)} "
            "outside the chain", mode, unroll,
        )
    try:
        hazard = _hazard(chain)
    except Exception as exc:  # noqa: BLE001 — analysis failure: stay safe
        hazard = f"hazard analysis failed ({exc})"
    if hazard is not None:
        return ChainDecision(False, hazard, mode, unroll)
    return ChainDecision(True, None, mode, _clamp_unroll(chain, unroll))


class ChainProgram:
    """ONE jitted resident program for a whole chain, unrolled K frames
    per launch.

    The trace composes every member op's current fn in chain order —
    interior handoffs become on-device values threaded stage to stage,
    never a host hop — and applies it to each of the K frame slots of a
    window, so steady state dispatches one XLA launch per window
    instead of one per node per frame. Windows are padded up to a
    bucket ladder (1,2,4,...,K — replicas of the last frame, or poison
    under the sanitizer, exactly the ``process_batch`` discipline) so
    the trace count stays O(log K). The jit cache is keyed (per-frame
    sig, bucket, member fn versions, donate) like ``FusedSegment``'s —
    a renegotiated spec or a model hot swap can never be served a stale
    program.
    """

    def __init__(self, chain, unroll: int) -> None:
        self.chain = chain
        self.unroll = max(1, int(unroll))
        self.buckets: Tuple[int, ...] = default_buckets(self.unroll)
        # (sig, bucket, versions, donate) -> jitted fn; _last fast path
        self._cache: Dict[tuple, Callable] = {}
        self._last: Optional[tuple] = None
        self.n_traces = 0
        # single-writer (the owning ChainNode's service thread): one XLA
        # dispatch per increment — the launch-count pin tests assert on
        self.launches = 0
        self.donate = all(seg.donate for seg in chain.segments)
        # set by the executor when its sanitizer is active (pad rows
        # become poison instead of last-frame replicas)
        self.sanitize_poison = False
        self._identity: Optional[bool] = None
        # ops whose class actually overrides transform_meta — skipping
        # the base-class identity hops keeps the per-frame cost of a
        # window O(overriders), not O(members) (at kfps window rates
        # three no-op Python calls per frame are real money)
        from nnstreamer_tpu.elements.base import TensorOp as _TensorOp

        self._meta_ops = [
            op for op in chain.ops
            if type(op).transform_meta is not _TensorOp.transform_meta
        ]

    @property
    def name(self) -> str:
        return self.chain.name

    def is_identity(self) -> bool:
        if self._identity is None:
            self._identity = all(
                seg.is_identity() for seg in self.chain.segments
            )
        return self._identity

    def _versions(self) -> tuple:
        return tuple(op.fn_version for op in self.chain.ops)

    def _compose(self) -> Callable:
        """The whole chain's composed fn, collected FRESH per cache
        fill (a reloaded/renegotiated member contributes its current
        fn). Identity ops contribute passthroughs and collapse out of
        the trace — XLA sees only the real math."""
        fns = [op.make_fn() for op in self.chain.ops]

        def composed(*tensors):
            t = tuple(tensors)
            for f in fns:
                t = tuple(f(t))
            return t

        return composed

    def _unrolled(self, k: int) -> Callable:
        """K literal repetitions of the composed chain over a flat
        argument list of K × T tensors — one program, K independent
        per-frame slices, so results stay bitwise-identical to the
        per-frame path (no vmap re-association)."""
        composed = self._compose()
        if k == 1:
            return composed

        def prog(*flat):
            t = len(flat) // k
            outs: list = []
            for i in range(k):
                outs.extend(composed(*flat[i * t:(i + 1) * t]))
            return tuple(outs)

        return prog

    def bucket_for(self, n: int) -> int:
        n = min(max(1, n), self.unroll)
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _jitted_for(
        self, sig: tuple, bucket: int, donate: bool
    ) -> Callable:
        key = (sig, bucket, self._versions(), donate)
        last = self._last
        if last is not None and last[0] == key:
            return last[1]
        fn = self._cache.get(key)
        if fn is None:
            target = self._unrolled(bucket)
            # whole-chain donation: the W-window's staged uploads are
            # node-owned, and _aliasable_argnums matches each output
            # slot to at most one input buffer across the ENTIRE
            # unrolled program — interior activations are XLA's to
            # reuse already (they never escape the trace)
            argnums = (
                FusedSegment._aliasable_argnums(
                    target, tuple(sig) * bucket, 0
                )
                if donate else ()
            )
            fn = jit_weights_as_arguments(target, argnums)
            self._cache[key] = fn
            self.n_traces += 1
        self._last = (key, fn)
        return fn

    def build(self) -> None:
        """Warm the steady-state window program at the negotiated spec
        (PAUSED-state parity, ``FusedSegment.build`` discipline): the
        full-unroll bucket on zeros so the first loaded window doesn't
        stall on an XLA compile; smaller buckets fill lazily at
        trickle/EOS boundaries. A deterministic compile failure
        re-raises (the node latches its fallback at build, not
        mid-stream); anything else is a skipped optimization."""
        if self.is_identity():
            return
        sig = self.chain.segments[0]._negotiated_sig()
        if sig is None:
            return
        import numpy as _np

        self._jitted_for(sig, 1, False)
        if self.unroll > 1:
            try:
                zeros = [
                    _np.zeros(shape, dtype)
                    for shape, dtype in sig
                ] * self.unroll
                jax.block_until_ready(
                    self._jitted_for(sig, self.unroll, False)(*zeros)
                )
            except Exception as exc:
                from nnstreamer_tpu.pipeline.device_faults import (
                    classify_device_fault,
                )

                if classify_device_fault(exc) == "compile":
                    raise
                _log.warning(
                    "%s: window warmup failed: %s", self.name, exc
                )

    def _apply_meta(self, f):
        for op in self._meta_ops:
            f = op.transform_meta(f)
        return f

    def process_window(self, frames, donate: bool = False):
        """One window through the resident program. Returns
        ``(out_frames, rows, launched)``: ``rows`` is the dispatched
        bucket width (pad rows included, batch-stats discipline) and
        ``launched`` is False on the no-dispatch paths — an identity
        chain (frames pass untouched) or a heterogeneous/renegotiating
        window (served per frame by the parity oracle, semantics
        identical)."""
        n = len(frames)
        if self.is_identity():
            if not self._meta_ops:
                return list(frames), n, False
            return [self._apply_meta(f) for f in frames], n, False
        sig = FusedSegment._sig_of(frames[0].tensors)
        if n > 1 and any(
            FusedSegment._sig_of(f.tensors) != sig for f in frames[1:]
        ):
            out = [self.process_frame_fallback(f) for f in frames]
            return out, n, False
        bucket = self.bucket_for(n)
        for seg in self.chain.segments:
            probes = seg._device_probes()
            if probes:
                # chaos injectors see the PADDED width — the width the
                # device would see (process_batch parity)
                for probe in probes:
                    probe(bucket)
        fn = self._jitted_for(sig, bucket, donate)
        pad = bucket - n
        flat: list = []
        for f in frames:
            flat.extend(f.tensors)
        if pad:
            filler = None
            if self.sanitize_poison:
                from nnstreamer_tpu.pipeline.sanitize import poison_like

                filler = poison_like
            last = frames[-1].tensors
            for _ in range(pad):
                flat.extend(
                    [filler(t) if filler else t for t in last]
                )
        outs = fn(*flat)
        self.launches += 1
        t = len(outs) // bucket
        meta = self._meta_ops
        result = []
        for j, frame in enumerate(frames):
            f = frame.with_tensors(list(outs[j * t:(j + 1) * t]))
            result.append(self._apply_meta(f) if meta else f)
        return result, bucket, True

    # -- the parity oracle -------------------------------------------------
    def process_frame_fallback(self, frame):
        """One frame through each member segment's OWN jitted program
        in chain order — the exact computation the member FusedNodes
        would run, so results are bitwise-identical to the per-node
        path (the oracle the compiled chain is always checked
        against)."""
        f = frame
        for seg in self.chain.segments:
            f = seg.process(f)
        return f

    def process_frame_eager(self, frame):
        """The degraded-degraded rung: every member segment's un-jitted
        path (a chain whose compiled AND per-segment programs both fault
        still serves, device-circuit semantics)."""
        f = frame
        for seg in self.chain.segments:
            f = seg.process_eager(f)
        return f
