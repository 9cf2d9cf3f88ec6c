"""Pipeline graph: build → negotiate → compile (fuse) → execute.

The reference's pipeline bring-up (SURVEY.md §3.1: parse description,
create elements, negotiate caps at PAUSED, stream at PLAYING) becomes:

    Pipeline.add/link (or pipeline/parse.py from a description string)
    → negotiate(): one topological pass propagating TensorsSpec/MediaSpec
    → compile(): partition the graph into execution nodes, FUSING maximal
      linear chains of TensorOp elements into single jitted XLA programs
      (the TPU-first move: the reference runs one chain function per
      element per frame with map/unmap; we run one XLA program for the
      whole chain with tensors resident in HBM)
    → Executor (pipeline/executor.py): one streaming thread per node with
      bounded queues (GStreamer streaming-thread parity → pipeline
      parallelism and backpressure).
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from nnstreamer_tpu.elements.base import (
    Element,
    HostElement,
    NegotiationError,
    Routing,
    Sink,
    Source,
    Spec,
    TensorOp,
)
from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.tensors.frame import Frame
from nnstreamer_tpu.tensors.spec import TensorsSpec

_log = get_logger("pipeline")


@dataclass(frozen=True)
class Link:
    src: Element
    src_pad: int
    dst: Element
    dst_pad: int


class Pipeline:
    def __init__(self, name: str = "pipeline") -> None:
        self.name = name
        self.elements: List[Element] = []
        self.links: List[Link] = []
        self._by_name: Dict[str, Element] = {}
        self._negotiated = False
        self._executor = None

    # -- build -------------------------------------------------------------
    def add(self, *elements: Element) -> "Pipeline":
        for e in elements:
            if e in self.elements:
                continue
            if e.name in self._by_name:
                raise ValueError(f"duplicate element name {e.name!r}")
            self.elements.append(e)
            self._by_name[e.name] = e
        return self

    def __getitem__(self, name: str) -> Element:
        return self._by_name[name]

    def link(
        self,
        src: Element,
        dst: Element,
        src_pad: Optional[int] = None,
        dst_pad: Optional[int] = None,
    ) -> "Pipeline":
        self.add(src, dst)
        if src_pad is None:
            src_pad = self._next_free_src_pad(src)
        if dst_pad is None:
            dst_pad = self._next_free_dst_pad(dst)
        for l in self.links:
            if l.src is src and l.src_pad == src_pad:
                raise ValueError(f"{src.name} src pad {src_pad} already linked")
            if l.dst is dst and l.dst_pad == dst_pad:
                raise ValueError(f"{dst.name} sink pad {dst_pad} already linked")
        if src.N_SRCS is not None and src_pad >= src.N_SRCS:
            raise ValueError(f"{src.name} has no src pad {src_pad}")
        if dst.N_SINKS is not None and dst_pad >= dst.N_SINKS:
            raise ValueError(f"{dst.name} has no sink pad {dst_pad}")
        self.links.append(Link(src, src_pad, dst, dst_pad))
        return self

    def chain(self, *elements: Element) -> "Pipeline":
        """Link a linear chain e1 ! e2 ! ... (gst-launch `!`)."""
        for a, b in zip(elements, elements[1:]):
            self.link(a, b)
        return self

    def _next_free_src_pad(self, e: Element) -> int:
        used = {l.src_pad for l in self.links if l.src is e}
        pad = 0
        while pad in used:
            pad += 1
        return pad

    def _next_free_dst_pad(self, e: Element) -> int:
        used = {l.dst_pad for l in self.links if l.dst is e}
        pad = 0
        while pad in used:
            pad += 1
        return pad

    # -- introspection -----------------------------------------------------
    def out_links(self, e: Element) -> List[Link]:
        return sorted(
            (l for l in self.links if l.src is e), key=lambda l: l.src_pad
        )

    def in_links(self, e: Element) -> List[Link]:
        return sorted(
            (l for l in self.links if l.dst is e), key=lambda l: l.dst_pad
        )

    def n_srcs(self, e: Element) -> int:
        return e.N_SRCS if e.N_SRCS is not None else len(self.out_links(e))

    def n_sinks(self, e: Element) -> int:
        return e.N_SINKS if e.N_SINKS is not None else len(self.in_links(e))

    # -- negotiation -------------------------------------------------------
    def toposort_partial(self) -> Tuple[List[Element], List[Element]]:
        """Kahn's algorithm; returns (topological order, leftover). A
        non-empty leftover means those elements sit in (or behind) a
        cycle. The static analyzer consumes the partial form; negotiate()
        treats leftover as fatal via _toposort()."""
        indeg = {e: len(self.in_links(e)) for e in self.elements}
        ready = [e for e in self.elements if indeg[e] == 0]
        order: List[Element] = []
        while ready:
            e = ready.pop(0)
            order.append(e)
            for l in self.out_links(e):
                indeg[l.dst] -= 1
                if indeg[l.dst] == 0:
                    ready.append(l.dst)
        ordered = set(order)
        leftover = [e for e in self.elements if e not in ordered]
        return order, leftover

    def _toposort(self) -> List[Element]:
        order, leftover = self.toposort_partial()
        if leftover:
            cyclic = [e.name for e in leftover]
            raise NegotiationError(
                f"pipeline has a cycle through {cyclic}; use tensor_repo "
                "(reposink/reposrc) for feedback loops"
            )
        return order

    def negotiate(self) -> "Pipeline":
        """One topological pass: propagate specs, validate links
        (the reference's PAUSED-state caps negotiation)."""
        for e in self.elements:
            ins, outs = self.n_sinks(e), self.n_srcs(e)
            if isinstance(e, Routing):
                e.set_pad_counts(ins, outs)
            if ins != len(self.in_links(e)) and ins > 0:
                raise NegotiationError(
                    f"{e.name}: {len(self.in_links(e))}/{ins} sink pads linked"
                )
        for e in self._toposort():
            in_specs: List[Spec] = [None] * self.n_sinks(e)  # type: ignore
            for l in self.in_links(e):
                in_specs[l.dst_pad] = l.src.out_specs[l.src_pad]
            try:
                e.fix_negotiation(in_specs)
            except NegotiationError:
                raise
            except Exception as exc:
                raise NegotiationError(f"{e.name}: {exc}") from exc
            if len(e.out_specs) != self.n_srcs(e):
                raise NegotiationError(
                    f"{e.name}: negotiated {len(e.out_specs)} specs for "
                    f"{self.n_srcs(e)} src pads"
                )
        self._wire_qos()
        self._negotiated = True
        return self

    def _wire_qos(self) -> None:
        """Attach each tensor_rate's QoS hint to its upstream linear path
        (the reference's upstream QoS event propagation,
        gsttensor_rate.c:452): producers on the path skip frames the rate
        limiter would drop. The walk stops at fan-in/fan-out boundaries —
        a shared upstream (tee) may feed branches that still need the
        frame — and at elements that restructure timestamps or windows
        (aggregator, another rate, batching converter): skipping THEIR
        inputs would change the content of outputs the limiter keeps."""
        from nnstreamer_tpu.elements.filter import TensorFilter
        from nnstreamer_tpu.elements.flow import Queue
        from nnstreamer_tpu.elements.transform import TensorTransform

        passthrough_timing = (TensorFilter, TensorTransform, Queue)
        for e in self.elements:
            qos = getattr(e, "qos", None)
            if qos is None or not getattr(qos, "enabled", False):
                continue
            cur = e
            while True:
                ins = self.in_links(cur)
                if len(ins) != 1:
                    break
                up = ins[0].src
                if len(self.out_links(up)) != 1:
                    break  # tee/demux boundary: other branches need frames
                if not isinstance(up, passthrough_timing):
                    break  # timestamp-restructuring or unknown element
                up.add_qos_source(qos)
                cur = up

    # -- compile: fuse linear TensorOp chains ------------------------------
    def compile_plan(self) -> "ExecPlan":
        if not self._negotiated:
            self.negotiate()
        # group consecutive TensorOps with 1:1 linkage into segments.
        # NNS_NO_FUSE=1 keeps every element its own segment — the
        # reference-faithful per-element execution mode (one program
        # per element, queue hops between), useful to localize a fault
        # to an element vs the fusion, and the oracle the fused-vs-
        # unfused equivalence tests compare against.
        import os

        no_fuse = os.environ.get("NNS_NO_FUSE", "").lower() in (
            "1", "true", "yes", "on",
        )
        seg_of: Dict[Element, "FusedSegment"] = {}
        segments: List[FusedSegment] = []
        from nnstreamer_tpu.pipeline.batching import (
            BatchStats,
            resolve_batch_config,
        )
        from nnstreamer_tpu.pipeline.device_faults import (
            resolve_device_policy,
        )
        from nnstreamer_tpu.pipeline.faults import resolve_fault_policy
        from nnstreamer_tpu.pipeline.transfer import (
            donation_enabled,
            resolve_ring_depth,
        )

        for e in self._toposort():
            # non-traceable TensorOps (host-bound backends) execute as host
            # nodes; they are fusion barriers like HostElement. An element
            # whose dead-letter error pad is LINKED is also a barrier:
            # per-frame error routing needs per-frame invokes, which a
            # fused program cannot give it (an unlinked pad — retry with
            # no overflow sink — costs nothing and fuses normally).
            err_routed = e.error_pad is not None and any(
                l.src_pad == e.error_pad for l in self.out_links(e)
            )
            if (
                not isinstance(e, TensorOp)
                or err_routed
                or not e.is_traceable()
            ):
                if isinstance(e, TensorOp):
                    # host-path batching/fault config resolves at PLAN time
                    # like the segments below, so a bad property fails
                    # compile_plan() instead of poisoning a running node
                    e.batch_config = resolve_batch_config([e])
                    if e.batch_stats is None:
                        e.batch_stats = BatchStats()
                    e.fault_policy = resolve_fault_policy([e])
                    e.device_policy = resolve_device_policy([e])
                    # host nodes keep the synchronous loop unless the
                    # element asks for a ring explicitly (a host
                    # backend's invoke can't overlap with itself, so
                    # the config-level default would only add latency)
                    raw = e.get_property("ring-depth")
                    e.ring_depth = (
                        resolve_ring_depth([e]) if raw is not None else 1
                    )
                continue
            ups = self.in_links(e)
            up = ups[0].src if len(ups) == 1 else None
            if (
                not no_fuse
                and up is not None
                and isinstance(up, TensorOp)
                and up in seg_of
                and len(self.out_links(up)) == 1
            ):
                seg = seg_of[up]
                seg.ops.append(e)
                seg_of[e] = seg
            else:
                seg = FusedSegment(ops=[e])
                segments.append(seg)
                seg_of[e] = seg
        # resolve micro-batching per segment (element properties over the
        # executor-level [executor] config default) and share the stats
        # object with the ops so tensor_filter's read-only avg-batch-size/
        # pad-waste-pct/batch-wait-ms properties report their segment
        from nnstreamer_tpu.elements.converter import TensorConverter
        from nnstreamer_tpu.elements.decoder import TensorDecoder
        from nnstreamer_tpu.elements.transform import TensorTransform

        def _postproc_op(op: TensorOp) -> bool:
            """Member ops that are fused pre/post-processing rather than
            model invokes (docs/on-device-ops.md): a device-path
            decoder, an image-op transform, or a normalizing converter.
            Counted per segment so the executor can emit
            nns_fused_postproc_total and nns-top can flag the node."""
            if isinstance(op, TensorDecoder):
                return True  # only traceable decoders reach a segment
            if isinstance(op, TensorTransform):
                return op.mode in ("resize", "crop-resize")
            if isinstance(op, TensorConverter):
                return op.input_norm is not None
            return False

        for seg in segments:
            seg.batch_config = resolve_batch_config(seg.ops)
            seg.fault_policy = resolve_fault_policy(seg.ops)
            seg.device_policy = resolve_device_policy(seg.ops)
            seg.ring_depth = resolve_ring_depth(seg.ops)
            seg.donate = donation_enabled()
            seg.postproc_ops = sum(1 for op in seg.ops if _postproc_op(op))
            for op in seg.ops:
                op.batch_stats = seg.batch_stats
        return ExecPlan(self, segments, seg_of)

    # -- run ---------------------------------------------------------------
    def start(self):
        from nnstreamer_tpu.pipeline.executor import Executor

        if self._executor is not None and self._executor.finished:
            raise RuntimeError(
                f"pipeline {self.name!r} already ran to completion; build a "
                "fresh Pipeline to run again"
            )
        if self._executor is None:
            self._executor = Executor(self.compile_plan())
        self._executor.start()
        return self._executor

    def run(self, timeout: Optional[float] = None):
        """Start, wait for EOS (or error), stop. Returns the executor for
        inspecting sink results. Raises TimeoutError if `timeout` elapses
        before EOS."""
        ex = self.start()
        completed = ex.wait(timeout)
        ex.stop()
        # NNS_TRACE=<path> env opt-in (GST_DEBUG_DUMP_DOT_DIR-style):
        # flush the chrome trace when the pipeline winds down
        import os

        from nnstreamer_tpu import trace as trace_mod

        trace_path = os.environ.get("NNS_TRACE")
        if trace_path:
            tracer = trace_mod.get()
            if tracer is not None:
                tracer.save(trace_path)
        if ex.errors:
            raise ex.errors[0]
        if not completed:
            raise TimeoutError(
                f"pipeline {self.name!r} did not reach EOS within {timeout}s"
            )
        return ex

    def stop(self) -> None:
        if self._executor is not None:
            self._executor.stop()

    def dump_dot(self, diagnostics=None, specs=None) -> str:
        """Graphviz dump (reference GST_DEBUG_DUMP_DOT_DIR parity).

        `diagnostics`: optional iterable of nns-lint Diagnostics; offending
        nodes are painted (red = error, orange = warning) with their codes
        appended to the label, and pipeline-level findings become the
        graph label. `specs`: optional {element name: out_specs} override
        for the spec line (nns-lint's dry-run results — this pipeline's
        own elements stay un-negotiated)."""
        by_elem: Dict[str, List] = {}
        graph_level: List[str] = []
        for d in diagnostics or ():
            if d.element is None:
                graph_level.append(d.code)
            else:
                by_elem.setdefault(d.element, []).append(d)
        lines = [f'digraph "{self.name}" {{', "  rankdir=LR;"]
        if graph_level:
            lines.append(f'  label="{" ".join(sorted(set(graph_level)))}";')
        for e in self.elements:
            spec = ""
            out = (specs or {}).get(e.name) or e.out_specs
            if out:
                s = out[0]
                spec = f"\\n{s}" if s is not None else ""
            style = ""
            diags = by_elem.get(e.name)
            if diags:
                codes = " ".join(sorted({d.code for d in diags}))
                spec += f"\\n{codes}"
                worst = (
                    "red"
                    if any(d.severity.value == "error" for d in diags)
                    else "orange"
                )
                style = f', style=filled, fillcolor="{worst}"'
            lines.append(
                f'  "{e.name}" [label="{e.FACTORY_NAME}\\n{e.name}{spec}"'
                f", shape=box{style}];"
            )
        for l in self.links:
            lines.append(f'  "{l.src.name}" -> "{l.dst.name}" [label="{l.src_pad}→{l.dst_pad}"];')
        lines.append("}")
        return "\n".join(lines)


def jit_weights_as_arguments(target: Callable, donate_argnums=()) -> Callable:
    """``jax.jit(target)``, with every array the trace closes over — a
    member filter's weights, a decoder's box priors — hoisted out of the
    program and passed to it as ARGUMENTS.

    The ops' ``make_fn()`` closures carry their weights by closure, and a
    closed-over array is lowered as a CONSTANT: serialized into the
    program's HLO, compiled with it, kept in HBM once per program (per
    shape, per batch bucket, per unroll width) and written once per
    program into the compile cache. An SSD-MobileNet segment serializes
    to 233 MB that way — more than the chip machine's cache will hold —
    against 24 MB with its 74 MB of weights as arguments (PERF.md,
    PR 21). Tracing once to a jaxpr names exactly those arrays
    (``consts``); the jitted program evaluates the jaxpr with them as
    its first argument, so every entry shares the one resident copy.

    Lazy like ``jax.jit``: the first call traces (every entry serves one
    signature, so that trace is the only one). ``donate_argnums`` counts
    the call's own arguments."""
    bound: List[Callable] = []

    def call(*args):
        if not bound:
            closed = jax.make_jaxpr(target)(*args)
            jaxpr = closed.jaxpr

            def run(consts, *tensors):
                return tuple(jax.core.eval_jaxpr(jaxpr, consts, *tensors))

            jitted = jax.jit(
                run, donate_argnums=tuple(i + 1 for i in donate_argnums)
            )
            bound.append(functools.partial(jitted, closed.consts))
        return bound[0](*args)

    return call


class FusedSegment:
    """A maximal linear chain of TensorOps compiled into ONE jitted fn.

    Compiled programs are cached by (arity, shapes, dtypes, batch
    bucket, op fn versions), NOT by "compiled once": a spec
    renegotiation (different shapes/dtypes arriving after a rebuild), a
    different micro-batch bucket, or a same-shape model hot swap
    (reload_model ticks the op's fn_version) gets its own entry with
    freshly collected op fns — a stale program can never be silently
    reused. ``n_traces`` counts cache
    fills (each entry traces exactly once: shapes are fixed per key), so
    tests can assert the bucket ladder bounds retracing at
    O(log max-batch).
    """

    def __init__(self, ops: List[TensorOp]) -> None:
        self.ops = ops
        # (sig, bucket, fn versions) -> jitted fn; bucket 0 = per-frame
        self._cache: Dict[tuple, Callable] = {}
        self._last: Optional[tuple] = None  # (full_key, fn) fast path
        self.n_traces = 0
        # micro-batching (pipeline/batching.py): resolved at plan time;
        # stats shared with the ops so tensor_filter can surface them
        self.batch_config = None
        # error policy (pipeline/faults.py): resolved at plan time from
        # the member ops' on-error/retry-* properties. Segments never
        # carry a route policy — route ops are fusion barriers.
        self.fault_policy = None
        # device-resilience policy (pipeline/device_faults.py): resolved
        # at plan time like the fault policy; the executor builds the
        # OOM bucket governor + device circuit from it per node
        self.device_policy = None
        # eager (un-jitted) program: the degraded path the device
        # circuit serves from — no XLA compile, minimal device arena
        self._eager: Optional[tuple] = None
        # device_probe hooks of member backends (chaos injectors):
        # resolved once, empty for real pipelines so the hot path pays
        # one len() check per batched dispatch
        self._probes: Optional[list] = None
        # set by the executor when its sanitizer is active: pad rows in
        # process_batch are then poison, not last-frame replicas. One
        # flag resolved at build — the hot path never re-reads config.
        self.sanitize_poison = False
        # resident streaming (pipeline/transfer.py, docs/streaming.md):
        # ring_depth = in-flight frames the executor keeps for this
        # segment; donate = node-owned activation buffers (staged
        # uploads, stacked windows) are donated to the program so XLA
        # reuses them for outputs. Both resolved at plan time.
        self.ring_depth: Optional[int] = None
        self.donate = False
        # fused pre/post-processing member count (docs/on-device-ops.md):
        # resolved at plan time; >0 arms the nns_fused_postproc_total
        # emitter and the nns-top `fused-post` note
        self.postproc_ops = 0
        # identity short-circuit: a segment of only-identity ops (the
        # passthrough backend) serves frames without ANY device program
        # — per-frame XLA dispatch is pure overhead there. Resolved on
        # first use (backends must be open).
        self._identity: Optional[bool] = None
        from nnstreamer_tpu.pipeline.batching import BatchStats

        self.batch_stats = BatchStats()

    @property
    def first(self) -> TensorOp:
        return self.ops[0]

    @property
    def last(self) -> TensorOp:
        return self.ops[-1]

    @property
    def name(self) -> str:
        return "+".join(o.name for o in self.ops)

    @staticmethod
    def _sig_of(tensors) -> tuple:
        # raw (shape, dtype) pairs: np.dtype is hashable and equality-
        # stable, so no string normalization — this runs per frame on
        # the fused hot path
        return tuple((tuple(t.shape), t.dtype) for t in tensors)

    def _compose(self) -> Callable:
        """Collect the ops' CURRENT fns (re-run per cache fill so a
        renegotiated/reloaded op contributes its fresh fn)."""
        fns = [op.make_fn() for op in self.ops]

        def composed(*tensors):
            t = tuple(tensors)
            for f in fns:
                t = tuple(f(t))
            return t

        return composed

    def is_identity(self) -> bool:
        """True when every member op declares is_identity(): process()
        then returns the frame untouched (no compile, no dispatch)."""
        if self._identity is None:
            try:
                self._identity = all(op.is_identity() for op in self.ops)
            except Exception:  # noqa: BLE001 — unopened backend: not identity
                self._identity = False
        return self._identity

    def _jitted_for(
        self, sig: tuple, bucket: int = 0, donate: bool = False
    ) -> Callable:
        # fn_version ticks on model hot swap (reload_model): same shapes,
        # different weights — the old program must not be served
        versions = tuple(op.fn_version for op in self.ops)
        key = (sig, bucket, versions, donate)
        last = self._last
        if last is not None and last[0] == key:
            return last[1]
        fn = self._cache.get(key)
        if fn is None:
            composed = self._compose()
            target = jax.vmap(composed) if bucket else composed
            # donate_argnums on the activations: the caller OWNS these
            # buffers (staged uploads / stacked windows — never an
            # upstream element's arrays), so XLA may reuse them for
            # outputs instead of growing the device arena per in-flight
            # frame (docs/streaming.md). Only inputs whose (shape,
            # dtype) matches an output can actually be aliased — a
            # uint8 image feeding a float program would just be deleted
            # with an XLA "unusable donation" warning, so those stay
            # un-donated.
            argnums = (
                self._aliasable_argnums(target, sig, bucket) if donate
                else ()
            )
            fn = jit_weights_as_arguments(target, argnums)
            self._cache[key] = fn
            self.n_traces += 1
        self._last = (key, fn)
        return fn

    @staticmethod
    def _aliasable_argnums(target, sig, bucket: int) -> tuple:
        """Input indices whose buffer XLA can actually reuse for an
        output: exact (shape, dtype) match, each output absorbing at
        most one input. eval_shape runs abstractly (no compile, no
        device) — a trace failure just disables donation for this
        entry."""
        try:
            shapes = [
                jax.ShapeDtypeStruct(
                    (bucket, *shape) if bucket else shape, dtype
                )
                for shape, dtype in sig
            ]
            outs = jax.eval_shape(target, *shapes)
            pool: Dict[tuple, int] = {}
            for o in outs:
                k = (tuple(o.shape), np.dtype(o.dtype))
                pool[k] = pool.get(k, 0) + 1
            argnums = []
            for i, (shape, dtype) in enumerate(sig):
                k = (
                    ((bucket, *shape) if bucket else tuple(shape)),
                    np.dtype(dtype),
                )
                if pool.get(k, 0) > 0:
                    pool[k] -= 1
                    argnums.append(i)
            return tuple(argnums)
        except Exception:  # noqa: BLE001 — donation is an optimization
            return ()

    def _negotiated_sig(self) -> Optional[tuple]:
        spec = self.first.in_specs[0] if self.first.in_specs else None
        if not isinstance(spec, TensorsSpec) or not spec.is_static:
            return None
        return tuple(
            (tuple(t.shape), t.dtype.np_dtype) for t in spec
        )

    def build(self) -> Optional[Callable]:
        """Instantiate the per-frame program for the negotiated spec
        (PAUSED-state parity); per-signature entries fill lazily. With
        batching active, also warm the max-batch bucket — the
        steady-state program under load — by invoking it on zeros, so
        the first full batch doesn't stall the stream on an XLA compile
        (smaller buckets stay lazy: they only appear at trickle/EOS
        boundaries where a one-off compile stall is tolerable)."""
        sig = self._negotiated_sig()
        if sig is None or self.is_identity():
            return None
        # warm the variants steady state will actually SERVE: the cache
        # key includes `donate`, so warming the un-donated program when
        # the executor then calls the donated one would leave the first
        # live frame stalling on a full XLA compile at PLAYING. The
        # per-frame path donates only off-CPU (the staging path); the
        # batched path donates its stacked windows everywhere.
        from nnstreamer_tpu.pipeline.transfer import default_backend_is_cpu

        fn = self._jitted_for(
            sig, 0, self.donate and not default_backend_is_cpu()
        )
        cfg = self.batch_config
        if cfg is not None and cfg.active:
            try:
                import numpy as _np

                bucket = cfg.buckets[-1]
                zeros = [
                    _np.zeros((bucket,) + shape, dtype)
                    for shape, dtype in sig
                ]
                jax.block_until_ready(
                    self._jitted_for(sig, bucket, self.donate)(*zeros)
                )
            except Exception as exc:
                from nnstreamer_tpu.pipeline.device_faults import (
                    classify_device_fault,
                )

                if classify_device_fault(exc) == "compile":
                    # deterministic: re-trying per frame would recompile
                    # forever — surface it so the executor's build
                    # handler opens the device circuit at PAUSED state,
                    # not mid-stream. OOM/transient warmup faults stay
                    # swallowed: the runtime governor ladder degrades
                    # those gracefully, frame by frame.
                    raise
                # otherwise the warmup is an optimization
                _log.warning("%s: batched warmup failed: %s", self.name, exc)
        return fn

    def process(self, frame: Frame, donate: bool = False) -> Frame:
        """One frame through the compiled program. ``donate=True`` hands
        the frame's tensors to XLA for output reuse — ONLY legal when
        the caller owns every buffer (the executor's staged-H2D path;
        donated arrays are deleted, so a shared/reused input would die
        under its other holders)."""
        identity = self._identity
        if identity or (identity is None and self.is_identity()):
            f = frame
        else:
            fn = self._jitted_for(self._sig_of(frame.tensors), 0, donate)
            f = frame.with_tensors(fn(*frame.tensors))
        for op in self.ops:
            f = op.transform_meta(f)
        return f

    def process_eager(self, frame: Frame) -> Frame:
        """Run the composed ops WITHOUT jit — the degraded path the
        device circuit (pipeline/device_faults.py) serves from when the
        compiled program cannot: no XLA compile (a deterministic compile
        failure would just recur), per-op dispatch instead of one fused
        arena (an OOM'd segment gets room back). Semantics identical to
        process(); slower by construction."""
        versions = tuple(op.fn_version for op in self.ops)
        if self._eager is None or self._eager[0] != versions:
            self._eager = (versions, self._compose())
        out = self._eager[1](*frame.tensors)
        f = frame.with_tensors(tuple(out))
        for op in self.ops:
            f = op.transform_meta(f)
        return f

    def _device_probes(self) -> list:
        """Member backends' ``device_probe(rows)`` hooks (chaos
        injectors declare one; real backends don't, so this is [] and
        the batched hot path pays a single truthiness check)."""
        if self._probes is None:
            self._probes = [
                hook
                for op in self.ops
                for hook in (
                    getattr(
                        getattr(op, "backend", None), "device_probe", None
                    ),
                )
                if hook is not None
            ]
        return self._probes

    def process_batch(self, frames, cfg) -> Tuple[List[Frame], int]:
        """ONE batched device invoke for a window of same-spec frames.

        Stacks each tensor index on a NEW leading axis, pads up to the
        next bucket with replicas of the last frame (rows computed and
        discarded — the price of a bounded trace count), runs the
        vmapped program, and splits results back per frame in order
        with per-frame metadata/timestamps applied exactly as the
        per-frame path would."""
        import jax.numpy as jnp

        n = len(frames)
        if self.is_identity():
            # no program to batch for: per-frame passthrough, no padding
            return [self.process(f) for f in frames], n
        sig = self._sig_of(frames[0].tensors)
        if any(self._sig_of(f.tensors) != sig for f in frames[1:]):
            # heterogeneous window (flexible stream / renegotiation
            # boundary): frames can't share one stacked invoke — fall
            # back to per-frame programs, semantics identical
            return [self.process(f) for f in frames], n
        bucket = cfg.bucket_for(n)
        probes = self._device_probes()
        if probes:
            # deterministic capacity boundary (chaos injectors): probe
            # with the PADDED bucket — that is the width the device sees
            for probe in probes:
                probe(bucket)
        # the stacked cols are freshly built below — this call owns
        # them, so donation is always safe here (an OOM retry restacks
        # from the still-live member frames)
        fn = self._jitted_for(sig, bucket, self.donate)
        pad = bucket - n
        filler = None
        if pad and self.sanitize_poison:
            # sanitizer on: pad rows are poison (NaN / int max) instead
            # of last-frame replicas — a split/index bug then yields
            # garbage instead of a plausibly-stale frame
            from nnstreamer_tpu.pipeline.sanitize import poison_like

            filler = poison_like
        cols = []
        for i in range(len(frames[0].tensors)):
            rows = [f.tensors[i] for f in frames]
            if pad:
                last = frames[-1].tensors[i]
                rows.extend([filler(last) if filler else last] * pad)
            cols.append(jnp.stack(rows))
        outs = fn(*cols)
        result: List[Frame] = []
        for j, frame in enumerate(frames):
            f = frame.with_tensors([o[j] for o in outs])
            for op in self.ops:
                f = op.transform_meta(f)
            result.append(f)
        return result, bucket


@dataclass
class Chain:
    """One compile unit (docs/chain-analysis.md): a maximal run of
    fused segments joined by device-resident handoffs. An eligible
    multi-segment chain under ``[executor] chain_mode=auto`` compiles
    into ONE resident program the executor dispatches once per
    unrolled window (pipeline/chain_program.py ``decide_chain`` /
    ``ChainProgram``); anything else runs each segment as its own XLA
    program with a device-array pass between nodes — the parity
    oracle the compiled path falls back to. ``nns-xray`` reports and
    lints at this granularity either way."""

    segments: List[FusedSegment]

    @property
    def first(self) -> TensorOp:
        return self.segments[0].first

    @property
    def last(self) -> TensorOp:
        return self.segments[-1].last

    @property
    def name(self) -> str:
        return " => ".join(s.name for s in self.segments)

    @property
    def ops(self) -> List[TensorOp]:
        return [op for s in self.segments for op in s.ops]


@dataclass
class ExecPlan:
    pipeline: Pipeline
    segments: List[FusedSegment]
    seg_of: Dict[Element, FusedSegment]

    def _device_successor(
        self, seg: FusedSegment
    ) -> Optional[FusedSegment]:
        """The unique fused segment ``seg`` hands frames to on device:
        reachable from ``seg.last`` across only ``DEVICE_PASSTHROUGH``
        plumbing (queue, capsfilter — the executor's resident handoff
        rides through those untouched, the same transparency
        ``Node._out_wants_host`` negotiates). Anything else on the path
        — a host-path op, routing, tee fan-out, a ``WANTS_HOST``
        consumer — severs the chain, as does reaching two different
        segments (no single linear program covers a fork)."""
        frontier = [l.dst for l in self.pipeline.out_links(seg.last)]
        seen: set = set()
        hit: Optional[FusedSegment] = None
        while frontier:
            e = frontier.pop()
            if id(e) in seen:
                continue
            seen.add(id(e))
            s2 = self.seg_of.get(e)
            if s2 is not None and s2 is not seg:
                if hit is not None and hit is not s2:
                    return None
                hit = s2
            elif getattr(type(e), "DEVICE_PASSTHROUGH", False):
                frontier.extend(
                    l.dst for l in self.pipeline.out_links(e)
                )
        return hit

    def chains(self) -> List[Chain]:
        """Compile units: maximal runs of fused segments joined by
        device handoffs (:class:`Chain`), in plan (topological) order.
        Every segment lands in exactly one chain; a pipeline with no
        host hop between its filters is a single chain end to end."""
        next_of: Dict[int, FusedSegment] = {}
        has_prev: set = set()
        for seg in self.segments:
            succ = self._device_successor(seg)
            if succ is not None:
                next_of[id(seg)] = succ
                has_prev.add(id(succ))
        out: List[Chain] = []
        for seg in self.segments:
            if id(seg) in has_prev:
                continue
            run = [seg]
            while id(run[-1]) in next_of:
                nxt = next_of[id(run[-1])]
                if any(s is nxt for s in run):  # cycle guard
                    break
                run.append(nxt)
            out.append(Chain(segments=run))
        return out
