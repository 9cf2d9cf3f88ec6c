"""nns-xray: whole-chain compile-unit inference and jaxpr lint.

``nns-lint`` answers "is this pipeline wired right"; this module
answers "what will XLA actually compile, and what will it cost". From
a launch string (or constructed Pipeline) it compiles the plan the
executor would run and reports at CHAIN granularity
(:meth:`ExecPlan.chains` — maximal runs of fused segments joined by
device-resident handoffs, the span ROADMAP item 1 would compile into
one resident program):

- **compile units** — which elements land in which chain, and what
  severs the chains (docs/chain-analysis.md);
- **jaxpr lint** — each segment's composed program traced abstractly
  (``jax.make_jaxpr``, no device work) and walked for silent f64/dtype
  promotion (NNS-W122), host callbacks inside a would-be-resident
  chain (NNS-W120), donation-defeating outputs (NNS-W123 via the same
  ``_aliasable_argnums`` the executor donates with), and jit-cache-key
  cardinality hazards from the bucket ladder (NNS-W121);
- **cost model** — per-chain params/activation/transient-HBM bytes and
  predicted per-frame host-transfer bytes at every boundary
  (analysis/costmodel.py), checked against the declared device bound
  (NNS-W124) and verifiable at runtime against ``TransferTally``
  (``Executor.transfer_crosscheck``, ``NNS_XRAY_CROSSCHECK``);
- **kernel dispatch** — :func:`dispatch_table` proves which Pallas/jnp
  implementation each dual-path op engages (ops/dispatch.py).

The shared static predicates (``device_capable`` & co.) moved here
from lint's resident-handoff pass, which now imports them — the two
analyzers can never disagree about what splits a chain.

Pipelines are never started. Stateful serving elements
(``LINT_SKIP_NEGOTIATE``) and pipelines whose negotiation fails (e.g.
doc snippets naming absent model files) degrade to notes-only results
with zero diagnostics.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from nnstreamer_tpu.analysis.costmodel import (
    ChainCost,
    TransferBoundary,
    chain_cost,
    configured_device_bound,
    plan_transfer_boundaries,
    predict_frame_transfers,
)
from nnstreamer_tpu.analysis.diagnostics import Diagnostic, LintReport
from nnstreamer_tpu.log import get_logger

_log = get_logger("xray")

# past this many jit-cache keys for ONE segment, steady state is still
# compiling (bucket ladders are O(log max-batch), so a healthy segment
# sits far below)
_CACHE_KEY_BOUND = 32
# donated-but-unreusable buffers below this are noise, not a finding
_DONATION_MIN_BYTES = 1 << 20
_HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "callback", "outside_call",
    "host_callback_call", "debug_callback",
})


# -- shared static predicates ------------------------------------------------
# Used by BOTH lint's resident-handoff pass (analysis/lint.py) and the
# chain passes below. Everything reads element/backend CLASSES — no
# backend open, no model load, no negotiation.

def device_capable(e: Any) -> bool:
    """A tensor_filter that will trace into a fused device segment:
    explicit registered framework whose backend class overrides
    ``traceable_fn``, no fallback-framework, no replica fan-out."""
    from nnstreamer_tpu import registry
    from nnstreamer_tpu.backends.base import Backend
    from nnstreamer_tpu.elements.filter import TensorFilter

    if not isinstance(e, TensorFilter):
        return False
    fw = e.get_property("framework")
    if not fw or str(fw) == "auto":
        return False
    if e.get_property("fallback-framework"):
        return False  # deliberate per-frame fusion barrier
    try:
        if int(e.get_property("replicas") or 0) > 1:
            return False  # idem
    except (TypeError, ValueError):
        pass
    try:
        cls = registry.get(registry.KIND_FILTER, str(fw))
    except KeyError:
        return False  # unknown framework has its own diagnostic
    return cls.traceable_fn is not Backend.traceable_fn


def transparent(e: Any) -> bool:
    """Plumbing a device array rides through untouched: thread/buffer
    boundaries and fan-out that never read tensor bytes."""
    from nnstreamer_tpu.elements.flow import CapsFilter, Queue, Tee

    return isinstance(e, (Queue, CapsFilter, Tee))


def host_bound(e: Any) -> bool:
    """Elements that read/produce tensor bytes on host. Routing
    (mux/demux/split/join) regroups frames without touching bytes, so
    it passes device arrays through; traceable TensorOps
    (tensor_transform, device filters) FUSE into the chain — no split
    to warn about."""
    from nnstreamer_tpu import registry
    from nnstreamer_tpu.backends.base import Backend
    from nnstreamer_tpu.elements.base import Routing, TensorOp
    from nnstreamer_tpu.elements.filter import TensorFilter

    if transparent(e) or isinstance(e, Routing):
        return False
    if isinstance(e, TensorFilter):
        fw = e.get_property("framework")
        if not fw or str(fw) == "auto":
            return False  # can't tell statically; never open here
        try:
            cls = registry.get(registry.KIND_FILTER, str(fw))
        except KeyError:
            return False
        return cls.traceable_fn is Backend.traceable_fn
    if isinstance(e, TensorOp):
        try:
            return not e.is_traceable()
        except Exception:  # noqa: BLE001 — can't tell without opening
            return False
    return hasattr(e, "host_process")


def host_postproc_with_device_path(e: Any) -> bool:
    """NNS-W116's static capability read (no negotiation, no
    model/labels load): a tensor_decoder that will RUN host
    (postproc=host, or postproc=auto with a subplugin that offers no
    auto-fuse make_fn) while its subplugin declares a device decode
    path for these options."""
    from nnstreamer_tpu import registry
    from nnstreamer_tpu.elements.decoder import TensorDecoder

    if not isinstance(e, TensorDecoder):
        return False
    if e.postproc == "device" or e.mode == "custom-code":
        return False
    try:
        cls = registry.get(registry.KIND_DECODER, e.mode)
    except KeyError:
        return False  # unknown mode has its own diagnostic
    probe = getattr(cls, "device_capable", None)
    if probe is None or not probe(e.options):
        return False
    if e.postproc == "auto" and getattr(cls, "make_fn", None) is not None:
        return False  # auto already fuses this subplugin
    return True


def decoder_will_fuse(e: Any) -> bool:
    """Decoders whose is_traceable() is False only because lint never
    negotiates: postproc=device always fuses (or fails negotiation
    loudly), and auto fuses subplugins that offer a make_fn for these
    options (image_labeling without labels)."""
    from nnstreamer_tpu import registry
    from nnstreamer_tpu.elements.decoder import TensorDecoder

    if not isinstance(e, TensorDecoder) or e.mode == "custom-code":
        return False
    if e.postproc == "device":
        return True
    if e.postproc != "auto":
        return False
    try:
        cls = registry.get(registry.KIND_DECODER, e.mode)
    except KeyError:
        return False
    if getattr(cls, "make_fn", None) is None:
        return False
    probe = getattr(cls, "device_capable", None)
    return probe is None or bool(probe(e.options))


def reaches_capable(e: Any, links: Callable[[Any], List[Any]]) -> bool:
    """A device-capable filter is reachable from ``e`` across only
    transparent plumbing (the resident handoff's span)."""
    seen = {e}
    frontier = [n for n in links(e) if n not in seen]
    while frontier:
        n = frontier.pop()
        if n in seen:
            continue
        seen.add(n)
        if device_capable(n):
            return True
        if transparent(n):
            frontier.extend(links(n))
    return False


# -- result types ------------------------------------------------------------

@dataclass
class ChainReport:
    """One compile unit's analysis row.

    ``compiled`` is the executor's OWN verdict for the whole-chain
    resident program (pipeline/chain_program.py ``decide_chain`` — the
    same function ``Executor._build`` calls, so the report can never
    disagree with what actually runs): ``yes (unroll K)``, or ``no:``
    followed by the blocking hazard/config."""

    name: str
    segments: List[str]
    n_ops: int
    cost: ChainCost
    notes: List[str] = field(default_factory=list)
    compiled: str = ""


@dataclass
class XrayResult:
    """Chain analysis outcome: compile units + costs + diagnostics.
    ``degraded`` means the pipeline could not be compiled here
    (stateful serving elements, absent model files) and only notes are
    available — by design zero W120–W124."""

    report: LintReport
    pipeline: Optional[Any] = None
    plan: Optional[Any] = None
    chains: List[ChainReport] = field(default_factory=list)
    boundaries: List[TransferBoundary] = field(default_factory=list)
    predicted: Dict[str, int] = field(default_factory=dict)
    predicted_tpu: Dict[str, int] = field(default_factory=dict)
    dispatch: List[Dict[str, Any]] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)
    errors: List[str] = field(default_factory=list)
    degraded: bool = False

    @property
    def diagnostics(self) -> List[Diagnostic]:
        return self.report.diagnostics

    @property
    def codes(self) -> List[str]:
        return self.report.codes

    @property
    def exit_code(self) -> int:
        if self.errors:
            return 2
        return self.report.exit_code

    def render(self) -> str:
        lines: List[str] = []
        for err in self.errors:
            lines.append(f"error: {err}")
        for note in self.notes:
            lines.append(f"note: {note}")
        lines.append(f"compile units: {len(self.chains)}")
        for ch in self.chains:
            lines.append(
                f"  chain [{ch.name}]: {ch.n_ops} op(s) in "
                f"{len(ch.segments)} segment(s)"
            )
            c = ch.cost
            lines.append(
                f"    params {_fmt_bytes(c.params_bytes)}, activations "
                f"{_fmt_bytes(c.activation_bytes)}, peak transient "
                f"{_fmt_bytes(c.transient_bytes)}, boundary in/out "
                f"{_fmt_bytes(c.boundary_in_bytes)}/"
                f"{_fmt_bytes(c.boundary_out_bytes)} per frame"
            )
            if ch.compiled:
                lines.append(f"    compiled: {ch.compiled}")
            for note in ch.notes:
                lines.append(f"    note: {note}")
        for b in self.boundaries:
            lines.append(
                f"  boundary {b.direction} {b.producer} -> {b.consumer} "
                f"({b.reason}): {_fmt_bytes(b.bytes_per_frame)}/frame"
            )
        if self.predicted:
            lines.append(
                f"predicted per-frame transfer here: "
                f"h2d={self.predicted['h2d']} d2h={self.predicted['d2h']}"
                f"  (on tpu: h2d={self.predicted_tpu['h2d']} "
                f"d2h={self.predicted_tpu['d2h']})"
            )
        for d in self.diagnostics:
            lines.append(str(d))
        if self.dispatch:
            lines.append("kernel dispatch (impl=auto):")
            for row in self.dispatch:
                measured = ",".join(row["measured"]) or "-"
                lines.append(
                    f"  {row['op']}: on-tpu={row['auto_on_tpu']} "
                    f"here={row['auto_here']} measured={measured}"
                    + (f" ({row['error']})" if row.get("error") else "")
                )
        return "\n".join(lines)


def _fmt_bytes(n: int) -> str:
    size = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if size < 1024 or unit == "GiB":
            return (
                f"{int(size)} {unit}" if unit == "B"
                else f"{size:.1f} {unit}"
            )
        size /= 1024
    return f"{n} B"


# -- jaxpr lint --------------------------------------------------------------

def _sub_jaxprs(v: Any) -> List[Any]:
    out = []
    vals = v if isinstance(v, (list, tuple)) else [v]
    for x in vals:
        x = getattr(x, "jaxpr", x)  # ClosedJaxpr → Jaxpr
        if hasattr(x, "eqns"):
            out.append(x)
    return out


def _iter_eqns(jaxpr: Any):
    """Every equation, recursing into sub-jaxprs (scan/cond/pjit
    bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def segment_jaxpr(seg: Any) -> Optional[Any]:
    """The segment's composed program traced abstractly at its
    negotiated per-frame signature (``jax.make_jaxpr`` over
    ShapeDtypeStructs — no device work). None when the input spec is
    flexible."""
    import jax

    sig = seg._negotiated_sig()
    if sig is None:
        return None
    shapes = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in sig]
    return jax.make_jaxpr(seg._compose())(*shapes)


def _is_wide(dtype: Any) -> bool:
    d = np.dtype(dtype)
    return d.kind in "fc" and d.itemsize >= 8


def dtype_findings(
    jaxpr: Any, declared_out: Optional[Tuple] = None
) -> List[str]:
    """NNS-W122 walker: silent f64/complex128 promotion (a wide value
    appears with no wide input) and traced-vs-negotiated output dtype
    drift. Pure jaxpr arithmetic — callable directly in tests under
    ``jax.enable_x64``."""
    msgs: List[str] = []
    if not any(_is_wide(a.dtype) for a in jaxpr.in_avals):
        for eqn in _iter_eqns(jaxpr.jaxpr):
            wide = [
                np.dtype(v.aval.dtype).name
                for v in eqn.outvars
                if getattr(getattr(v, "aval", None), "dtype", None)
                is not None and _is_wide(v.aval.dtype)
            ]
            if wide:
                msgs.append(
                    f"`{eqn.primitive.name}` produces {wide[0]} with no "
                    f"64-bit input"
                )
                break  # one promotion site is enough evidence
    if declared_out:
        for i, (aval, want) in enumerate(zip(jaxpr.out_avals, declared_out)):
            if np.dtype(aval.dtype) != np.dtype(want):
                msgs.append(
                    f"output {i} traces as {np.dtype(aval.dtype).name} "
                    f"but negotiated {np.dtype(want).name}"
                )
    return msgs


def host_callback_prims(jaxpr: Any) -> List[str]:
    """NNS-W120 walker: host-callback primitives inside a device
    program (each invocation round-trips through Python + host
    memory)."""
    found = []
    for eqn in _iter_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in _HOST_CALLBACK_PRIMS and name not in found:
            found.append(name)
    return found


def cache_key_finding(seg: Any) -> Optional[str]:
    """NNS-W121: unbounded or exploding jit-cache key space for one
    segment."""
    sig = seg._negotiated_sig()
    cfg = seg.batch_config
    active = bool(
        cfg is not None and getattr(cfg, "active", False)
        and getattr(cfg, "buckets", ())
    )
    if sig is None and active:
        return (
            "flexible per-frame input spec under micro-batching: every "
            "distinct arriving shape multiplies the bucket ladder "
            f"({len(cfg.buckets)} buckets) into fresh XLA compiles — "
            "the cache key space is unbounded"
        )
    if sig is not None and active:
        n_keys = (len(cfg.buckets) + 1) * (2 if seg.donate else 1)
        if n_keys > _CACHE_KEY_BOUND:
            return (
                f"{n_keys} jit-cache keys for one segment (buckets x "
                "donation variants): steady state keeps compiling"
            )
    return None


def donation_finding(seg: Any) -> Optional[str]:
    """NNS-W123: the segment streams with donated buffers but XLA can
    reuse none of them (no output shape/dtype-matches any input).
    Checked on the path that actually donates at runtime: the batched
    stacked-window program when micro-batching is active, else the
    per-frame staging program (which only donates off-CPU —
    pipeline/graph.py ``build``), so a CPU-only run without batching
    never false-positives."""
    from nnstreamer_tpu.pipeline.transfer import default_backend_is_cpu

    sig = seg._negotiated_sig()
    if sig is None or not seg.donate or (seg.ring_depth or 1) <= 1:
        return None
    cfg = seg.batch_config
    batched = bool(
        cfg is not None and getattr(cfg, "active", False)
        and getattr(cfg, "buckets", ())
    )
    if not batched and default_backend_is_cpu():
        return None  # the per-frame path never donates on local CPU
    bucket = int(cfg.buckets[-1]) if batched else 0
    in_bytes = sum(
        int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        for shape, dtype in sig
    ) * max(1, bucket)
    if in_bytes < _DONATION_MIN_BYTES:
        return None
    try:
        import jax

        composed = seg._compose()
        target = jax.vmap(composed) if bucket else composed
        argnums = seg._aliasable_argnums(target, sig, bucket)
    except Exception:  # noqa: BLE001 — untraceable here: no verdict
        return None
    if argnums:
        return None
    return (
        f"donate is on (ring-depth {seg.ring_depth}) but no output "
        f"matches any input's shape/dtype: {_fmt_bytes(in_bytes)} donated "
        "per dispatch with nothing reused — every frame pays a fresh "
        "output allocation"
    )


# -- chain passes ------------------------------------------------------------

def _nearest_segment(plan: Any, e: Any, links: Callable) -> Optional[Any]:
    seen: set = set()
    frontier = list(links(e))
    while frontier:
        n = frontier.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        seg = plan.seg_of.get(n)
        if seg is not None:
            return seg
        if transparent(n):
            frontier.extend(links(n))
    return None


def _host_split_pass(plan: Any, chains: List[Any], report: LintReport) -> None:
    """NNS-W120 (structural): a host-path tensor op with a fused
    segment on BOTH sides — the two chains around it would be one
    compile unit if the op had a device path. Decoders that W116
    already pinpoints (device path exists, one property away) are
    excluded: one code per boundary."""
    from nnstreamer_tpu.elements.base import Routing, TensorOp

    pipeline = plan.pipeline
    chain_of = {
        id(seg): ch for ch in chains for seg in ch.segments
    }

    def ups(e):
        return [ln.src for ln in pipeline.in_links(e)]

    def downs(e):
        return [ln.dst for ln in pipeline.out_links(e)]

    for e in pipeline.elements:
        if not isinstance(e, TensorOp) or e in plan.seg_of:
            continue
        if transparent(e) or isinstance(e, Routing):
            continue
        if decoder_will_fuse(e) or host_postproc_with_device_path(e):
            continue  # W116 territory (one-property fix; lint emits it)
        up_seg = _nearest_segment(plan, e, ups)
        down_seg = _nearest_segment(plan, e, downs)
        if up_seg is None or down_seg is None:
            continue
        up_name = chain_of[id(up_seg)].name
        down_name = chain_of[id(down_seg)].name
        report.add(
            "NNS-W120", e.name,
            f"host-path op severs the chain: [{up_name}] and "
            f"[{down_name}] would be ONE compile unit, but every frame "
            "materializes to host and re-stages to device here",
            "give this op a device-capable framework/traceable path, or "
            "move it outside the device span (docs/chain-analysis.md)",
        )


def _segment_pass(
    seg: Any, report: LintReport, notes: List[str]
) -> None:
    jaxpr = None
    try:
        jaxpr = segment_jaxpr(seg)
    except Exception as exc:  # noqa: BLE001 — trace is best-effort
        notes.append(f"{seg.name}: trace unavailable ({exc})")
    if jaxpr is not None:
        for prim in host_callback_prims(jaxpr):
            report.add(
                "NNS-W120", seg.first.name,
                f"host callback `{prim}` inside device segment "
                f"{seg.name}: every invocation round-trips through "
                "Python and host memory, and the chain can never become "
                "one resident program",
                "compute in-graph, or split the callback into an "
                "explicit host element (docs/chain-analysis.md)",
            )
        declared = None
        out_spec = seg.last.out_specs[0] if seg.last.out_specs else None
        if out_spec is not None and getattr(out_spec, "is_static", False):
            declared = tuple(t.dtype.np_dtype for t in out_spec)
        for msg in dtype_findings(jaxpr, declared):
            report.add(
                "NNS-W122", seg.first.name,
                f"segment {seg.name}: {msg}",
                "pin dtypes explicitly (astype at the boundary) — on "
                "TPU 64-bit math is emulated and doubles activation "
                "bytes (docs/chain-analysis.md)",
            )
        for i, v in enumerate(jaxpr.jaxpr.outvars):
            if any(v is iv for iv in jaxpr.jaxpr.invars):
                notes.append(
                    f"{seg.name}: output {i} is an untouched passthrough "
                    "of an input (dead compute path?)"
                )
    msg = cache_key_finding(seg)
    if msg is not None:
        report.add(
            "NNS-W121", seg.first.name,
            f"segment {seg.name}: {msg}",
            "declare static dimensions upstream (capsfilter / source "
            "dimensions=) or disable batching on this segment "
            "(docs/chain-analysis.md)",
        )
    msg = donation_finding(seg)
    if msg is not None:
        report.add(
            "NNS-W123", seg.first.name,
            f"segment {seg.name}: {msg}",
            "match an output to an input shape/dtype (in-place-style "
            "update) or set donate=false for this segment "
            "(docs/chain-analysis.md)",
        )


def _bound_pass(chain: Any, cost: ChainCost, report: LintReport) -> None:
    bound = configured_device_bound()
    if bound is None or cost.resident_bytes <= bound:
        return
    report.add(
        "NNS-W124", chain.first.name,
        f"chain [{chain.name}]: resident "
        f"{_fmt_bytes(cost.resident_bytes)} (params "
        f"{_fmt_bytes(cost.params_bytes)} + peak transient "
        f"{_fmt_bytes(cost.transient_bytes)} at the max micro-batch "
        f"bucket) exceeds [plane] memory_per_device {_fmt_bytes(bound)}",
        "shrink the max batch bucket, split the chain across devices "
        "(serving_plane placement), or raise the bound "
        "(docs/chain-analysis.md)",
    )


def _compiled_pass(
    plan: Any, chain: Any, cr: "ChainReport", report: LintReport
) -> None:
    """Fill the chain report's ``compiled`` column from the executor's
    own verdict (pipeline/chain_program.py ``decide_chain``) and emit
    NNS-W125 for the one configuration the lint exists for: a
    hazard-free multi-segment chain someone switched OFF — leaving a
    per-node-per-frame dispatch cost the compiled path would remove."""
    from nnstreamer_tpu.pipeline.chain_program import decide_chain

    try:
        d = decide_chain(plan, chain)
    except Exception as exc:  # noqa: BLE001 — verdict is best-effort here
        cr.compiled = f"no: verdict unavailable ({exc})"
        return
    if d.compiles:
        cr.compiled = f"yes (unroll {d.unroll})"
        return
    if d.eligible:  # and therefore mode == "off"
        cr.compiled = "no: chain_mode=off"
        report.add(
            "NNS-W125", chain.first.name,
            f"chain [{chain.name}]: {len(chain.segments)} hazard-free "
            "segments are running with chain_mode=off — every frame "
            "crosses one service thread per node where ONE resident "
            "program (dispatched once per unrolled window) would serve "
            "it",
            "set [executor] chain_mode=auto (or drop the chain-mode=off "
            "property) to compile this chain; keep off only while "
            "debugging against the per-node parity oracle "
            "(docs/chain-analysis.md)",
        )
        return
    cr.compiled = f"no: {d.reason}"


# -- entry point -------------------------------------------------------------

def xray(
    target: Union[str, Any], open_backends: bool = True
) -> XrayResult:
    """Analyze a launch string or constructed Pipeline at chain
    granularity. Compiles the plan (negotiation runs — tensor_filter
    backends open exactly as the executor would open them; nothing is
    started). ``open_backends=False`` skips params estimation in the
    cost model."""
    report = LintReport()
    res = XrayResult(report=report)
    if isinstance(target, str):
        from nnstreamer_tpu.pipeline.parse import parse_pipeline

        try:
            pipeline = parse_pipeline(target)
        except Exception as exc:  # noqa: BLE001 — surfaced as the result
            res.errors.append(f"parse failed: {exc}")
            return res
    else:
        pipeline = target
    res.pipeline = pipeline
    skip = [
        e.name for e in pipeline.elements if type(e).LINT_SKIP_NEGOTIATE
    ]
    if skip:
        res.degraded = True
        res.notes.append(
            "negotiation skipped (stateful serving elements: "
            f"{', '.join(skip)}); chain analysis unavailable"
        )
        return res
    try:
        plan = pipeline.compile_plan()
    except Exception as exc:  # noqa: BLE001 — degrade, lint owns the error
        res.degraded = True
        res.notes.append(
            f"compile_plan failed ({exc}); chain analysis unavailable"
        )
        return res
    res.plan = plan
    chains = plan.chains()
    res.boundaries = plan_transfer_boundaries(plan)
    res.predicted = predict_frame_transfers(plan)
    res.predicted_tpu = predict_frame_transfers(plan, assume_tpu=True)
    _host_split_pass(plan, chains, report)
    for chain in chains:
        cost = chain_cost(chain, open_backends=open_backends)
        cr = ChainReport(
            name=chain.name,
            segments=[s.name for s in chain.segments],
            n_ops=len(chain.ops),
            cost=cost,
        )
        for seg in chain.segments:
            _segment_pass(seg, report, cr.notes)
        _bound_pass(chain, cost, report)
        _compiled_pass(plan, chain, cr, report)
        res.chains.append(cr)
    return res


# -- kernel dispatch table ---------------------------------------------------

def _probe_crop() -> None:
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.image import crop_and_resize

    crop_and_resize(
        jnp.zeros((8, 8, 3), jnp.float32),
        jnp.asarray([[0.0, 0.0, 4.0, 4.0]], jnp.float32), 4, 4,
    )


def _probe_resize() -> None:
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.image import resize_bilinear

    resize_bilinear(jnp.zeros((8, 8, 3), jnp.float32), 4, 4)


def _probe_nms() -> None:
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.detection import nms

    nms(
        jnp.zeros((4, 4), jnp.float32), jnp.zeros((4,), jnp.float32),
        0.5, 2,
    )


def _probe_block_attn() -> None:
    import jax.numpy as jnp

    from nnstreamer_tpu.kv.block_attn import block_attention

    b, h, hd, bs = 1, 2, 4, 2
    block_attention(
        jnp.zeros((b, 1, h, hd), jnp.float32),
        jnp.zeros((4, bs, h, hd), jnp.float32),
        jnp.zeros((4, bs, h, hd), jnp.float32),
        jnp.zeros((b, 2), jnp.int32),
        jnp.zeros((b,), jnp.int32),
        (
            jnp.zeros((b, 1, h, hd), jnp.float32),
            jnp.zeros((b, 1, h, hd), jnp.float32),
        ),
    )


_DISPATCH_PROBES: List[Tuple[str, Optional[Callable[[], None]]]] = [
    ("crop_and_resize", _probe_crop),
    ("resize_bilinear", _probe_resize),
    ("nms", _probe_nms),
    ("block_attention", _probe_block_attn),
    ("serving_attention", None),  # construction-time dispatch: static row
]


def dispatch_table(run: bool = True) -> List[Dict[str, Any]]:
    """Which implementation each dual-path op engages under
    ``impl="auto"``: the static decision for TPU and for THIS backend,
    plus — with ``run=True`` — the impls actually measured by invoking
    each op on tiny inputs and diffing the dispatch tally
    (ops/dispatch.py). The dispatch record lands at the branch point
    before any math, so even a probe that fails numerically still
    proves its dispatch."""
    import jax

    from nnstreamer_tpu.ops import dispatch as disp

    on_tpu = jax.default_backend() == "tpu"
    rows: List[Dict[str, Any]] = []
    for op, probe in _DISPATCH_PROBES:
        fallback = "xla" if op == "serving_attention" else "jnp"
        before = disp.tally.snapshot()
        err = None
        if run and probe is not None:
            try:
                probe()
            except Exception as exc:  # noqa: BLE001 — probe is best-effort
                err = f"probe failed: {exc}"
        rows.append({
            "op": op,
            "auto_on_tpu": "pallas",
            "auto_here": "pallas" if on_tpu else fallback,
            "measured": (
                disp.engaged_impls(op, before)
                if run and probe is not None else []
            ),
            "error": err,
        })
    return rows
