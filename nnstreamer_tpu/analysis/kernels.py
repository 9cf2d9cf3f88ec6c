"""nns-kscope static analysis: VMEM residency, tile alignment, index-map
hazards and roofline cost rows for every registered Pallas kernel
(ops/pallas/registry.py) — derived abstractly. No device, nothing
allocated, nothing traced.

What kernel authors otherwise take on faith — "the blocks fit and the
DMA engine is fed" — becomes checkable facts:

- **VMEM residency** (NNS-W127): per grid step the Pallas pipeline
  keeps every operand/result block resident, DOUBLE-buffered when its
  index-map output changes between consecutive steps (that overlap is
  what hides the next DMA behind compute), plus all scratch. The sum
  must fit per-core VMEM (``[tpu] vmem_bytes``, default 16 MiB —
  costmodel.configured_vmem_bound).
- **Tile alignment** (NNS-W128): each of a block's last two dims must
  be the whole axis or a multiple of the hardware tile — last dim the
  128-wide lane, second-minor the dtype sublane (f32 8, bf16 16, int8
  32). The TPU compiler refuses any other block, a unit dim included.
- **Index-map hazards** (NNS-W128): the REAL index-map callables run
  over the REAL grid (with representative scalar-prefetch values),
  catching out-of-bounds block picks and prefetch shape drift
  statically.
- **Roofline row**: HBM traffic by index-map transition counting (a
  block refetches only when its index CHANGES between steps), FLOPs
  from the plan, arithmetic intensity = flops / hbm_bytes — the
  analysis/costmodel.py vocabulary extended to kernel granularity
  (costmodel.KernelCost).

:func:`pallas_request_pass` is the pipeline-level consumer (NNS-W129):
a pipeline that REQUESTS impl=pallas on an element whose kernel would
degrade to the jnp path (unsupported dtype, kill switch, a mode with no
kernel) is told at lint time, not by reading dispatch tallies after the
frames already ran. :func:`differential_sweep` and :func:`engage` are
the dynamic complements: parity (interpreted off-TPU, compiled on a
chip) vs each kernel's jnp reference, and dispatch-tally proof that a
requested pallas path actually engaged (docs/kernel-analysis.md).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from nnstreamer_tpu.analysis.costmodel import (
    KernelCost,
    configured_vmem_bound,
)
from nnstreamer_tpu.analysis.diagnostics import LintReport
from nnstreamer_tpu.ops.pallas import registry as kernel_registry
from nnstreamer_tpu.ops.pallas.registry import (
    BlockDesc,
    KernelSpec,
    LaunchPlan,
    ShapeCase,
)

#: TPU vector-register lane width: the last block dim tiles to this.
LANE = 128

#: dtype itemsize → minimum second-minor (sublane) tile.
SUBLANE = {4: 8, 2: 16, 1: 32}

#: grid-enumeration budget: beyond this many steps the walk stops and
#: varying-block fetch counts scale linearly (noted on the report).
GRID_ENUM_CAP = 100_000


def _np_dtype(name: str) -> np.dtype:
    """numpy dtype from a registry dtype name; ml_dtypes supplies the
    TPU dtypes plain numpy does not know (bfloat16, fp8)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, str(name)))


# -- per-case report ---------------------------------------------------------


@dataclass
class BlockReport:
    """One operand/result block's static verdicts for one shape case."""

    name: str
    kind: str                      # "in" | "out"
    array_shape: Tuple[int, ...]
    block_shape: Tuple[int, ...]
    dtype: str
    block_bytes: int               # one buffer
    buffers: int                   # 2 when the index map varies over grid
    fetches: int                   # estimated DMA transitions over the grid
    problems: List[str] = field(default_factory=list)

    @property
    def vmem_bytes(self) -> int:
        return self.block_bytes * self.buffers

    @property
    def hbm_bytes(self) -> int:
        return self.block_bytes * self.fetches


@dataclass
class CaseReport:
    """Everything nns-kscope derives for one kernel × shape case."""

    kernel: str
    case: str
    grid: Tuple[int, ...]
    steps: int                     # total grid steps
    enumerated: int                # steps actually walked (cap)
    vmem_bytes: int                # blocks (buffered) + scratch
    vmem_bound: int
    smem_bytes: int                # scalar-prefetch operands
    scratch_bytes: int
    cost: KernelCost
    blocks: List[BlockReport]
    hazards: List[str] = field(default_factory=list)
    notes: str = ""

    @property
    def over_budget(self) -> bool:
        return self.vmem_bytes > self.vmem_bound

    @property
    def misaligned(self) -> List[BlockReport]:
        return [b for b in self.blocks if b.problems]

    def to_row(self) -> Dict[str, Any]:
        return {
            "kernel": self.kernel,
            "case": self.case,
            "grid": list(self.grid),
            "steps": self.steps,
            "vmem_bytes": self.vmem_bytes,
            "vmem_bound": self.vmem_bound,
            "over_budget": self.over_budget,
            "smem_bytes": self.smem_bytes,
            "scratch_bytes": self.scratch_bytes,
            "hbm_read_bytes": self.cost.hbm_read_bytes,
            "hbm_write_bytes": self.cost.hbm_write_bytes,
            "flops": self.cost.flops,
            "arithmetic_intensity": self.cost.arithmetic_intensity,
            "misaligned": sorted(b.name for b in self.misaligned),
            "hazards": list(self.hazards),
            "notes": self.notes,
        }


# -- alignment ---------------------------------------------------------------


def _alignment_problems(b: BlockDesc) -> List[str]:
    """Lane/sublane tile verdicts for one block: the TPU compiler's
    block rule. The last two block dims must each be the whole axis or
    a multiple of the hardware tile — 128 lanes, and the dtype's
    sublane count — and it REFUSES anything else, a unit dim included:
    a ``(1, 1, 1, d)`` block over ``[B, 1, H, d]`` picks one head off
    the sublane axis, which no tiled DMA can do."""
    probs: List[str] = []
    if not b.block_shape:
        return probs
    dt = _np_dtype(b.dtype)
    last_b, last_a = b.block_shape[-1], b.array_shape[-1]
    if last_b != last_a and last_b % LANE:
        probs.append(
            f"last dim {last_b} is neither the whole axis ({last_a}) nor "
            f"a multiple of the {LANE}-wide lane tile"
        )
    sub = SUBLANE.get(dt.itemsize)
    if sub is not None and len(b.block_shape) >= 2:
        sec_b, sec_a = b.block_shape[-2], b.array_shape[-2]
        if sec_b != sec_a and sec_b % sub:
            probs.append(
                f"second-minor dim {sec_b} is neither the whole axis "
                f"({sec_a}) nor a multiple of the {dt.name} sublane "
                f"tile ({sub})"
            )
    return probs


# -- grid enumeration --------------------------------------------------------


def _prefetch_values(plan: LaunchPlan, hazards: List[str]) -> List[np.ndarray]:
    """Representative scalar-prefetch arrays for index-map enumeration;
    shape drift between ``make()`` and the declared SMEM shape is a
    hazard (the kernel would read garbage past the real rows)."""
    vals: List[np.ndarray] = []
    for p in plan.prefetch:
        arr: Optional[np.ndarray] = None
        if p.make is not None:
            try:
                arr = np.asarray(p.make())
            except Exception as exc:  # noqa: BLE001 - reported, not fatal
                hazards.append(
                    f"prefetch {p.name!r}: make() raised "
                    f"{type(exc).__name__}: {exc}"
                )
        if arr is not None and tuple(arr.shape) != tuple(p.shape):
            hazards.append(
                f"prefetch {p.name!r}: make() shape {tuple(arr.shape)} "
                f"drifts from the declared SMEM shape {tuple(p.shape)}"
            )
        if arr is None:
            arr = np.zeros(tuple(p.shape), dtype=np.int32)
        vals.append(arr)
    return vals


def _n_blocks(b: BlockDesc) -> Tuple[int, ...]:
    return tuple(
        -(-int(a) // int(k)) for a, k in zip(b.array_shape, b.block_shape)
    )


def _enumerate(plan: LaunchPlan):
    """Walk the grid row-major, calling every block's REAL index map
    with representative prefetch values. Returns
    ``(usage, hazards, total_steps, enumerated_steps)`` where usage maps
    block name → dict(fetches, varies, problem)."""
    hazards: List[str] = []
    prefetch = _prefetch_values(plan, hazards)
    total = 1
    for g in plan.grid:
        total *= int(g)
    usage: Dict[str, Dict[str, Any]] = {
        b.name: {"fetches": 0, "varies": False, "last": None, "problem": None}
        for b in plan.blocks
    }
    enumerated = min(total, GRID_ENUM_CAP)
    walker = itertools.product(*(range(int(g)) for g in plan.grid))
    for step, coords in enumerate(walker):
        if step >= enumerated:
            break
        for b in plan.blocks:
            u = usage[b.name]
            if u["problem"]:
                continue
            try:
                raw = b.index_map(*coords, *prefetch)
            except Exception as exc:  # noqa: BLE001 - reported, not fatal
                u["problem"] = (
                    f"index map raised {type(exc).__name__} at grid step "
                    f"{coords}: {exc}"
                )
                continue
            idx = tuple(int(v) for v in raw)
            if len(idx) != len(b.block_shape):
                u["problem"] = (
                    f"index map returns {len(idx)} coordinates for a "
                    f"rank-{len(b.block_shape)} block"
                )
                continue
            bounds = _n_blocks(b)
            if any(not 0 <= c < n for c, n in zip(idx, bounds)):
                u["problem"] = (
                    f"index map picks block {idx} outside the {bounds} "
                    f"block grid at step {coords}"
                )
                continue
            if idx != u["last"]:
                u["fetches"] += 1
                if u["last"] is not None:
                    u["varies"] = True
                u["last"] = idx
    return usage, hazards, total, enumerated


# -- the analyzer ------------------------------------------------------------


def analyze_case(
    spec: KernelSpec,
    case: Union[ShapeCase, str],
    bound: Optional[int] = None,
) -> CaseReport:
    """Static verdicts for one kernel × shape case."""
    if isinstance(case, str):
        case = next(c for c in spec.cases if c.name == case)
    plan = spec.plan(dict(case.params))
    vmem_bound = configured_vmem_bound() if bound is None else int(bound)
    usage, hazards, total, enumerated = _enumerate(plan)
    # linear scale-up for fetch counts past the enumeration cap; a
    # constant-index block fetched once stays once regardless of cap
    scale = (total / enumerated) if enumerated else 0.0
    notes = ""
    if total > enumerated:
        notes = (
            f"grid has {total} steps; walked {enumerated} and scaled "
            f"varying-block fetch counts linearly"
        )
    blocks: List[BlockReport] = []
    vmem = 0
    hbm_read = 0
    hbm_write = 0
    for b in plan.blocks:
        dt = _np_dtype(b.dtype)
        nbytes = int(np.prod(b.block_shape, dtype=np.int64)) * dt.itemsize
        u = usage[b.name]
        buffers = 2 if u["varies"] else 1
        fetches = (
            int(round(u["fetches"] * scale)) if u["varies"]
            else u["fetches"]
        )
        problems = _alignment_problems(b)
        if u["problem"]:
            problems.append(u["problem"])
        row = BlockReport(
            name=b.name, kind=b.kind,
            array_shape=tuple(b.array_shape),
            block_shape=tuple(b.block_shape),
            dtype=b.dtype, block_bytes=nbytes, buffers=buffers,
            fetches=fetches, problems=problems,
        )
        blocks.append(row)
        vmem += row.vmem_bytes
        if b.kind == "out":
            hbm_write += row.hbm_bytes
        else:
            hbm_read += row.hbm_bytes
    scratch_bytes = sum(
        int(np.prod(s.shape, dtype=np.int64)) * _np_dtype(s.dtype).itemsize
        for s in plan.scratch
    )
    smem_bytes = sum(
        int(np.prod(p.shape, dtype=np.int64)) * _np_dtype(p.dtype).itemsize
        for p in plan.prefetch
    )
    return CaseReport(
        kernel=spec.name, case=case.name,
        grid=tuple(int(g) for g in plan.grid),
        steps=total, enumerated=enumerated,
        vmem_bytes=vmem + scratch_bytes, vmem_bound=vmem_bound,
        smem_bytes=smem_bytes, scratch_bytes=scratch_bytes,
        cost=KernelCost(
            hbm_read_bytes=hbm_read, hbm_write_bytes=hbm_write,
            flops=int(plan.flops),
        ),
        blocks=blocks, hazards=hazards, notes=notes,
    )


def analyze(
    specs: Optional[Sequence[KernelSpec]] = None,
    bound: Optional[int] = None,
) -> Tuple[List[CaseReport], LintReport]:
    """Every registered kernel × shape case → case reports + a
    LintReport carrying NNS-W127 (VMEM over budget) and NNS-W128
    (misaligned tile / index-map hazard) findings."""
    if specs is None:
        specs = kernel_registry.all_specs()
    report = LintReport()
    reports: List[CaseReport] = []
    for spec in specs:
        for case in spec.cases:
            r = analyze_case(spec, case, bound)
            reports.append(r)
            where = f"{r.kernel}:{r.case}"
            if r.over_budget:
                report.add(
                    "NNS-W127", where,
                    f"per-grid-step VMEM residency {r.vmem_bytes} B "
                    f"(blocks double-buffered where their index varies, "
                    f"+ {r.scratch_bytes} B scratch) exceeds the "
                    f"{r.vmem_bound} B bound",
                    "shrink the block shapes (the pipeline refetches "
                    "more, but fits) or raise [tpu] vmem_bytes if the "
                    "target core really has more",
                )
            for blk in r.blocks:
                for p in blk.problems:
                    report.add(
                        "NNS-W128", where,
                        f"block {blk.name!r}: {p}",
                        "pick block dims that are whole axes or "
                        "multiples of the dtype tile (lane 128; sublane "
                        "8/16/32 for 4/2/1-byte dtypes), and index maps "
                        "that stay inside the block grid",
                    )
            for h in r.hazards:
                report.add(
                    "NNS-W128", where, h,
                    "keep the PrefetchDesc declared shape and its "
                    "make() in lockstep — the kernel indexes SMEM by "
                    "the declared shape",
                )
    return reports, report


# -- dynamic complements: parity sweep + engagement proof --------------------


def _leaf_pairs(got: Any, want: Any) -> Iterable[Tuple[Any, Any]]:
    if isinstance(got, (tuple, list)):
        for g, w in zip(got, want):
            yield from _leaf_pairs(g, w)
    else:
        yield got, want


def _max_err(got: Any, want: Any, atol: float) -> float:
    """Compare in float64 (uint8 differences would wrap) and raise on
    mismatch; returns the max abs error across all leaves."""
    worst = 0.0
    for g, w in _leaf_pairs(got, want):
        ga = np.asarray(g, dtype=np.float64)
        wa = np.asarray(w, dtype=np.float64)
        np.testing.assert_allclose(ga, wa, atol=atol, rtol=1e-5)
        if ga.size:
            worst = max(worst, float(np.max(np.abs(ga - wa))))
    return worst


def differential_sweep(
    specs: Optional[Sequence[KernelSpec]] = None,
    full: bool = False,
) -> List[Dict[str, Any]]:
    """Interpret-mode parity: run every kernel against its jnp
    reference over the tier-1 shape subset (``full=True`` takes the
    whole grid — the `slow` sweep). One row per kernel × case."""
    if specs is None:
        specs = kernel_registry.all_specs()
    rows: List[Dict[str, Any]] = []
    for spec in specs:
        cases = spec.cases if full else spec.tier1_cases()
        for case in cases:
            row: Dict[str, Any] = {
                "kernel": spec.name, "case": case.name,
                "ok": True, "max_err": 0.0, "error": None,
            }
            try:
                got, want, atol = spec.run_case(dict(case.params))
                row["max_err"] = _max_err(got, want, atol)
            except Exception as exc:  # noqa: BLE001 - one row per failure
                row["ok"] = False
                row["error"] = f"{type(exc).__name__}: {exc}"
            rows.append(row)
    return rows


def engage(
    specs: Optional[Sequence[KernelSpec]] = None,
) -> List[Dict[str, Any]]:
    """Dispatch-tally proof that each kernel's requested pallas path
    engages: snapshot the tally, run the spec's tiny probe (explicit
    impl=pallas through the public op), and diff. A row is ``ok`` only
    when the probe ran clean AND the op dispatched to pallas and
    nothing else — a silent jnp fallback fails the row (the
    ``nns-kscope --engage`` / ``bench.py --capture-tpu`` contract)."""
    from nnstreamer_tpu.ops import dispatch

    if specs is None:
        specs = kernel_registry.all_specs()
    rows: List[Dict[str, Any]] = []
    for spec in specs:
        snap = dispatch.tally.snapshot()
        error: Optional[str] = None
        try:
            spec.probe()
        except Exception as exc:  # noqa: BLE001 - one row per failure
            error = f"{type(exc).__name__}: {exc}"
        impls = dispatch.engaged_impls(spec.dispatch_op, snap)
        rows.append({
            "kernel": spec.name,
            "op": spec.dispatch_op,
            "impls": impls,
            "ok": error is None and impls == ["pallas"],
            "error": error,
        })
    return rows


# -- pipeline-level pass (NNS-W129) ------------------------------------------

#: tensor_transform image modes with a Pallas kernel behind them.
_TRANSFORM_KERNELS = {
    "resize": "resize_bilinear",
    "crop-resize": "crop_and_resize",
}


def _transform_input_dtype(pipeline, specs, e) -> Optional[str]:
    """The dtype the transform's kernel would see: the image tensor of
    the upstream out spec (first rank≥3 tensor, else the first)."""
    for link in pipeline.in_links(e):
        up = specs.get(link.src.name)
        if not up or link.src_pad >= len(up):
            continue
        spec = up[link.src_pad]
        tensors = getattr(spec, "tensors", None)
        if not tensors:
            continue
        img = next((t for t in tensors if t.rank >= 3), tensors[0])
        try:
            return np.dtype(img.dtype.np_dtype).name
        except Exception:  # noqa: BLE001 - dtype stays unknown
            return None
    return None


def pallas_request_pass(pipeline, report: LintReport, specs) -> None:
    """NNS-W129: the pipeline REQUESTS a pallas implementation that
    would dispatch the jnp/xla path — an unsupported dtype, the
    NNS_TPU_PALLAS_DISABLE kill switch, or a mode with no kernel at
    all. Runs as a lint() pass after spec negotiation (the specs dict
    supplies the upstream dtypes)."""
    from nnstreamer_tpu.ops.pallas._compat import pallas_ok

    for e in pipeline.elements:
        factory = getattr(type(e), "FACTORY_NAME", "")
        if factory == "tensor_transform":
            if str(e.get_property("impl", "auto") or "auto").lower() != (
                "pallas"
            ):
                continue
            mode = str(e.get_property("mode", "") or "").lower()
            kernel = _TRANSFORM_KERNELS.get(mode)
            if kernel is None:
                report.add(
                    "NNS-W129", e.name,
                    f"impl=pallas requested but mode={mode} has no "
                    "Pallas kernel; every frame runs the jnp path",
                    "only resize / crop-resize dispatch to kernels — "
                    "drop impl=pallas or switch modes",
                )
                continue
            dtype = _transform_input_dtype(pipeline, specs, e)
            ok, reason = pallas_ok(kernel, dtype)
            if not ok:
                report.add(
                    "NNS-W129", e.name,
                    f"impl=pallas requested but {kernel} would dispatch "
                    f"jnp: {reason}",
                    "fix the input dtype (or clear "
                    "NNS_TPU_PALLAS_DISABLE) so the requested kernel "
                    "can engage, or drop impl=pallas",
                )
        elif factory == "tensor_llm_serversink":
            impl = str(e.get_property("attn-impl", "") or "").strip()
            if impl.lower() != "pallas":
                continue
            from nnstreamer_tpu.config import conf

            layout = str(e.get_property("kv-layout", "") or "").strip() or (
                conf().get("llm", "kv_layout", "slot")
            )
            if str(e.get_property("plane", "") or "").strip() and (
                layout == "slot"
                and not str(e.get_property("kv-layout", "") or "").strip()
            ):
                layout = "paged"  # plane= implies the shared paged batcher
            kernel = (
                "paged_decode_attention" if layout == "paged"
                else "decode_attention"
            )
            cache_dtype = str(
                e.get_property("cache-dtype", "auto") or "auto"
            ).strip()
            dtype = "int8" if cache_dtype == "int8" else "float32"
            ok, reason = pallas_ok(kernel, dtype)
            if not ok:
                report.add(
                    "NNS-W129", e.name,
                    f"attn-impl=pallas requested but {kernel} would "
                    f"dispatch xla: {reason}",
                    "fix cache-dtype (or clear NNS_TPU_PALLAS_DISABLE) "
                    "so the serving attention kernel can engage, or "
                    "drop attn-impl=pallas",
                )
