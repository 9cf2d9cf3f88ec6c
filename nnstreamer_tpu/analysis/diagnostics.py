"""Structured lint diagnostics (the reference's gst-validate report model:
one issue-type registry, many reports per run, never fail-fast).

Every problem `nns-lint` can find has a stable code in the ``NNS-Exxx``
(error) / ``NNS-Wxxx`` (warning) namespace so scripts and CI can match on
codes instead of message text. The catalog below is the single source of
truth; docs/linting.md renders from the same table.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


class Severity(enum.Enum):
    ERROR = "error"
    WARNING = "warning"

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return self.value


# code → (severity, slug, one-line description)
CATALOG: Dict[str, Tuple[Severity, str, str]] = {
    "NNS-E001": (
        Severity.ERROR, "unlinked-sink-pad",
        "an element's required sink pad has nothing linked to it",
    ),
    "NNS-E002": (
        Severity.ERROR, "cycle",
        "the pipeline graph contains a cycle (use tensor_repo for loops)",
    ),
    "NNS-E003": (
        Severity.ERROR, "caps-mismatch",
        "spec negotiation would fail on this element at build time",
    ),
    "NNS-E004": (
        Severity.ERROR, "unknown-element",
        "no element factory registered under this name",
    ),
    "NNS-E005": (
        Severity.ERROR, "bad-property-value",
        "a property value cannot be coerced to its declared type",
    ),
    "NNS-E006": (
        Severity.ERROR, "unknown-framework",
        "tensor_filter framework= names no registered backend",
    ),
    "NNS-E007": (
        Severity.ERROR, "unknown-decoder",
        "tensor_decoder mode= names no registered decoder subplugin",
    ),
    "NNS-E008": (
        Severity.ERROR, "unknown-converter",
        "tensor_converter mode= names no registered converter subplugin",
    ),
    "NNS-E009": (
        Severity.ERROR, "parse-error",
        "the launch string does not parse (bad token, dangling '!', ...)",
    ),
    "NNS-E010": (
        Severity.ERROR, "restricted-element",
        "the element exists but is blocked by [common] restricted_elements",
    ),
    "NNS-E011": (
        Severity.ERROR, "construction-failed",
        "the element constructor raised (missing required property, "
        "unopenable resource, ...)",
    ),
    "NNS-W101": (
        Severity.WARNING, "unknown-property",
        "property is not in the element's schema (typo?)",
    ),
    "NNS-W102": (
        Severity.WARNING, "missing-model-file",
        "tensor_filter model path does not exist on disk",
    ),
    "NNS-W103": (
        Severity.WARNING, "unqueued-tee-branch",
        "mux fan-in branches share a tee ancestor without an intervening "
        "queue (classic deadlock topology)",
    ),
    "NNS-W104": (
        Severity.WARNING, "unreachable-element",
        "element is not reachable from any source; it will never see data",
    ),
    "NNS-W105": (
        Severity.WARNING, "unlinked-src-pad",
        "an element's src pad has nothing linked; its output is dropped",
    ),
    "NNS-W106": (
        Severity.WARNING, "suspicious-property-value",
        "the value parses at runtime but probably not as intended "
        "(e.g. an unrecognized boolean string silently becomes false)",
    ),
    "NNS-W107": (
        Severity.WARNING, "unrouted-error-pad",
        "on-error=route but the dead-letter error pad is unlinked; "
        "failed frames are silently dropped",
    ),
    # -- nns-san graph-level deadlock/capacity pass (analysis/lint.py) ------
    "NNS-W108": (
        Severity.WARNING, "channel-capacity",
        "a bounded channel is sized so it cannot do its job (non-positive "
        "queue-size is clamped to 1; max-batch larger than the input "
        "channel depth can never fill a batch)",
    ),
    "NNS-W109": (
        Severity.WARNING, "unqueued-fanout-join",
        "fan-in branches share a non-tee fan-out ancestor (demux/split) "
        "with no intervening queue on some branch — the same blocking "
        "topology as the tee case (NNS-W103)",
    ),
    "NNS-W110": (
        Severity.WARNING, "rate-skewed-join",
        "a synchronizing fan-in has a data-dependent frame dropper "
        "(tensor_if SKIP, on-error=drop/retry) on a strict subset of its "
        "branches; the join can starve waiting for skipped counterparts",
    ),
    "NNS-W111": (
        Severity.WARNING, "unbounded-query-server",
        "a tensor_query_serversrc has no admission bound (max-clients / "
        "max-inflight / per-client-inflight / rate); overload degrades "
        "as unbounded queueing and silent latency collapse",
    ),
    "NNS-W112": (
        Severity.WARNING, "replica-no-failover-policy",
        "a multi-replica filter (replicas=N) keeps the default "
        "on-error=stop: losing every replica then kills the whole "
        "pipeline, and in a serving pipeline admitted clients hang "
        "instead of receiving terminal NACKs",
    ),
    "NNS-W113": (
        Severity.WARNING, "host-split-device-segments",
        "a host-bound element sits between two device-capable "
        "(traceable) filters: every frame materializes to host and "
        "back mid-stream, defeating the resident device-to-device "
        "segment handoff",
    ),
    "NNS-W114": (
        Severity.WARNING, "duplicate-model-no-sharing",
        "two or more tensor_filter instances open the same "
        "model/framework without shared-tensor-filter-key or a serving "
        "plane: each loads its own copy of the weights on device",
    ),
    "NNS-W115": (
        Severity.WARNING, "oversized-static-kv-cache",
        "an LLM serving element's slot-layout KV cache (n-slots × "
        "max-len, sized for the worst case of every slot) exceeds the "
        "declared device memory bound while kv-layout=paged is "
        "available: a block-table arena serves the same requests in "
        "the actually-used tokens, with prefix sharing on top",
    ),
    "NNS-W116": (
        Severity.WARNING, "host-postproc-splits-device-chain",
        "a tensor_decoder whose decode math HAS a device (traceable) "
        "path runs as a host node between two device-capable filters: "
        "every frame materializes its (usually much larger) decoder "
        "inputs to host mid-stream; postproc=device folds the decode "
        "into the adjacent fused segment and only the small decoded "
        "tensor ever leaves the device",
    ),
    "NNS-W118": (
        Severity.WARNING, "blocking-plane-submit-under-ring",
        "a serving-plane stream that cannot overlap its submits: either "
        "a plane filter sets ring-depth>1 but disables the local window "
        "collector (batching=false forces per-frame blocking submits, "
        "so the in-flight ring never engages), or several streams share "
        "one plane with every in-flight depth left at 1 — each stream "
        "then blocks a full plane round trip per window while the "
        "async ticket ring would overlap submit/compute/delivery",
    ),
    "NNS-W119": (
        Severity.WARNING, "single-endpoint-no-failover",
        "a tensor_query_client stamps a per-request SLO (deadline-ms) "
        "but binds exactly one endpoint with retry-max=0: any endpoint "
        "hiccup is a terminal error with no reconnect, no failover, and "
        "no hedge — bind a fleet (hosts=h1:p1,h2:p2) or grant a "
        "retry-max budget",
    ),
    # -- nns-xray chain analysis (analysis/xray.py, docs/chain-analysis.md) -
    "NNS-W120": (
        Severity.WARNING, "chain-split-by-host-node",
        "a host-path tensor op severs an otherwise compileable chain "
        "of fused segments: frames materialize to host and re-stage to "
        "device at the split, and the span can never become one "
        "resident program; a device-capable framework (or "
        "postproc=device for decoders, which W116 pinpoints) rejoins "
        "the chain",
    ),
    "NNS-W121": (
        Severity.WARNING, "recompile-hazard-cache-keys",
        "a fused segment's jit-cache key space is unbounded or "
        "explodes: a flexible (per-frame shape) input spec under "
        "micro-batching, or arity x buckets x donation variants over "
        "the retrace bound — each new key is a fresh XLA compile on "
        "the hot path",
    ),
    "NNS-W122": (
        Severity.WARNING, "dtype-promotion-in-device-segment",
        "a device segment's traced program silently promotes to f64/"
        "complex128 (or drifts from its negotiated output dtype) with "
        "no 64-bit input: on TPU that is an emulated-precision slowdown "
        "and a doubled activation footprint the specs never declared",
    ),
    "NNS-W123": (
        Severity.WARNING, "donation-defeating-output",
        "a segment streams with donated input buffers (donate under "
        "ring-depth>1) but no output matches any input's shape/dtype, "
        "so XLA can reuse nothing: every frame pays a fresh output "
        "allocation while the donated arena is discarded",
    ),
    "NNS-W124": (
        Severity.WARNING, "chain-transient-hbm-over-bound",
        "a chain's static cost (resident params + peak per-program "
        "transient working set at the max micro-batch bucket) exceeds "
        "the declared [plane] memory_per_device bound: the chain OOMs "
        "on a real chip even though each stage fits alone",
    ),
    "NNS-W125": (
        Severity.WARNING, "chain-eligible-not-compiled",
        "a hazard-free multi-segment chain is running with chain_mode="
        "off: every frame still crosses one service thread per node "
        "where ONE resident whole-chain program (dispatched once per "
        "unrolled window) would serve it — host-dispatch overhead the "
        "compiled-chain path exists to remove",
    ),
    # -- fleet serving robustness (docs/llm-serving.md) ---------------------
    "NNS-W126": (
        Severity.WARNING, "llm-drain-loses-generations",
        "a fleet-tuned query serversrc (explicit retry-after-ms — its "
        "clients re-route on drain NACKs) feeds an LLM serversink with "
        "no migrate-to peer and no checkpoint-dir: draining this "
        "server abandons every in-flight generation's KV and decoded "
        "tokens, so re-routed requests pay a full re-prefill from "
        "token zero on the next endpoint",
    ),
    # -- nns-kscope kernel analysis (analysis/kernels.py, ------------------
    # docs/kernel-analysis.md)
    "NNS-W127": (
        Severity.WARNING, "kernel-vmem-over-budget",
        "a Pallas kernel's per-grid-step VMEM residency (operand/result "
        "blocks, double-buffered where their index map varies over the "
        "grid, plus scratch) exceeds the configured per-core VMEM bound "
        "([tpu] vmem_bytes, default 16 MiB): the launch OOMs or spills "
        "on a real chip even though the HBM arrays fit",
    ),
    "NNS-W128": (
        Severity.WARNING, "misaligned-tile",
        "a Pallas block is misaligned or its index map is hazardous: a "
        "block dim that is neither the whole axis nor a multiple of the "
        "hardware tile (lane 128; sublane 8/16/32 for 4/2/1-byte "
        "dtypes) is refused by the TPU compiler, and an index map that "
        "picks blocks outside the block grid (or a scalar-prefetch "
        "operand whose values drift from its declared SMEM shape) reads "
        "garbage",
    ),
    "NNS-W129": (
        Severity.WARNING, "pipeline-requests-pallas-but-dispatches-jnp",
        "an element explicitly requests a Pallas implementation "
        "(impl=pallas / attn-impl=pallas) that would silently dispatch "
        "the jnp/xla fallback: the input dtype is outside the kernel's "
        "registered support, the NNS_TPU_PALLAS_DISABLE kill switch is "
        "set, or the configured mode has no kernel at all",
    ),
    "NNS-W130": (
        Severity.WARNING, "prefill-role-no-decode-peer",
        "an LLM serversink declares role=prefill but names no "
        "decode-peers: every request it prefills decodes locally — the "
        "disaggregation it was configured for never happens, and with "
        "no checkpoint-dir either, a drain abandons the in-flight "
        "generations it was supposed to hand off",
    ),
    # -- nns-san race lint (analysis/racecheck.py): findings over SOURCE ----
    # code, not pipelines; `element` carries file:line
    "NNS-R001": (
        Severity.WARNING, "unlocked-shared-write",
        "a shared counter (self.attr += ...) is read-modify-written from "
        "more than one method of a thread-spawning class without the "
        "owning lock held at every site",
    ),
    "NNS-R002": (
        Severity.WARNING, "blocking-call-under-lock",
        "an unbounded blocking call (sleep, join without timeout, bare "
        "wait, recv/accept) runs while a threading lock is held",
    ),
    "NNS-R003": (
        Severity.ERROR, "swallowed-interrupt",
        "a bare except (or except BaseException) that does not re-raise "
        "swallows KeyboardInterrupt/SystemExit",
    ),
    "NNS-R004": (
        Severity.WARNING, "silent-except-in-loop",
        "except Exception with a pass/continue-only body inside a loop: a "
        "service loop that silently eats every failure forever",
    ),
    "NNS-R005": (
        Severity.WARNING, "thread-without-join",
        "a thread is created with no join-or-daemon story (neither "
        "daemon=True nor a reachable .join())",
    ),
    "NNS-R006": (
        Severity.ERROR, "dekker-ordering",
        "a channel class violates the documented _Chan parking discipline "
        "(advertise the waiting flag BEFORE re-checking the deque; check "
        "the peer's flag AFTER the deque op) — a missed-wakeup bug",
    ),
    # -- nns-san runtime sanitizer (pipeline/sanitize.py) -------------------
    "NNS-S001": (
        Severity.ERROR, "spec-violation",
        "a frame on a negotiated static link does not conform to the "
        "pad's TensorsSpec (shape/dtype drift the jit would mask or a "
        "downstream consumer would crash on)",
    ),
    "NNS-S002": (
        Severity.ERROR, "accounting-leak",
        "a node's frame accounting broke at EOS: offered != delivered + "
        "dropped + routed (frames vanished or were duplicated)",
    ),
    "NNS-S003": (
        Severity.WARNING, "lock-order-cycle",
        "watched locks were acquired in cyclic order by different "
        "threads — a latent deadlock",
    ),
    "NNS-S004": (
        Severity.WARNING, "thread-leak",
        "threads were still alive after Executor shutdown joined "
        "everything it started (stragglers listed)",
    ),
}


@dataclass(frozen=True)
class Diagnostic:
    """One finding: stable code + severity + offending element + advice."""

    code: str
    severity: Severity
    element: Optional[str]  # element (instance) name, None = whole pipeline
    message: str
    hint: str = ""

    @property
    def slug(self) -> str:
        return CATALOG[self.code][1] if self.code in CATALOG else ""

    def __str__(self) -> str:
        where = f" [{self.element}]" if self.element else ""
        hint = f" (hint: {self.hint})" if self.hint else ""
        return (
            f"{self.code} {self.severity.value}{where}: {self.message}{hint}"
        )


def make(code: str, element: Optional[str], message: str, hint: str = "") -> Diagnostic:
    """Build a Diagnostic with the catalog's severity for `code`."""
    sev, _, _ = CATALOG[code]
    return Diagnostic(code, sev, element, message, hint)


@dataclass
class LintReport:
    """All diagnostics from one lint run, never fail-fast."""

    diagnostics: List[Diagnostic] = field(default_factory=list)

    def add(self, code: str, element: Optional[str], message: str,
            hint: str = "") -> None:
        self.diagnostics.append(make(code, element, message, hint))

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity is Severity.ERROR]

    @property
    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity is Severity.WARNING]

    @property
    def codes(self) -> List[str]:
        return sorted({d.code for d in self.diagnostics})

    @property
    def exit_code(self) -> int:
        """nns-lint / nns-launch --check contract: 0 clean, 1 warnings
        only, 2 any error."""
        if self.errors:
            return 2
        if self.warnings:
            return 1
        return 0

    def by_element(self) -> Dict[Optional[str], List[Diagnostic]]:
        out: Dict[Optional[str], List[Diagnostic]] = {}
        for d in self.diagnostics:
            out.setdefault(d.element, []).append(d)
        return out

    def render(self) -> str:
        if not self.diagnostics:
            return "pipeline is clean"
        lines = [str(d) for d in self.diagnostics]
        lines.append(
            f"{len(self.errors)} error(s), {len(self.warnings)} warning(s)"
        )
        return "\n".join(lines)
