"""tensor_llm_{serversink,serversrc}: continuous-batching LLM serving as
pipeline elements.

The reference serves one model to many clients at *frame* granularity:
tensor_query_serversrc emits client-tagged requests, the pipeline
processes them one at a time, serversink routes replies by client_id
(gst/nnstreamer/tensor_query/tensor_query_serversrc.c:379-427). An LLM
server multiplexes at *token* granularity instead — requests decode
concurrently in one slot batch (models/serving.ContinuousBatcher) and
finish out of order.

That asynchrony maps onto the same pairing pattern the reference uses for
repo and query elements: two elements share a server object through a
global ``id`` table —

    tensor_query_serversrc id=7 ! tensor_llm_serversink id=0 model=...
    tensor_llm_serversrc id=0 ! tensor_query_serversink id=7

- ``tensor_llm_serversink`` (a Sink) submits each incoming prompt frame
  (int32 token tensor; per-frame ``max_new_tokens`` meta overrides the
  element default). When the batch is full it pumps the batcher until a
  slot frees — admission backpressure.
- ``tensor_llm_serversrc`` (a Source, its own executor thread → decode
  makes progress even when no new prompts arrive) steps the batcher and
  emits one frame per *completed* request: tokens [1, n], with the
  request frame's meta (client_id!) preserved, so a downstream
  query-serversink routes each generation back to its requester.

EOS: the sink's flush marks end-of-submissions; the src drains every
pending request, then ends its stream.
"""

from __future__ import annotations

import threading
import time as _time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from nnstreamer_tpu import registry
from nnstreamer_tpu import trace as _trace
from nnstreamer_tpu.elements.base import (
    ElementError,
    NegotiationError,
    PropSpec,
    Sink,
    Source,
    Spec,
)
from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.tensors.frame import EOS_FRAME, Frame
from nnstreamer_tpu.tensors.spec import TensorFormat, TensorsSpec

_log = get_logger("llm_serve")
_table: Dict[str, "_LlmServer"] = {}
_table_lock = threading.Lock()


def _get_server(srv_id: str, create_kw: Optional[dict] = None):
    with _table_lock:
        srv = _table.get(srv_id)
        if srv is not None and create_kw is not None and srv.eos:
            # stale server from a previous (stopped/drained) pipeline
            # run reusing this id: replace rather than resurrect — its
            # props may differ and its eos flag would end the new
            # stream. Its plane ref (if any) is NOT released here: the
            # stale server's own src may still be draining pending
            # generations through it — release rides that src's
            # _drop_server, which always releases the server it held.
            srv = None
        if srv is None:
            if create_kw is None:
                raise ElementError(
                    f"tensor_llm_server id={srv_id}: no serversink created "
                    "the server yet (the sink owns the model props)"
                )
            srv = _table[srv_id] = _LlmServer(**create_kw)
        return srv


def drain_server(srv_id: str, migrate_to: Optional[str] = None) -> Dict:
    """Operator surface (fleet tooling, tests): gracefully drain the
    id-keyed LLM server — new submits NACK ``draining``, chunked
    prefills settle, in-flight generations live-migrate to the peer (or
    resume locally when the peer refuses). Returns the drain summary
    (docs/llm-serving.md "Migration & recovery")."""
    with _table_lock:
        srv = _table.get(str(srv_id))
    if srv is None:
        raise ElementError(
            f"tensor_llm_server id={srv_id}: no server registered"
        )
    return srv.drain(migrate_to)


# meta keys that are meaningless outside the submitting process — the
# same hop-local set edge/serialize.py strips at the wire (client_id is
# the SOURCE server's transport pairing tag; the adopting server's own
# edge layer re-tags replies)
_SPAN_META_SKIP = frozenset({
    "client_id", "wall_t0", "admit_t", "_nns_srv", "_nns_budget_released",
})


def _span_meta(meta: dict) -> dict:
    """The JSON-scalar, cross-process-meaningful subset of a request's
    frame meta — what rides ``RequestSpan.meta`` (and the span
    checkpoint files) so the adopting or resuming server emits the
    finished generation with its identity (``frame_id``!) intact."""
    out = {}
    for k, v in meta.items():
        if k in _SPAN_META_SKIP:
            continue
        if v is None or isinstance(v, (str, int, float, bool)):
            out[k] = v
    return out


def _drop_server(srv_id: str, srv) -> None:
    """Remove the table entry — but only if it is still ``srv``: another
    pipeline may have reused the id with a fresh server, and a src that
    stopped before ever acquiring its server (srv None) must not evict a
    live entry another pipeline registered under the same id. A
    plane-attached server also drops its plane ref (last sharer out
    closes the shared batcher) — unconditionally on ``srv``, not just
    when the table entry still matched: a stale server replaced by a
    fresh one under the same id would otherwise leak its ref forever.
    release_plane is idempotent, so the drained-then-stopped src's two
    calls release once; so is stopping (and joining) the stall sentinel."""
    with _table_lock:
        if srv is not None and _table.get(srv_id) is srv:
            _table.pop(srv_id, None)
    if srv is not None:
        srv.release_plane()
        srv.stop_sentinel()


def _build_batcher(model: str, options: Dict[str, str], n_slots: int,
                   max_len: int, prompt_len: int, speculate: int,
                   speculate_model: str, kv_layout: str, block_size: int,
                   kv_blocks: int, cache_dtype: str, prefill_chunks: int,
                   attn_impl: str = ""):
    """Open the zoo model (+ optional draft) and build the
    ContinuousBatcher — shared by the private-server path and the
    LlmPlane opener (serving_plane/llm.py), so through-plane serving
    runs the EXACT construction a solo serversink would."""
    from nnstreamer_tpu.models import zoo
    from nnstreamer_tpu.models.serving import ContinuousBatcher
    from nnstreamer_tpu.obs import metrics as _obs_metrics

    if not model.startswith("zoo:"):
        raise ElementError(
            f"tensor_llm_serversink: model must be zoo:<name>, got "
            f"{model!r}"
        )
    t0 = _time.perf_counter()
    m = zoo.get(model[len("zoo:"):], **options)
    t_weights = _time.perf_counter() - t0
    n_heads = int(options.get("n_heads", 8))
    draft_kw = {}
    if speculate_model:
        # speculate-model=zoo:<name>: a draft model proposes the
        # speculate=k chunks instead of prompt-lookup. Its config
        # rides in the same custom dict under draft_-prefixed keys
        # (draft_d_model, draft_n_layers, draft_n_heads, ...); the
        # vocab must match the target's.
        if not speculate_model.startswith("zoo:"):
            raise ElementError(
                f"tensor_llm_serversink: speculate-model must be "
                f"zoo:<name>, got {speculate_model!r}"
            )
        d_opts = {
            k[len("draft_"):]: v for k, v in options.items()
            if k.startswith("draft_")
        }
        if "vocab" in options and "vocab" not in d_opts:
            d_opts["vocab"] = options["vocab"]
        dm = zoo.get(speculate_model[len("zoo:"):], **d_opts)
        draft_kw = dict(
            draft_params=dm.params,
            draft_n_heads=int(d_opts.get("n_heads", 8)),
        )
    kv_kw = {}
    if kv_layout != "slot":
        # paged KV (nnstreamer_tpu/kv/, docs/llm-serving.md):
        # block-table cache with prefix sharing, chunked prefill
        # and preemption-by-eviction; incompatible with a draft
        # model for now (ContinuousBatcher validates)
        kv_kw = dict(
            kv_layout=kv_layout, block_size=block_size,
            kv_blocks=kv_blocks or None,
            prefill_chunks=prefill_chunks,
        )
    t0 = _time.perf_counter()
    cb = ContinuousBatcher(
        m.params, n_heads, n_slots=n_slots, max_len=max_len,
        prompt_len=prompt_len, cache_dtype=cache_dtype,
        attn_impl=attn_impl, family=m.family,
        **kv_kw, **draft_kw,
    )
    reg = _obs_metrics.get()
    if reg is not None:
        reg.gauge("nns_llm_setup_seconds", phase="weights").set(t_weights)
        reg.gauge("nns_llm_setup_seconds", phase="batcher").set(
            _time.perf_counter() - t0
        )
    return cb


class _LlmServer:
    """Shared state between the sink (submit) and src (pump/emit)."""

    def __init__(self, model: str, options: Dict[str, str], n_slots: int,
                 max_len: int, prompt_len: int, default_new: int,
                 stream: bool = False, speculate: int = 0,
                 speculate_model: str = "", pump_tokens: int = 1,
                 kv_layout: str = "slot", block_size: int = 16,
                 kv_blocks: int = 0, cache_dtype: str = "auto",
                 prefill_chunks: int = 0, attn_impl: str = "",
                 plane: str = "", plane_weight: float = 1.0,
                 srv_id: str = "0", migrate_to: str = "",
                 checkpoint_every_tokens: int = 0,
                 checkpoint_dir: str = "",
                 role: str = "", decode_peers: str = ""):
        role = str(role or "")
        decode_peers = str(decode_peers or "")
        if role not in ("", "prefill", "decode"):
            raise ElementError(
                f"tensor_llm_serversink: role={role!r} must be "
                "prefill or decode"
            )
        if decode_peers and role != "prefill":
            raise ElementError(
                "tensor_llm_serversink: decode-peers needs role=prefill "
                "(only the prefill role ships spans to decode peers)"
            )
        if role:
            # disaggregated serving moves block-table KV spans between
            # roles (docs/llm-serving.md "Disaggregated serving") —
            # meaningless for the contiguous slot cache, refused on a
            # shared plane like migrate-to/checkpoint-*
            if kv_layout != "paged":
                raise ElementError(
                    "tensor_llm_serversink: role=prefill/decode needs "
                    "kv-layout=paged (handoffs are block-table spans)"
                )
            if plane:
                from nnstreamer_tpu.serving_plane.llm import LlmPlaneError

                raise LlmPlaneError(
                    f"llm plane {plane!r}: role= refused — plane-shared "
                    "batchers cannot extract or adopt request spans; "
                    "serve the role with a private kv-layout=paged "
                    "batcher instead"
                )
        if (migrate_to or checkpoint_dir or checkpoint_every_tokens):
            # migration + crash recovery (docs/llm-serving.md
            # "Migration & recovery") move block-table KV spans — they
            # have no meaning for the contiguous slot cache
            if kv_layout != "paged":
                raise ElementError(
                    "tensor_llm_serversink: migrate-to / "
                    "checkpoint-every-tokens / checkpoint-dir need "
                    "kv-layout=paged (spans are block-table slices)"
                )
            if plane:
                # typed plane refusal, raised BEFORE acquiring a plane
                # ref (nothing to release on this failure path)
                from nnstreamer_tpu.serving_plane.llm import LlmPlaneError

                raise LlmPlaneError(
                    f"llm plane {plane!r}: migrate-to/checkpoint-* "
                    "refused — plane-shared batchers cannot migrate "
                    "or checkpoint requests; serve with a private "
                    "kv-layout=paged batcher instead"
                )
        self.role = role
        self._disagg = None  # DisaggController (prefill role with peers)
        self._disagg_done: Dict[int, list] = {}  # decode role: rid→tokens
        if role == "prefill" and decode_peers:
            # built BEFORE the batcher so a malformed decode-peers spec
            # fails loudly without paying the model load
            from nnstreamer_tpu.serving_plane.disagg import DisaggController

            try:
                self._disagg = DisaggController(
                    decode_peers,
                    llm_id=int(srv_id) if str(srv_id).isdigit() else 0,
                )
            except ValueError as exc:
                raise ElementError(
                    f"tensor_llm_serversink: {exc}"
                ) from exc
        if speculate_model and speculate != -1 and speculate < 2:
            # a draft model exists ONLY to propose speculate=k chunks;
            # without this, every request would pay the draft prefill
            # for a proposer the plain-step pump never consults
            speculate = 4
        self.plane_name = plane
        self._plane = None   # LlmPlane once acquired
        self._stream = None  # this server's LlmStream
        if plane:
            # plane=<name> (docs/llm-serving.md): this serversink is one
            # client stream of a SHARED paged batcher — the tensor
            # plane's discipline at token granularity. The features that
            # assume a private batcher are rejected with the reason:
            if kv_layout != "paged":
                raise ElementError(
                    f"tensor_llm_serversink: plane={plane!r} needs "
                    "kv-layout=paged (the shared batcher is the paged "
                    "arena; slot caches are per-server by construction)"
                )
            if speculate or speculate_model:
                raise ElementError(
                    f"tensor_llm_serversink: plane={plane!r} cannot "
                    "combine with speculate/speculate-model (the "
                    "speculation controller state is per-server)"
                )
            if stream:
                raise ElementError(
                    f"tensor_llm_serversink: plane={plane!r} cannot "
                    "combine with stream=true (per-token routing "
                    "through a shared plane is not wired yet)"
                )
            from nnstreamer_tpu.serving_plane import llm as llm_plane

            sig = (
                model, tuple(sorted(options.items())), n_slots, max_len,
                prompt_len, kv_layout, block_size, kv_blocks,
                cache_dtype, prefill_chunks, attn_impl,
                max(1, int(pump_tokens)),
            )
            self._plane = llm_plane.acquire(
                plane, sig,
                opener=lambda: _build_batcher(
                    model, options, n_slots, max_len, prompt_len,
                    speculate, speculate_model, kv_layout, block_size,
                    kv_blocks, cache_dtype, prefill_chunks, attn_impl,
                ),
                pump_tokens=pump_tokens,
            )
            try:
                self._stream = self._plane.attach(srv_id, plane_weight)
            except ValueError:
                # same id string attached elsewhere in this process:
                # disambiguate rather than refuse (ids are only unique
                # per pairing)
                self._stream = self._plane.attach(
                    f"{srv_id}@{id(self) & 0xffff:04x}", plane_weight
                )
            self.cb = self._plane.cb
        else:
            self.cb = _build_batcher(
                model, options, n_slots, max_len, prompt_len, speculate,
                speculate_model, kv_layout, block_size, kv_blocks,
                cache_dtype, prefill_chunks, attn_impl,
            )
        # properties the served block family does not carry refuse here,
        # by name (models/family.py): no silent fallback, no half path
        unsupported = self.cb._family.unsupported
        for prop, feature, on in (
            ("speculate", "speculate", bool(speculate)),
            ("speculate-model", "draft model", bool(speculate_model)),
            ("role", "migration", bool(role)),
            ("decode-peers", "migration", bool(decode_peers)),
            ("migrate-to", "migration", bool(migrate_to)),
            ("checkpoint-every-tokens", "snapshot",
             bool(checkpoint_every_tokens)),
            ("checkpoint-dir", "snapshot", bool(checkpoint_dir)),
        ):
            if on and feature in unsupported:
                raise ElementError(
                    f"tensor_llm_serversink: {prop} is not supported by "
                    f"the {self.cb._family.name} block family ({model})"
                )
        # until the first token of any request: what is left of set-up
        # once the batcher exists (nns_llm_setup_seconds{first_token})
        self._t_built: Optional[float] = _time.perf_counter()
        self.default_new = default_new
        self._lock = threading.Lock()
        self._pending: Dict[int, dict] = {}  # rid -> request meta
        self._out: deque = deque()
        self.eos = False
        self.stopped = False
        # token streaming: emit one frame per NEW token as it decodes,
        # then a final done frame — the SSE-style serving surface in the
        # pipeline idiom. Authoritative when set at creation (the sink's
        # stream prop); the serversrc's stream=true also flips it at
        # acquisition, which is race-free only in the single-pipeline
        # layout (all elements start before any frame flows) — paired
        # ACROSS pipelines, set it on the sink.
        self.stream = stream
        # speculate=k: pump via spec_step(k) — prompt-lookup speculation
        # batched over slots (greedy slots emit several tokens per
        # program launch when the guesses land; exact equivalence).
        # speculate=auto (-1): k adapts to the measured acceptance rate
        # (EMA) between 2 and 8 — long chunks when guesses land, minimal
        # verify width when they don't.
        self.speculate = speculate
        # pump=N: target tokens per program launch — step_pump(N) /
        # spec_pump(rounds=⌈N/k⌉). N=1 keeps the per-token step path
        # (minimum admission latency); larger N amortizes the
        # host↔device round trip N ways (ONE readback per pump).
        # Admissions join at the next pump, so latency-sensitive
        # servers keep N small.
        self.pump_tokens = max(1, int(pump_tokens))
        self._spec_k = 4
        self._acc_ema = 0.5
        self._spec_seen = (0, 0)  # (columns, accepted) at last adapt
        self._sent: Dict[int, int] = {}  # rid -> tokens already streamed
        # -- live migration + crash recovery (docs/llm-serving.md
        # "Migration & recovery") --------------------------------------
        self.srv_id = str(srv_id)
        self._paged = kv_layout == "paged" or plane != ""
        self.migrate_to = str(migrate_to or "")
        self.draining = False
        self._edge_srv = None  # paired serversrc id, learned at submit
        self._ckpt_every = max(0, int(checkpoint_every_tokens))
        self._ckpt_dir = str(checkpoint_dir or "")
        self._ckpt_seen: Dict[int, int] = {}  # rid -> tokens at last ckpt
        from nnstreamer_tpu.obs import metrics as _obs_metrics

        self._obs_reg = _obs_metrics.get()
        # the llm_id the migration handshake routes by: the serversink
        # id when numeric (the usual "id=0"), else 0 — the receiving
        # process falls back to its only handler anyway when exactly
        # one LLM server runs there
        self._mig_id = int(self.srv_id) if self.srv_id.isdigit() else 0
        self._mig_registered = False
        if self._plane is None and self._paged:
            # every private paged server is adoptable: being a
            # migration DESTINATION needs no props — migrate-to only
            # configures where THIS server ships its spans at drain
            from nnstreamer_tpu.edge import query as _equery

            _equery.register_migration_handler(self._mig_id, self)
            self._mig_registered = True
            if self._ckpt_dir:
                self._restore_checkpoints()
        # the host's stalls on the trace's clock (trace.StallSentinel),
        # under the operator's existing opt-in: metrics or a Tracer
        self._sentinel = None
        if self._obs_reg is not None or _trace.get() is not None:
            try:
                self._sentinel = _trace.StallSentinel(self._obs_reg)
            except OSError as exc:  # no timerfd on this platform
                _log.warning("no stall sentinel: %s", exc)

    def stop_sentinel(self) -> None:
        if self._sentinel is not None:
            self._sentinel.stop()

    def submit(self, frame: Frame) -> None:
        t_in = _time.perf_counter()
        if frame.meta.get("_nns_srv") is not None:
            # remember which edge serversrc feeds this server, so
            # drain() can flip its readiness flag and NACK at admission
            self._edge_srv = frame.meta.get("_nns_srv")
        if self.draining:
            self._nack_draining(frame)
            return
        prompt = np.asarray(frame.tensors[0]).reshape(-1).astype(np.int32)
        budget = int(frame.meta.get("max_new_tokens", self.default_new))
        # per-request sampling params ride in frame meta (greedy default)
        kw = dict(
            temperature=float(frame.meta.get("temperature", 0.0)),
            top_k=int(frame.meta.get("top_k", 0)),
            top_p=float(frame.meta.get("top_p", 1.0)),
        )
        if "seed" in frame.meta:
            kw["seed"] = int(frame.meta["seed"])
        if "deadline_ms" in frame.meta:
            # SLO accounting (nns-top --requests); the edge layer's
            # deadline shedding is upstream of this element
            kw["deadline_s"] = float(frame.meta["deadline_ms"]) / 1000.0
        if self._plane is not None:
            # through-plane serving: the prompt queues for weighted-fair
            # admission into the SHARED batcher (serving_plane/llm.py);
            # backpressure past the fair backlog pumps inside submit
            if self.stopped:
                raise ElementError("tensor_llm_serversink: stopped")
            self._plane.submit(
                self._stream, prompt, budget, kw, dict(frame.meta)
            )
            return
        retries = 0
        while True:
            if self.stopped:
                raise ElementError("tensor_llm_serversink: stopped")
            rid = self.cb.submit(prompt, budget, **kw)
            if rid is not None:
                break
            retries += 1
            # batch full: pumping here IS the backpressure — admission
            # waits until decoding frees a slot. A no-progress pump is
            # NOT an error: the src thread may have just stepped/ drained
            # concurrently (freeing slots), so loop and retry submit.
            if not self.pump():
                _time.sleep(0.005)
        with self._lock:
            self._pending[rid] = dict(frame.meta)
        _trace.instant(
            "nns.llm.admitted", rid=rid, retries=retries,
            slot_wait_ms=(_time.perf_counter() - t_in) * 1000.0,
        )

    def pump(self) -> bool:
        """One decode step; harvest finished requests (and, in streaming
        mode, every new token). True if anything advanced."""
        if self._plane is not None:
            # the SHARED batcher advances every stream's requests; this
            # server's finished generations land on its own plane
            # stream deque (pop reads them there)
            return self._plane.pump()
        with _trace.span("nns.llm.pump", pending=len(self._pending)):
            return self._pump()

    def _pump(self) -> bool:
        N = self.pump_tokens
        if self.speculate == -1:
            if N > 1:
                emitted = self.cb.spec_pump(
                    rounds=max(1, -(-N // self._spec_k)), k=self._spec_k
                )
            else:
                emitted = self.cb.spec_step(k=self._spec_k)
            st = self.cb.stats()
            # normalize by proposal COLUMNS, not rounds: a round offers
            # active_slots×(k-1) proposals, so a rounds-based rate would
            # saturate on multi-slot servers and pin k at max exactly
            # when acceptance is poor
            cols, acc = st["spec_columns"], st["spec_accepted_tokens"]
            dc = cols - self._spec_seen[0]
            if dc > 0:
                rate = (acc - self._spec_seen[1]) / dc
                self._acc_ema = 0.7 * self._acc_ema + 0.3 * rate
                self._spec_k = min(
                    8, max(2, 2 + int(round(self._acc_ema * 6)))
                )
                self._spec_seen = (cols, acc)
        elif self.speculate > 1:
            if N > 1:
                emitted = self.cb.spec_pump(
                    rounds=max(1, -(-N // self.speculate)),
                    k=self.speculate,
                )
            else:
                emitted = self.cb.spec_step(k=self.speculate)
        elif N > 1:
            emitted = self.cb.step_pump(N)
        else:
            emitted = self.cb.step()
        harvested = False
        finished: List[int] = []
        with _trace.span("nns.llm.harvest"), self._lock:
            if self.stream:
                # count-based catch-up off cb.partials() (one batcher
                # lock pass for all pending rids): robust to tokens
                # emitted by ANY thread's step between two pumps
                parts = self.cb.partials(list(self._pending))
                for rid, meta in self._pending.items():
                    toks = parts.get(rid)
                    if toks is None:
                        continue
                    if self.role == "decode" and meta.get("_nns_disagg"):
                        continue  # fetched whole by the prefill side
                    harvested |= self._stream_new_locked(rid, meta, toks)
            for rid in list(self._pending):
                toks = self.cb.result(rid)
                if toks is not None:
                    meta = self._pending.pop(rid)
                    park = (
                        self.role == "decode"
                        and bool(meta.get("_nns_disagg"))
                    )
                    if self.stream and not park:
                        # a concurrent pump's step may have finished the
                        # request AFTER our catch-up pass above — emit the
                        # tail tokens per-frame before the done frame so
                        # the one-frame-per-token contract holds
                        self._stream_new_locked(rid, meta, toks)
                        meta = {**meta, "stream": True, "done": True}
                    self._sent.pop(rid, None)
                    if park:
                        # a handed-off generation finished HERE, but the
                        # prefill side owns DELIVER (at-most-once rides
                        # its unchanged frame_id): park the tokens for
                        # its disagg_fetch instead of emitting
                        self._disagg_done[rid] = list(toks)
                    else:
                        self._out.append((toks, meta))
                    finished.append(rid)
                    harvested = True
        if self._ckpt_dir:
            for rid in finished:
                self._ckpt_drop(rid)
            if self._ckpt_every:
                self._checkpoint_tick()
        if self._disagg is not None and not self.stopped:
            # prefill role: offload freshly-extractable requests to the
            # decode peers and relay finished handoffs into _out
            harvested |= self._disagg.tick(self)
        if self._t_built is not None and (emitted or harvested):
            if self._obs_reg is not None:
                self._obs_reg.gauge(
                    "nns_llm_setup_seconds", phase="first_token"
                ).set(_time.perf_counter() - self._t_built)
            self._t_built = None
        return bool(emitted) or harvested

    def _stream_new_locked(self, rid: int, meta: dict, toks) -> bool:
        """Emit per-token frames for tokens not yet streamed (_lock held)."""
        n0 = self._sent.get(rid, 0)
        for i in range(n0, len(toks)):
            self._out.append((
                [toks[i]],
                {**meta, "stream": True, "done": False, "token_index": i},
            ))
        self._sent[rid] = len(toks)
        return len(toks) > n0

    # -- live migration + crash recovery (docs/llm-serving.md
    # "Migration & recovery") ------------------------------------------

    def _nack_draining(self, frame: Frame) -> None:
        """A submit reaching a draining LLM server is NACKed
        ``draining`` with the retry-after hint (the PR-15 edge-drain
        contract, now honoured when the DOWNSTREAM consumer drains
        behind a still-ready serversrc) — the fleet client re-routes
        instead of timing out behind a server that will never finish
        the request."""
        srv = frame.meta.get("_nns_srv")
        cid = frame.meta.get("client_id")
        if srv is not None and cid is not None:
            from nnstreamer_tpu.edge.query import discard_admitted

            discard_admitted(
                srv, cid, "nack", frame_id=frame.meta.get("frame_id"),
                draining=True,
            )
            return
        # no edge hop to answer through (direct pipeline submit): the
        # typed refusal is the only channel left
        raise ElementError(
            "tensor_llm_serversink: draining — not accepting new "
            "requests (resubmit to another endpoint)"
        )

    def migration_probe(self, tokens) -> int:
        """How many leading ``tokens`` this server's prefix index
        already covers (full blocks only) — the sender strips those
        blocks' payloads and ships only the unshared suffix. Answers
        ``migrate_probe`` CTRLs through the edge/query.py handler
        registry."""
        from nnstreamer_tpu.kv.migrate import SpanStateError

        if self._plane is not None:
            self._plane.refuse_migration("migrate_probe")
        if self.draining or self.stopped:
            raise SpanStateError(
                f"tensor_llm_server id={self.srv_id}: draining"
            )
        return int(self.cb.probe_prefix([int(t) for t in tokens]))

    def migration_adopt(self, span_bytes: bytes) -> int:
        """Decode + adopt an incoming KV span: the generation continues
        HERE under the returned rid — bitwise-identically for greedy
        requests — and this server's serversrc emits it with the span's
        surviving frame meta (``frame_id`` intact for reply dedup)."""
        from nnstreamer_tpu.kv import migrate as _migrate

        if self._plane is not None:
            self._plane.refuse_migration("migrate_span")
        if self.draining or self.stopped:
            raise _migrate.SpanStateError(
                f"tensor_llm_server id={self.srv_id}: draining"
            )
        span = _migrate.decode_span(span_bytes)
        rid = self.cb.adopt_request(span)
        with self._lock:
            self._pending[rid] = dict(span.meta)
        return rid

    # the disagg controller stamps surviving frame meta onto spans it
    # extracts — the same propagation filter drain()/checkpointing use
    span_meta = staticmethod(_span_meta)

    def migration_advert(self) -> Dict:
        """Piggybacked on every ``migrate_probe_ack`` (docs/
        llm-serving.md "Disaggregated serving"): one probe roundtrip
        tells the prefill side how WARM this server is (shared_tokens,
        from the probe itself) and how FULL (pool headroom, from this
        advert) — enough to pick the best decode peer without a second
        exchange."""
        out: Dict = {"role": self.role or ""}
        if self.role != "decode":
            return out
        st = self.cb.stats()
        out["free_slots"] = int(st.get("slots_free", 0) or 0)
        # cached blocks are evictable on demand, so they count as
        # headroom for an incoming span's unshared suffix
        out["free_blocks"] = (
            int(st.get("kv_blocks_free", 0) or 0)
            + int(st.get("kv_blocks_cached", 0) or 0)
        )
        return out

    def disagg_fetch(self, rid: int):
        """Answer a ``disagg_fetch`` CTRL from the prefill peer that
        handed rid off here: finished tokens (popped — exactly-once,
        the prefill side owns DELIVER), ``None`` while still decoding,
        or SpanStateError for an rid this server has never seen (the
        peer stops polling and resubmits the prompt)."""
        from nnstreamer_tpu.kv.migrate import SpanStateError

        rid = int(rid)
        with self._lock:
            toks = self._disagg_done.pop(rid, None)
            if toks is not None:
                return toks
            if rid in self._pending:
                return None
        raise SpanStateError(
            f"tensor_llm_server id={self.srv_id}: rid {rid} unknown"
        )

    def drain(self, migrate_to: Optional[str] = None) -> Dict[str, int]:
        """Graceful drain with live migration: stop admitting (new
        submits NACK ``draining``, the paired edge serversrc flips to
        SRV_DRAINING), settle every chunked prefill mid-flight (a span
        is only extractable once its request is decoding — no job left
        half-staged), then per in-flight request: extract the KV span,
        probe the peer's prefix coverage, ship the slimmed span. A
        refusing or unreachable peer falls back to local re-prefill
        resume; with no peer configured the requests simply finish in
        place. Returns ``{"migrated", "resumed", "completed", "kept"}``
        counts."""
        import time as _time

        if self._plane is not None:
            self._plane.refuse_migration("drain(migrate_to=...)")
        self.draining = True
        if self._edge_srv is not None:
            from nnstreamer_tpu.edge import query as _equery

            _equery._set_server_state(
                self._edge_srv, _equery.SRV_DRAINING
            )
        summary = {"migrated": 0, "resumed": 0, "completed": 0, "kept": 0}
        if self._paged:
            # settle chunked prefills: every queued/half-staged prefill
            # lands (its request becomes decoding — and extractable)
            # before any span leaves; completed chunks are never re-run
            while (self.cb.stats().get("kv_prefill_queue") or 0) > 0:
                if self.stopped:
                    break
                if not self.pump():
                    _time.sleep(0.002)
        target = self.migrate_to if migrate_to is None else str(migrate_to)
        with self._lock:
            rids = list(self._pending)
        if not target or not rids:
            summary["kept"] = len(rids)
            return summary
        if not self._paged:
            raise ElementError(
                "tensor_llm_serversink: drain(migrate_to=...) needs "
                "kv-layout=paged (spans are block-table slices)"
            )
        # host:port[/llm-id] — the peer's serversink id defaults to this
        # server's own (symmetric fleet configs), and a peer hosting a
        # single LLM server answers regardless (handler fallback)
        peer_id = self._mig_id
        base, sep, suffix = target.partition("/")
        if sep:
            target = base
            peer_id = int(suffix) if suffix.isdigit() else 0
        host, _, port_s = target.rpartition(":")
        if not host or not port_s.isdigit():
            raise ElementError(
                f"tensor_llm_serversink: migrate-to={target!r} must be "
                "host:port[/llm-id]"
            )
        port = int(port_s)
        from nnstreamer_tpu.edge import query as _equery
        from nnstreamer_tpu.edge.transport import TransportError
        from nnstreamer_tpu.kv import migrate as _migrate

        for rid in rids:
            try:
                span = self.cb.extract_request(rid)
            except _migrate.SpanError:
                # finished between the settle loop and now — pump's
                # harvest owns it (still a terminal outcome)
                summary["completed"] += 1
                continue
            with self._lock:
                meta = dict(self._pending.get(rid) or {})
            span.meta.update(_span_meta(meta))
            try:
                shared = _equery.probe_migration(
                    host, port, span.kv_tokens, llm_id=peer_id
                )
                wire = _migrate.encode_span(span.strip_shared(shared))
                _equery.send_migration(
                    host, port, wire, llm_id=peer_id
                )
            except (_equery.MigrationRefused, TransportError, OSError,
                    ValueError, _migrate.SpanError):
                # the request is still whole on this side — resume it
                # locally via re-prefill of the surviving context (the
                # cold fallback; generated tokens are NOT lost)
                new_rid = self.cb.resume_from_span(span)
                with self._lock:
                    self._pending[new_rid] = self._pending.pop(rid, meta)
                    n_sent = self._sent.pop(rid, None)
                    if n_sent is not None:
                        self._sent[new_rid] = n_sent
                if self._ckpt_dir:
                    self._ckpt_rename(rid, new_rid)
                summary["resumed"] += 1
            else:
                with self._lock:
                    self._pending.pop(rid, None)
                    self._sent.pop(rid, None)
                self._ckpt_drop(rid)
                summary["migrated"] += 1
        return summary

    def _checkpoint_tick(self) -> None:
        """Every checkpoint-every-tokens NEW tokens per request, write
        an atomic span checkpoint — a hard-killed server process
        resumes its in-flight generations from these files at next
        construction, without re-running completed prefill chunks."""
        with self._lock:
            rids = list(self._pending)
        if not rids or not self._paged:
            return
        parts = self.cb.partials(rids)
        for rid in rids:
            n = len(parts.get(rid) or ())
            if n - self._ckpt_seen.get(rid, 0) < self._ckpt_every:
                continue
            if self._write_checkpoint(rid):
                self._ckpt_seen[rid] = n

    def _write_checkpoint(self, rid: int) -> bool:
        import os

        from nnstreamer_tpu.kv import migrate as _migrate

        with self._lock:
            meta = dict(self._pending.get(rid) or {})
        try:
            span = self.cb.extract_request(rid, remove=False)
        except _migrate.SpanError:
            return False  # finished or mid-prefill this instant — skip
        span.meta.update(_span_meta(meta))
        path = os.path.join(self._ckpt_dir, f"req-{rid}.span")
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(self._ckpt_dir, exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(_migrate.encode_span(span))
            # atomic replace: a reader (or the restore scan after a
            # crash) sees the old complete checkpoint or the new one,
            # never a torn file
            os.replace(tmp, path)
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        return True

    def _ckpt_drop(self, rid: int) -> None:
        import os

        self._ckpt_seen.pop(rid, None)
        if not self._ckpt_dir:
            return
        try:
            os.remove(os.path.join(self._ckpt_dir, f"req-{rid}.span"))
        except OSError:
            pass

    def _ckpt_rename(self, old: int, new: int) -> None:
        """A request changed rid (resume fallback, restore adoption):
        move its checkpoint file along — the stale name would be
        re-adopted as a GHOST duplicate at the next restart."""
        import os

        self._ckpt_seen[new] = self._ckpt_seen.pop(old, 0)
        try:
            os.replace(
                os.path.join(self._ckpt_dir, f"req-{old}.span"),
                os.path.join(self._ckpt_dir, f"req-{new}.span"),
            )
        except OSError:
            pass

    def _restore_checkpoints(self) -> None:
        """Crash recovery: adopt every span checkpoint a previous
        (hard-killed) server process left in checkpoint-dir — the
        landed KV re-enters the arena directly, so completed prefill
        chunks are NOT re-run. Corrupt or unadoptable files are set
        aside (``.bad``) rather than retried forever."""
        import os

        from nnstreamer_tpu.kv import migrate as _migrate

        try:
            names = sorted(os.listdir(self._ckpt_dir))
        except OSError:
            return  # fresh dir: created lazily at the first checkpoint
        for name in names:
            if not name.endswith(".span"):
                continue
            path = os.path.join(self._ckpt_dir, name)
            try:
                with open(path, "rb") as f:
                    span = _migrate.decode_span(f.read())
                rid = self.cb.adopt_request(span)
            except (OSError, _migrate.SpanError):
                try:
                    os.replace(path, path + ".bad")
                except OSError:
                    pass
                continue
            with self._lock:
                self._pending[rid] = dict(span.meta)
            # keep the file (under the adopted rid's name) until the
            # request finishes or re-checkpoints: a crash right after
            # restore must not lose the generation a second time
            dest = os.path.join(self._ckpt_dir, f"req-{rid}.span")
            if dest != path:
                try:
                    os.replace(path, dest)
                except OSError:
                    pass
            self._ckpt_seen[rid] = len(span.tokens)
            if self._obs_reg is not None:
                self._obs_reg.counter(
                    "nns_request_resumes_total", kind="checkpoint"
                ).inc()

    def stats(self) -> Dict:
        """Batcher counters + the adaptive-speculation control state
        (VERDICT r4 #5: a silent proposer regression shows up here as a
        sagging acceptance rate / k pinned at 2 — visible in --stats,
        not only in wall time)."""
        if self._plane is not None:
            # shared-batcher counters + ONLY this stream's request rows
            # (per-stream SLO ledgers: sharers never report each
            # other's — serving_plane/llm.py)
            return self._sentinel_stats(self._plane.stats_for(self._stream))
        st = self._sentinel_stats(self.cb.stats())
        # per-request SLO rows for nns-top --requests (serving_requests
        # once the executor prefixes the row)
        st["requests"] = {
            str(rid): row for rid, row in self.cb.requests().items()
        }
        if self.role:
            st["disagg_role"] = self.role
        if self._disagg is not None:
            st["disagg"] = self._disagg.stats()
        if self.role == "decode":
            with self._lock:
                st["disagg_done_waiting"] = len(self._disagg_done)
        if self.speculate == -1:
            st["spec_k"] = self._spec_k
            # the EMA is the auto controller's state — in fixed-k mode
            # it never updates, and a frozen 0.5 would read "healthy"
            # during the exact regression this surface exists to catch
            # (fixed-k readers watch spec_acceptance_rate instead)
            st["spec_acceptance_ema"] = self._acc_ema
        elif self.speculate > 1:
            st["spec_k"] = self.speculate
        return st

    def _sentinel_stats(self, st: Dict) -> Dict:
        """The stall sentinel's totals over the server's life, where one runs."""
        s = self._sentinel
        if s is not None:
            st.update(host_stalls=s.stalls, host_stall_ms=s.stall_ms,
                      host_stalls_machine=s.stalls_machine)
        return st

    def pop(self):
        if self._plane is not None:
            return self._plane.pop(self._stream)
        with self._lock:
            return self._out.popleft() if self._out else None

    @property
    def drained(self) -> bool:
        if self._plane is not None:
            return self.eos and self._plane.idle_for(self._stream)
        if self._disagg is not None and not self._disagg.idle():
            return False  # handed-off generations still in flight
        with self._lock:
            return (
                self.eos and not self._pending and not self._out
                and not self._disagg_done
            )

    def release_plane(self) -> None:
        """Detach from (and drop one ref of) the shared LLM plane —
        called when this server leaves the pairing table. Idempotent
        (the src calls it at drain AND at stop) and race-guarded under
        ``_lock``; private-batcher servers only unregister their
        migration handler here."""
        if self._mig_registered:
            self._mig_registered = False
            from nnstreamer_tpu.edge import query as _equery

            _equery.unregister_migration_handler(self._mig_id, self)
        with self._lock:
            plane, self._plane = self._plane, None
        if plane is None:
            return
        from nnstreamer_tpu.serving_plane import llm as llm_plane

        if self._stream is not None:
            plane.detach(self._stream)
        llm_plane.release(self.plane_name, plane)
        self.cb = None


@registry.element("tensor_llm_serversink")
class LlmServerSink(Sink):
    """Submit prompt frames into the shared continuous batcher.

    Props: id (pairing key), model (zoo:transformer_lm), custom
    (model options, filter-style "k:v,k2:v2"), n-slots, max-len,
    prompt-len, max-new-tokens (per-request default; per-frame
    ``max_new_tokens`` meta overrides), stream (one frame per NEW
    token then a done frame), speculate (=k: pump via spec_step —
    prompt-lookup speculation batched over slots, working across
    sampling/windowed/Pallas configurations; =auto adapts k to the
    measured acceptance rate), speculate-model
    (zoo:<name>: a DRAFT model proposes the speculate=k chunks instead
    of prompt-lookup; configure it with draft_-prefixed keys in the
    custom dict, e.g. draft_d_model/draft_n_layers/draft_n_heads —
    vocab is inherited from the target; implies speculate=4 when
    speculate is unset), pump (=N: target tokens per program launch —
    step_pump(N)/spec_pump over device-scanned rounds, ONE
    device→host read per pump instead of one per token; default 1
    keeps per-token stepping for minimum admission latency),
    kv-layout/block-size/kv-blocks/prefill-chunks (paged KV cache:
    block-table arena with prefix sharing, chunked prefill and
    preemption-by-eviction — docs/llm-serving.md; defaults from the
    [llm] config section),
    cache-dtype (int8 stores the KV cache quantized), kv-memory-bound
    (declared HBM budget consumed by nns-lint NNS-W115),
    migrate-to (peer host:port — drain-time live KV-span migration;
    in-flight generations continue on the peer bitwise-identically for
    greedy requests), checkpoint-every-tokens/checkpoint-dir (periodic
    atomic span checkpoints; a restarted server adopts the files and
    resumes without re-running completed prefill chunks — docs/
    llm-serving.md "Migration & recovery"; all three require
    kv-layout=paged and are refused on plane= with a typed error),
    role/decode-peers (disaggregated prefill/decode serving — a
    role=prefill server runs chunked prefill then hands each KV span
    to the warmest decode peer, a role=decode server advertises pool
    headroom in probe acks and parks finished handoffs for the
    prefill side's fetch — docs/llm-serving.md "Disaggregated
    serving"; same kv-layout=paged / no-plane constraints)."""

    FACTORY_NAME = "tensor_llm_serversink"

    # negotiate() builds the shared _LlmServer (full model load) and
    # registers it in the module-global _table — nns-lint must not do
    # that during a dry run
    LINT_SKIP_NEGOTIATE = True

    PROPERTIES = {
        "id": PropSpec("str", "0", desc="pairing key with the serversrc"),
        "model": PropSpec("str", "zoo:transformer_lm"),
        "custom": PropSpec("str", "", desc="model options 'k:v,k2:v2'"),
        "n-slots": PropSpec("int", 4),
        "max-len": PropSpec("int", 256),
        "prompt-len": PropSpec("int", 64),
        "max-new-tokens": PropSpec("int", 16),
        "stream": PropSpec("bool", False),
        "speculate": PropSpec("str", "0", desc="k, or 'auto'"),
        "speculate-model": PropSpec("str", "", desc="zoo:<draft model>"),
        "pump": PropSpec("int", 1, desc="target tokens per launch"),
        # paged KV cache (nnstreamer_tpu/kv/, docs/llm-serving.md);
        # empty strings defer to the [llm] config section
        "kv-layout": PropSpec("str", "", desc="slot | paged ([llm] default)"),
        "block-size": PropSpec("int", 0, desc="tokens per KV block (paged)"),
        "kv-blocks": PropSpec("int", 0, desc="arena blocks (paged; 0=auto)"),
        "cache-dtype": PropSpec("str", "auto", desc="auto | int8"),
        "attn-impl": PropSpec(
            "str", "",
            desc="decode attention: xla | pallas; unset takes [llm] "
            "attn_impl, else the block-table kernel for kv-layout=paged "
            "on a TPU and xla elsewhere (stats: attn_impl); a pallas "
            "request the kernel registry would degrade is flagged by "
            "nns-lint NNS-W129",
        ),
        "prefill-chunks": PropSpec(
            "int", 0,
            desc="prefill buckets a pump may spend (paged): N caps a pump "
            "at N, the bound on the largest decode stall; 0=[llm] "
            "prefill_chunks, whose 0 follows the queue (one bucket per "
            "job waiting at the pump's start, at least 1)",
        ),
        "kv-memory-bound": PropSpec(
            "str", "", desc="declared KV HBM bound (lint NNS-W115)"
        ),
        # through-plane serving (serving_plane/llm.py,
        # docs/llm-serving.md): serversinks naming one plane share ONE
        # paged ContinuousBatcher — cross-stream admission rides the
        # deficit-round-robin scheduler, SLO ledgers stay per stream
        "plane": PropSpec(
            "str", "",
            desc="attach to the named process-wide LLM serving plane "
            "(shared paged batcher; requires kv-layout=paged)",
        ),
        "plane-weight": PropSpec(
            "float", 1.0,
            desc="this stream's weighted-fair admission share on the "
            "LLM plane (default 1.0)",
        ),
        # live migration + crash recovery (docs/llm-serving.md
        # "Migration & recovery"): paged private batchers only —
        # plane-shared batchers refuse these with a typed error
        "migrate-to": PropSpec(
            "str", "",
            desc="peer host:port[/llm-id] for drain-time live KV-span "
            "migration (requires kv-layout=paged)",
        ),
        "checkpoint-every-tokens": PropSpec(
            "int", 0,
            desc="write an atomic span checkpoint every N generated "
            "tokens per request (0 = off; requires kv-layout=paged)",
        ),
        "checkpoint-dir": PropSpec(
            "str", "",
            desc="span checkpoint directory — in-flight generations "
            "found here resume at startup (crash recovery)",
        ),
        # disaggregated prefill/decode serving (serving_plane/disagg.py,
        # docs/llm-serving.md "Disaggregated serving"): paged private
        # batchers only, same refusal taxonomy as migrate-to
        "role": PropSpec(
            "enum", "", ("", "prefill", "decode"),
            desc="disaggregated serving role: prefill runs chunked "
            "prefill then hands the KV span to a decode peer; decode "
            "advertises pool headroom and adopts handed-off spans "
            "(requires kv-layout=paged)",
        ),
        "decode-peers": PropSpec(
            "str", "",
            desc="comma-separated decode peers host:port[/llm-id] for "
            "role=prefill handoffs (refusal or unreachable peers fall "
            "back to local decode — tokens are never lost)",
        ),
    }

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        self.srv_id = str(self.get_property("id", "0"))
        # filter-style "k:v,k2:v2" option grammar (one parser for all
        # custom= props)
        from nnstreamer_tpu.backends.base import FilterProps

        options = FilterProps(
            custom=str(self.get_property("custom", ""))
        ).custom_dict()
        from nnstreamer_tpu.elements.base import _parse_bool

        from nnstreamer_tpu.config import conf

        cfg = conf()
        kv_layout = str(self.get_property("kv-layout", "")).strip() or (
            cfg.get("llm", "kv_layout", "slot")
        )
        if (
            str(self.get_property("plane", "") or "")
            and not str(self.get_property("kv-layout", "")).strip()
            and kv_layout == "slot"
        ):
            # plane= means "the shared paged batcher" — an unset
            # kv-layout follows the plane rather than the slot default
            kv_layout = "paged"
        block_size = int(self.get_property("block-size", 0)) or (
            cfg.get_int("llm", "block_size", 16)
        )
        kv_blocks = int(self.get_property("kv-blocks", 0)) or (
            cfg.get_int("llm", "kv_blocks", 0)
        )
        prefill_chunks = int(self.get_property("prefill-chunks", 0)) or (
            cfg.get_int("llm", "prefill_chunks", 0)
        )
        self._create_kw = dict(
            model=str(self.get_property("model", "zoo:transformer_lm")),
            options=options,
            n_slots=int(self.get_property("n-slots", 4)),
            max_len=int(self.get_property("max-len", 256)),
            prompt_len=int(self.get_property("prompt-len", 64)),
            default_new=int(self.get_property("max-new-tokens", 16)),
            stream=_parse_bool(self.get_property("stream", False)),
            speculate=(
                -1 if str(self.get_property("speculate", 0)) == "auto"
                else int(self.get_property("speculate", 0))
            ),
            speculate_model=str(self.get_property("speculate-model", "")),
            pump_tokens=int(self.get_property("pump", 1)),
            kv_layout=kv_layout,
            block_size=block_size,
            kv_blocks=kv_blocks,
            cache_dtype=str(self.get_property("cache-dtype", "auto")),
            prefill_chunks=prefill_chunks,
            attn_impl=str(self.get_property("attn-impl", "")).strip() or (
                cfg.get("llm", "attn_impl", "")
            ),
            plane=str(self.get_property("plane", "") or ""),
            plane_weight=float(self.get_property("plane-weight", 1.0)),
            srv_id=self.srv_id,
            migrate_to=str(self.get_property("migrate-to", "") or ""),
            checkpoint_every_tokens=int(
                self.get_property("checkpoint-every-tokens", 0)
            ),
            checkpoint_dir=str(
                self.get_property("checkpoint-dir", "") or ""
            ),
            role=str(self.get_property("role", "") or ""),
            decode_peers=str(self.get_property("decode-peers", "") or ""),
        )
        self._server: Optional[_LlmServer] = None

    def negotiate(self, in_specs: List[Spec]) -> List[Spec]:
        (spec,) = in_specs
        if not isinstance(spec, TensorsSpec):
            raise NegotiationError(f"{self.name}: needs tensor input")
        self._server = _get_server(self.srv_id, self._create_kw)
        return []

    def render(self, frame: Frame) -> None:
        self._server.submit(frame)

    def on_eos(self) -> None:
        if self._server is not None:
            self._server.eos = True

    def stop(self) -> None:
        if self._server is not None:
            self._server.eos = True
            self._server.stopped = True


@registry.element("tensor_llm_serversrc")
class LlmServerSrc(Source):
    """Emit one frame per completed generation: tokens [1, n] int32 with
    the submitting frame's meta preserved (client_id routing)."""

    FACTORY_NAME = "tensor_llm_serversrc"

    PROPERTIES = {
        "id": PropSpec("str", "0", desc="pairing key with the serversink"),
        "stream": PropSpec("bool", False),
    }

    def __init__(self, name=None, **props):
        super().__init__(name, **props)
        from nnstreamer_tpu.elements.base import _parse_bool

        self.srv_id = str(self.get_property("id", "0"))
        # stream=true: one frame per NEW token (meta: stream/done/
        # token_index + the request frame's meta incl. client_id), then a
        # final done frame carrying the full generation
        self.stream = _parse_bool(self.get_property("stream", False))
        # THIS run's server, held by object reference — the id string is
        # reusable across pipelines, so it never identifies the server
        self._server: Optional[_LlmServer] = None
        self._final_stats: Optional[Dict] = None
        self._burst = None  # the open nns.llm.emit span, if any

    def _acquired(self, srv: Optional[_LlmServer]) -> Optional[_LlmServer]:
        if srv is not None and self.stream:
            srv.stream = True
        return srv

    def start(self) -> None:
        # acquire the paired server eagerly so teardown before the first
        # generate() still releases it from the table (the sink creates
        # it at negotiate, which precedes every element's start). If the
        # id pairs across pipelines started out of order the table may
        # still be empty here — generate() keeps the lazy fallback.
        if self._server is None:
            with _table_lock:
                self._server = self._acquired(_table.get(self.srv_id))

    def stop(self) -> None:
        # pipeline teardown (drained or not) releases the server — model
        # params and KV caches must not outlive the pipeline in _table;
        # keep a final stats snapshot for post-run --stats readers
        self._end_burst()
        if self._final_stats is None:
            self._final_stats = self.serving_stats()
        _drop_server(self.srv_id, self._server)

    def serving_stats(self) -> Optional[Dict]:
        """Batcher counters for the executor's --stats surface (this
        run's server only, live or final snapshot)."""
        if self._final_stats is not None:
            return self._final_stats
        if self._server is not None:
            return self._server.stats()
        return None

    def output_spec(self) -> Spec:
        # generations vary in length per request → flexible
        return TensorsSpec(format=TensorFormat.FLEXIBLE)

    def generate(self):
        srv = self._server
        if srv is None:
            srv = self._server = self._acquired(_get_server(self.srv_id))
        item = srv.pop()
        if item is None:
            self._end_burst()
            if srv.drained:
                self._final_stats = srv.stats()
                _drop_server(self.srv_id, srv)
                return EOS_FRAME
            if not srv.pump():  # decode even while no prompts arrive
                # idle (no active slots): the executor re-polls
                # immediately, so bound the spin here
                _time.sleep(0.002)
            item = srv.pop()
            if item is None:
                return None
        if self._burst is None:
            # one span per burst of frames a pump left in the queue, held
            # open across generate calls (always this node's thread): it
            # covers the executor's push of each frame downstream, which
            # is the emission
            self._burst = _trace.span(
                "nns.llm.emit", frames=len(srv._out) + 1
            ).__enter__()
        toks, meta = item
        arr = np.asarray(toks, np.int32)[None, :]
        return Frame((arr,), meta=meta)

    def _end_burst(self) -> None:
        if self._burst is not None:
            self._burst.close()
            self._burst = None
