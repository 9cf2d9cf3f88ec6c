"""Async transfer engine: staged H2D, coalesced D2H, transfer accounting.

The device plane's three transfer disciplines (docs/streaming.md), built
for the resident streaming executor's double-buffered frame ring:

- **Staged H2D** (`stage_frame`, `stage_iter`): host tensors become
  device arrays via ``jax.device_put`` — an *async* call, so issuing the
  put for frame N+1 while frame N's compute occupies the device overlaps
  the wire time with useful work. On a process-local CPU backend the put
  is a pure pessimization (the "device" IS host memory, and the jitted
  call's own ingest is a plain — often zero-copy — memcpy), so staging
  there is a pass-through unless ``force`` asks for a real copy (the
  donation path needs one: ``jnp.asarray`` ALIASES host numpy buffers on
  CPU, and a donated alias would let the program scribble on the
  caller's array).
- **Coalesced D2H** (`FrameFetch`): a frame's (or a whole sink window's)
  tensors ride ONE ``copy_to_host_async`` instead of one per tensor —
  per-transfer latency dominates small results on a remote-attached
  device, so T tensors × W frames must not pay T·W round trips. A
  cached jitted packer bitcasts every tensor to a flat uint8 buffer and
  concatenates; the host side splits the single fetched buffer back by
  dtype/shape with numpy views (no second copy). Process-local CPU
  arrays skip the packer — ``np.asarray`` there is a memcpy, and the
  eager stack/concat ops the packer replaces cost more than they save.
- **Accounting** (`tally`, ``nns_transfer_bytes_total``): every byte
  that crosses the host↔device boundary through this module is counted
  by direction, so "adjacent fused segments hand off on device with
  ZERO host materialization" is an assertable number, not a hope.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional

import numpy as np

from nnstreamer_tpu.log import get_logger

_log = get_logger("transfer")


# -- transfer accounting ----------------------------------------------------

class TransferTally:
    """Process-local transfer byte/event counters (always on — the obs
    registry mirrors them into ``nns_transfer_bytes_total`` when metrics
    are enabled). One short lock per *event* (a frame's worth of
    tensors), never per tensor: the lock rides a boundary that already
    implies a host↔device copy."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.h2d_events = 0
        self.d2h_events = 0

    def count(self, direction: str, nbytes: int) -> None:
        with self._lock:
            if direction == "h2d":
                self.h2d_bytes += nbytes
                self.h2d_events += 1
            else:
                self.d2h_bytes += nbytes
                self.d2h_events += 1

    def reset(self) -> None:
        with self._lock:
            self.h2d_bytes = self.d2h_bytes = 0
            self.h2d_events = self.d2h_events = 0

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "h2d_events": self.h2d_events,
                "d2h_events": self.d2h_events,
            }


#: module-level tally: tests assert zero-materialization handoffs here;
#: the executor adds per-element obs counters on top.
tally = TransferTally()

_mirror_lock = threading.Lock()
_mirrored = {"h2d": 0, "d2h": 0}


def mirror_into(metrics) -> None:
    """Advance the ``nns_transfer_bytes_total`` counters to match the
    process tally. Watermark-based: several executors stopping in one
    process each publish only the not-yet-mirrored delta, so the
    global counter never double-counts shared traffic (per-run
    attribution lives in ``Executor.totals()["transfer"]``)."""
    snap = tally.snapshot()
    with _mirror_lock:
        for direction, key in (("h2d", "h2d_bytes"), ("d2h", "d2h_bytes")):
            delta = snap[key] - _mirrored[direction]
            if delta > 0:
                _mirrored[direction] += delta
                metrics.counter(
                    "nns_transfer_bytes_total", direction=direction
                ).inc(delta)


def _nbytes(tensors: Iterable[Any]) -> int:
    total = 0
    for t in tensors:
        size = getattr(t, "nbytes", None)
        if size is None:
            size = int(np.prod(t.shape)) * np.dtype(t.dtype).itemsize
        total += int(size)
    return total


# -- placement probes -------------------------------------------------------

def is_device_array(t: Any) -> bool:
    """True for arrays living behind a device runtime (jax.Array duck
    type) — numpy and scalars are host by definition."""
    return hasattr(t, "copy_to_host_async")


def _platform_of(t: Any) -> Optional[str]:
    try:
        devs = t.devices()
        for d in devs:
            return d.platform
    except Exception:  # noqa: BLE001 — deleted/donated array
        return None
    return None


def is_local_cpu(t: Any) -> bool:
    """True when ``t`` lives on a process-local CPU backend: fetching is
    a memcpy (or free), so neither the packer nor async staging pays."""
    return _platform_of(t) == "cpu"


_default_cpu: Optional[bool] = None


def default_backend_is_cpu() -> bool:
    """Cached ``jax.default_backend() == 'cpu'`` (the staging bypass
    decision is per-process, not per-frame)."""
    global _default_cpu
    if _default_cpu is None:
        import jax

        _default_cpu = jax.default_backend() == "cpu"
    return _default_cpu


def _cpu_target(device) -> bool:
    """True when staging would target process-local CPU memory — the
    default backend with no explicit device, or an explicit CPU device.
    Either way the put is a copy into the same RAM the tensor already
    occupies."""
    if device is None:
        return default_backend_is_cpu()
    return getattr(device, "platform", None) == "cpu"


# -- staged H2D -------------------------------------------------------------

def stage_frame(frame, device=None, force: bool = False):
    """Upload a frame's host tensors to ``device`` via async
    ``jax.device_put``; device-resident tensors pass through untouched.
    Returns the staged frame (the SAME frame object when nothing moved).

    On a process-local CPU backend the put is skipped unless ``force``:
    the jitted call ingests host numpy directly (zero-copy on aligned
    buffers), and an explicit put would add a copy for nothing. ``force``
    exists for the donation path, which must own a private device buffer
    (``jax.device_put`` COPIES host memory — post-submit mutation of the
    source array cannot reach the program)."""
    if not force and _cpu_target(device):
        return frame
    host_idx = [
        i for i, t in enumerate(frame.tensors) if not is_device_array(t)
    ]
    if not host_idx:
        return frame
    import jax

    tensors = list(frame.tensors)
    moved = [tensors[i] for i in host_idx]
    tally.count("h2d", _nbytes(moved))
    for i in host_idx:
        tensors[i] = jax.device_put(tensors[i], device)
    return frame.with_tensors(tensors)


def stage_iter(arrays: Iterable[Any], device=None, depth: int = 3) -> Iterator[Any]:
    """Pipeline ``jax.device_put`` uploads on a feeder thread, yielding
    staged device arrays in order with up to ``depth`` uploads in
    flight — the bench's streaming-ingest harness (H2D of frame N+1
    overlaps compute of frame N even when the put itself blocks). On a
    process-local CPU backend the arrays are
    yielded as-is: the jitted call's own ingest is the cheaper copy."""
    if _cpu_target(device):
        for a in arrays:
            yield a
        return
    import queue as queue_mod

    import jax

    q: queue_mod.Queue = queue_mod.Queue(maxsize=max(1, depth))
    _END = object()
    stop = threading.Event()
    err: List[BaseException] = []

    def _put(item) -> bool:
        # bounded put that gives up when the consumer abandoned the
        # generator — a plain q.put would park this thread forever
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue_mod.Full:
                continue
        return False

    def _feed() -> None:
        try:
            for a in arrays:
                if stop.is_set():
                    return
                tally.count("h2d", _nbytes((a,)))
                if not _put(jax.device_put(a, device)):
                    return
        except Exception as exc:  # noqa: BLE001 — re-raised by consumer
            err.append(exc)
        finally:
            _put(_END)

    th = threading.Thread(target=_feed, name="nns-h2d-stager", daemon=True)
    th.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                break
            yield item
        if err:
            # a mid-stream device_put failure must surface as an error,
            # not as a silently truncated stream (a bench loop counting
            # planned iterations would publish inflated fps)
            raise err[0]
    finally:
        stop.set()
        try:
            while True:  # unblock a feeder parked on a full queue
                q.get_nowait()
        except queue_mod.Empty:
            pass
        th.join(timeout=5.0)


# -- coalesced D2H ----------------------------------------------------------

# signature -> jitted packer. A signature is ((shape, dtype), ...) over
# every tensor in the fetch set; entries are tiny programs (bitcast +
# concat) and the set of signatures is bounded by the pipeline's
# negotiated specs × sink window sizes.
_packer_cache: Dict[tuple, Callable] = {}
_packer_lock = threading.Lock()


def _sig_of(tensors) -> tuple:
    return tuple((tuple(t.shape), np.dtype(t.dtype)) for t in tensors)


def _make_packer() -> Callable:
    import jax
    import jax.numpy as jnp
    from jax import lax

    def pack(*ts):
        parts = []
        for t in ts:
            if t.dtype == jnp.bool_:
                # bitcast rejects bool; uint8 has identical bytes
                t = t.astype(jnp.uint8)
            u = lax.bitcast_convert_type(t, jnp.uint8)
            parts.append(u.reshape(-1))
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    return jax.jit(pack)


def _packer_for(sig: tuple) -> Callable:
    with _packer_lock:
        fn = _packer_cache.get(sig)
        if fn is None:
            fn = _make_packer()
            _packer_cache[sig] = fn
    return fn


class FrameFetch:
    """One in-flight coalesced D2H fetch for an ordered set of device
    tensors (a frame's worth, or a whole sink window's).

    ``start`` dispatches the cached packer (one device-side flatten +
    concat) and begins ONE async host copy of the packed buffer;
    ``finish`` materializes numpy tensors by splitting the single
    fetched buffer with views. Anything that can't ride the packer —
    local CPU arrays, host tensors already, packer trace failures —
    degrades to per-tensor fetches, never an error: the fetch is an
    optimization, correctness lives in finish() always returning host
    arrays."""

    __slots__ = ("_tensors", "_sig", "_packed", "_dev_idx", "_per_tensor")

    def __init__(self, tensors: List[Any]) -> None:
        self._tensors = list(tensors)
        self._sig = None
        self._packed = None
        self._dev_idx: List[int] = []
        self._per_tensor = False

    def _fetch_per_tensor(self, dev_ts) -> "FrameFetch":
        """Shared degradation tail: one async copy per device tensor,
        best-effort (finish() materializes with np.asarray either
        way)."""
        self._per_tensor = True
        for t in dev_ts:
            try:
                t.copy_to_host_async()
            except Exception:  # noqa: BLE001 — fetch is best-effort
                pass
        return self

    def start(self) -> "FrameFetch":
        ts = self._tensors
        dev_idx = [i for i, t in enumerate(ts) if is_device_array(t)]
        dev_ts = [ts[i] for i in dev_idx]
        if not dev_ts:
            return self
        tally.count("d2h", _nbytes(dev_ts))
        if len(dev_ts) < 2 or is_local_cpu(dev_ts[0]):
            # a lone tensor is already one transfer; local CPU arrays
            # fetch by memcpy — the packer would only add dispatches
            return self._fetch_per_tensor(dev_ts)
        if len({_platform_of(t) for t in dev_ts}) > 1:
            # tensors pinned across devices can't share one packed
            # buffer without migrating them; per-tensor keeps placement
            return self._fetch_per_tensor(dev_ts)
        try:
            # only the DEVICE tensors ride the packer: jit-ingesting an
            # already-host tensor would pay a pointless H2D upload just
            # to copy the same bytes back; finish() splices host
            # tensors through untouched
            sig = _sig_of(dev_ts)
            packed = _packer_for(sig)(*dev_ts)
            packed.copy_to_host_async()
            self._sig = sig
            self._packed = packed
            self._dev_idx = dev_idx
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            _log.debug("packed fetch unavailable: %s", exc)
            return self._fetch_per_tensor(dev_ts)
        return self

    def finish(self) -> List[Any]:
        """Host (numpy) tensors, in order. Blocks only on whatever part
        of the async copy hasn't landed yet."""
        if self._packed is not None:
            buf = np.asarray(self._packed)
            fetched: List[Any] = []
            offset = 0
            for shape, dtype in self._sig:
                n = int(np.prod(shape)) * dtype.itemsize
                view = buf[offset:offset + n]
                if dtype == np.bool_:
                    arr = view.view(np.uint8).astype(np.bool_)
                else:
                    arr = view.view(dtype)
                fetched.append(arr.reshape(shape))
                offset += n
            out = list(self._tensors)
            for i, arr in zip(self._dev_idx, fetched):
                out[i] = arr
            return out
        return [
            np.asarray(t) if is_device_array(t) else t
            for t in self._tensors
        ]


def fetch_frame(frame) -> FrameFetch:
    """Start a coalesced async D2H for one frame's tensors."""
    return FrameFetch(list(frame.tensors)).start()


def fetch_window(frames: List[Any]) -> List[Any]:
    """Materialize a window of frames to host through ONE coalesced
    fetch across every tensor of every frame (the sink sync-window
    path), returning host-tensor frames in order. All-host windows
    (the executor-ceiling pipelines) return as-is — W×T ``is_device``
    probes are the only cost, not W new frame objects."""
    flat: List[Any] = []
    counts: List[int] = []
    for f in frames:
        counts.append(len(f.tensors))
        flat.extend(f.tensors)
    if not any(is_device_array(t) for t in flat):
        return frames
    fetched = FrameFetch(flat).start().finish()
    out = []
    i = 0
    for f, n in zip(frames, counts):
        out.append(f.with_tensors(fetched[i:i + n]).mark_synced())
        i += n
    return out


# -- stream (ring) configuration -------------------------------------------

def resolve_ring_depth(elems) -> int:
    """Resolve the in-flight frame ring depth for an execution node:
    the first member element's ``ring-depth`` property outranks the
    ``[executor] ring_depth`` config default (NNS_TPU_EXECUTOR_RING_DEPTH
    env over ini, the standard layering). Clamped to [1, 32]; 1 is the
    synchronous dispatch-and-deliver discipline."""
    from nnstreamer_tpu.config import conf

    raw = None
    for e in elems:
        raw = e.get_property("ring-depth")
        if raw is not None:
            break
    if raw is None:
        raw = conf().get("executor", "ring_depth", "2")
    try:
        depth = int(raw)
    except (TypeError, ValueError):
        _log.warning("ring-depth=%r is not an int; using 2", raw)
        depth = 2
    return max(1, min(32, depth))


def resolve_chain_mode(elems) -> str:
    """Resolve whole-chain compilation mode for one chain
    (pipeline/chain_program.py): ``off`` from ANY member element's
    ``chain-mode`` property outranks the ``[executor] chain_mode``
    config default (NNS_TPU_EXECUTOR_CHAIN_MODE env over ini) — one
    member opting out keeps the whole chain on the per-node parity
    path, mirroring how one non-traceable op severs fusion. Unknown
    values fall back to ``auto`` with a warning."""
    from nnstreamer_tpu.config import conf

    raw = None
    for e in elems:
        get = getattr(e, "get_property", None)
        got = get("chain-mode") if get is not None else None
        if got is not None:
            raw = str(got).strip().lower()
            if raw == "off":
                return "off"
    if raw is None:
        raw = str(conf().get("executor", "chain_mode", "auto")).strip().lower()
    if raw not in ("auto", "off"):
        _log.warning("chain-mode=%r not one of auto/off; using auto", raw)
        return "auto"
    return raw


def resolve_chain_unroll(elems) -> int:
    """Frames per compiled-chain launch window (``[executor]
    chain_unroll``, default 4, clamped to [1, 32]) — the STATIC ceiling;
    pipeline/chain_program.py further clamps it by the W124
    transient-HBM bound and the runtime OOM bucket governor rung."""
    from nnstreamer_tpu.config import conf

    raw = conf().get("executor", "chain_unroll", "4")
    try:
        unroll = int(raw)
    except (TypeError, ValueError):
        _log.warning("[executor] chain_unroll=%r is not an int; using 4", raw)
        unroll = 4
    return max(1, min(32, unroll))


def xray_crosscheck_enabled() -> bool:
    """``NNS_XRAY_CROSSCHECK`` env first, then ``[executor]
    xray_crosscheck`` (default off): the executor then compares the
    nns-xray static transfer prediction against this tally at stop()
    and logs the verdict — the cost model's verification loop
    (docs/chain-analysis.md)."""
    raw = os.environ.get("NNS_XRAY_CROSSCHECK")
    if raw is not None:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    from nnstreamer_tpu.config import conf

    return conf().get_bool("executor", "xray_crosscheck", False)


def donation_enabled() -> bool:
    """``[executor] donate`` (default on): donate node-OWNED activation
    buffers (staged H2D uploads, stacked batch windows) to the fused
    program so XLA reuses them for outputs instead of growing the
    arena. Only buffers this runtime itself created are ever donated —
    an upstream element's array may be shared or reused (tee fan-out,
    source frame pools), and donating one would delete it under the
    owner."""
    from nnstreamer_tpu.config import conf

    return conf().get_bool("executor", "donate", True)
