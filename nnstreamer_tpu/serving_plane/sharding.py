"""The device programs a ModelPlane dispatches collected batches to.

Three backings, one interface (``invoke(windows) -> outs`` where a
window is one frame's tensor tuple, plus ``invoke_one`` for the
heterogeneous/per-frame fallback):

- :class:`VmapProgram` — ONE device: ``jit(vmap(fn))`` per (signature,
  bucket) with the batching.py bucket ladder, optionally pinned to a
  specific device (placement). The cross-stream generalization of
  ``FusedSegment.process_batch``: same stacking, same padding
  discipline, same bounded trace count — so batched results stay
  bitwise-identical to isolated per-frame invokes.
- :class:`MeshShardedProgram` — N devices, data-parallel: the same
  vmapped program jitted with ``batch_sharding`` over a ``dp`` mesh
  axis (parallel/mesh.py), bucket ladder aligned to multiples of the
  mesh size so every dispatch divides evenly across chips. XLA GSPMD
  inserts the collectives; rows are computed independently, so
  per-frame parity holds exactly like the single-device case.
- :class:`ReplicatedProgram` — K single-device programs behind the
  PR-7 :class:`~nnstreamer_tpu.parallel.replicas.ReplicaSet`: windows
  round-robin over healthy replicas, a device-classified fault fails
  the in-flight window over to the next replica, repeated faults bench
  a replica, probes re-admit it (docs/resilience.md semantics at plane
  granularity).

Thread safety: a plane's service thread is the only invoker; the
programs keep no locks of their own (ReplicaSet locks internally).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.pipeline.batching import default_buckets

_log = get_logger("serving_plane.sharding")

Window = Tuple[Any, ...]


def _sig_of(window: Window) -> tuple:
    return tuple((tuple(t.shape), t.dtype) for t in window)


class VmapProgram:
    """``jit(vmap(fn))`` per (signature, bucket) over a bucket ladder.

    ``fn`` is the backend's traceable fn: ``(tensors tuple) -> tensors
    tuple``. ``device`` pins dispatch to one jax device (the placement
    planner's unit); ``in_shardings`` (a per-tensor
    :class:`~jax.sharding.NamedSharding` factory result) data-shards
    the stacked batch instead. ``n_traces`` counts cache fills so tests
    bound retracing at O(log max-batch), the FusedSegment contract.
    """

    mode = "single"

    def __init__(
        self,
        fn: Callable[[Window], Window],
        buckets: Sequence[int],
        device=None,
        in_shardings=None,
    ) -> None:
        self._fn = fn
        self.buckets = tuple(buckets)
        self._device = device
        self._in_shardings = in_shardings
        self._cache: Dict[tuple, Callable] = {}
        self.n_traces = 0

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _jitted(self, sig: tuple, bucket: int) -> Callable:
        import jax

        key = (sig, bucket)
        fn = self._cache.get(key)
        if fn is None:
            base = self._fn
            target = (
                jax.vmap(lambda *ts: tuple(base(ts)))
                if bucket else (lambda *ts: tuple(base(ts)))
            )
            kw = {}
            if self._in_shardings is not None and bucket:
                kw["in_shardings"] = tuple(
                    self._in_shardings for _ in sig
                )
            fn = jax.jit(target, **kw)
            self._cache[key] = fn
            self.n_traces += 1
        return fn

    def _place(self, cols: List[Any]) -> List[Any]:
        if self._device is None:
            return cols
        import jax

        return [jax.device_put(c, self._device) for c in cols]

    def invoke_one(self, window: Window) -> Window:
        tensors = window
        if self._device is not None:
            tensors = tuple(self._place(list(tensors)))
        return tuple(self._jitted(_sig_of(window), 0)(*tensors))

    def invoke(self, windows: List[Window]) -> List[Window]:
        import jax.numpy as jnp

        n = len(windows)
        if n == 1:
            return [self.invoke_one(windows[0])]
        sig = _sig_of(windows[0])
        if any(_sig_of(w) != sig for w in windows[1:]):
            # heterogeneous batch (flexible streams): per-frame
            # programs, identical semantics (FusedSegment parity rule)
            return [self.invoke_one(w) for w in windows]
        cap = self.buckets[-1]
        if n > cap:
            # a batch wider than the top bucket (a caller's explicit
            # max-batch= exceeding the plane's, or a scheduler taking
            # one oversized window) chunks to the ladder instead of
            # computing a NEGATIVE pad — which would silently pad
            # nothing and crash a mesh-sharded jit on the non-divisible
            # size
            out: List[Window] = []
            for i in range(0, n, cap):
                out.extend(self.invoke(windows[i:i + cap]))
            return out
        bucket = self.bucket_for(n)
        pad = bucket - n
        cols = []
        for i in range(len(windows[0])):
            rows = [w[i] for w in windows]
            if pad:
                rows.extend([windows[-1][i]] * pad)
            cols.append(jnp.stack(rows))
        outs = self._jitted(sig, bucket)(*self._place(cols))
        return [tuple(o[j] for o in outs) for j in range(n)]

    def stats(self) -> Dict[str, Any]:
        return {"mode": self.mode, "n_traces": self.n_traces}

    def close(self) -> None:
        self._cache.clear()


class MeshShardedProgram(VmapProgram):
    """Data-sharded plane program over an N-device ``dp`` mesh: bucket
    ladder in multiples of the mesh size (every dispatch divides evenly
    across chips — a 3-frame batch on a 4-chip mesh pads to 4, the
    padding-waste ledger counts the cost exactly like bucket padding)."""

    mode = "shard"

    def __init__(
        self,
        fn: Callable[[Window], Window],
        mesh,
        max_batch: int = 8,
    ) -> None:
        from nnstreamer_tpu.parallel.mesh import batch_sharding

        d = int(mesh.size)
        cap = max(d, ((max(1, int(max_batch)) + d - 1) // d) * d)
        buckets: List[int] = []
        b = d
        while b < cap:
            buckets.append(b)
            b *= 2
        buckets.append(cap)
        super().__init__(
            fn, buckets, in_shardings=batch_sharding(mesh, "dp")
        )
        self.mesh = mesh

    def stats(self) -> Dict[str, Any]:
        return {
            "mode": self.mode, "n_traces": self.n_traces,
            "mesh_devices": int(self.mesh.size),
        }


class HostProgram:
    """Per-frame (or host-batched) dispatch for backends with no
    traceable fn: the plane still shares ONE opened backend across all
    streams — the memory win survives — but device batching degrades
    to the backend's own ``invoke_batched`` (when it declared
    ``batchable``) or a per-frame loop."""

    mode = "host"

    def __init__(self, backend) -> None:
        self._backend = backend
        self.n_traces = 0

    def invoke_one(self, window: Window) -> Window:
        return tuple(self._backend.invoke(window))

    def invoke(self, windows: List[Window]) -> List[Window]:
        b = self._backend
        if getattr(b, "batchable", False) and len(windows) > 1:
            sig = _sig_of(windows[0])
            if all(_sig_of(w) == sig for w in windows[1:]):
                return [tuple(o) for o in b.invoke_batched(windows)]
        return [self.invoke_one(w) for w in windows]

    def stats(self) -> Dict[str, Any]:
        return {"mode": self.mode}

    def close(self) -> None:
        pass


class ReplicatedProgram:
    """K per-replica programs behind a ReplicaSet: load-balanced window
    dispatch with device-fault failover. Failover granularity is one
    collected window (the in-flight unit at this layer): a window on a
    dying replica re-dispatches WHOLE onto the next healthy one, frames
    in order, so per-stream FIFO survives a replica loss."""

    mode = "replicas"

    def __init__(
        self,
        programs: Sequence[Any],
        unhealthy_after: int = 3,
        probe_every: int = 64,
    ) -> None:
        from nnstreamer_tpu.parallel.replicas import ReplicaSet

        self.programs = list(programs)
        self._rs = ReplicaSet(
            [p.invoke for p in self.programs],
            unhealthy_after=unhealthy_after,
            probe_every=probe_every,
        )

    def invoke(self, windows: List[Window]) -> List[Window]:
        return self._rs.dispatch(windows)

    def invoke_one(self, window: Window) -> Window:
        return self._rs.dispatch([window])[0]

    @property
    def n_traces(self) -> int:
        return sum(getattr(p, "n_traces", 0) for p in self.programs)

    def replica_stats(self) -> Dict[str, Any]:
        return self._rs.stats()

    def stats(self) -> Dict[str, Any]:
        return {
            "mode": self.mode, "n_traces": self.n_traces,
            **{f"rep_{k}": v for k, v in self._rs.stats().items()},
        }

    def close(self) -> None:
        for p in self.programs:
            close = getattr(p, "close", None)
            if callable(close):
                close()


def build_plane_program(backends: Sequence[Any], cfg) -> Any:
    """Back a plane with the program its config asks for.

    ``mode=single``: one backend, one device (vmapped when traceable).
    ``mode=shard``: one backend data-sharded over ``cfg.devices`` chips.
    ``mode=replicas``: one program per opened backend (``cfg.devices``
    of them), device-pinned round-robin, behind ReplicaSet failover.
    A non-traceable backend degrades to :class:`HostProgram` (sharing
    without device batching) with a warning — except under ``replicas``,
    where per-replica host programs still fail over correctly.
    """
    import jax

    buckets = default_buckets(cfg.max_batch)
    if cfg.mode == "replicas":
        devs = jax.devices()
        programs = []
        for i, b in enumerate(backends):
            fn = b.traceable_fn()
            if fn is None:
                programs.append(HostProgram(b))
            else:
                programs.append(
                    VmapProgram(fn, buckets, device=devs[i % len(devs)])
                )
        return ReplicatedProgram(
            programs,
            unhealthy_after=cfg.unhealthy_after,
            probe_every=cfg.probe_every,
        )
    primary = backends[0]
    # the plane_fn hook (jax backend) hands out the raw fn even when a
    # device pin made traceable_fn refuse (a pin is a FUSION barrier,
    # not a batching barrier — the plane honors it itself), so
    # plane= device=N batches on chip N instead of silently degrading
    # to a per-frame host loop
    fn = device = None
    hook = getattr(primary, "plane_fn", None)
    if callable(hook):
        fn, device = hook()
    if fn is None:
        fn = primary.traceable_fn()
    if fn is None:
        if cfg.mode == "shard":
            _log.warning(
                "plane mode=shard needs a traceable backend; %s is "
                "host-bound — serving shared-but-unsharded",
                type(primary).__name__,
            )
        return HostProgram(primary)
    if cfg.mode == "shard":
        from nnstreamer_tpu.parallel.mesh import make_mesh

        n = max(1, int(cfg.devices))
        if n > len(jax.devices()):
            raise ValueError(
                f"plane mode=shard asks for {n} devices; jax has "
                f"{len(jax.devices())} — a silently narrower mesh would "
                "serve at a fraction of the capacity the config names"
            )
        if n == 1:
            return VmapProgram(fn, buckets, device=device)
        if device is not None:
            _log.warning(
                "plane mode=shard ignores the stage's device pin: the "
                "dp mesh governs placement"
            )
        mesh = make_mesh(n, axes=("dp",))
        return MeshShardedProgram(fn, mesh, max_batch=cfg.max_batch)
    return VmapProgram(fn, buckets, device=device)
