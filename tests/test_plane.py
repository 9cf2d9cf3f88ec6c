"""nns-plane serving plane (serving_plane/, docs/serving-plane.md):
cross-stream continuous batching with bitwise per-frame parity,
per-stream FIFO, weighted-fair scheduling with a starvation bound,
Hermes placement under memory bounds, replica failover through the
plane, per-stream fault/sanitizer accounting, the NNS-W114 lint, and
the observability surface (plane_* stats, nns-top --models)."""

import os
import threading

import jax
import numpy as np
import pytest

from nnstreamer_tpu.analysis import lint
from nnstreamer_tpu.backends.base import FilterProps
from nnstreamer_tpu.backends.fakes import ScalerBackend
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.pipeline.parse import parse_pipeline
from nnstreamer_tpu.serving_plane import (
    ModelPlane,
    PlacementError,
    PlaneConfig,
    plan_placement,
    resolve_plane_config,
)
from nnstreamer_tpu.serving_plane import plane as plane_mod
from nnstreamer_tpu.serving_plane.scheduler import (
    PlaneStream,
    StreamScheduler,
)
from nnstreamer_tpu.serving_plane.sharding import (
    MeshShardedProgram,
    VmapProgram,
)
from nnstreamer_tpu.tensors.frame import Frame
from nnstreamer_tpu.tensors.spec import TensorsSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(dims="4"):
    return TensorsSpec.from_strings(dims, "float32")


def _scaler(factor=3.0):
    b = ScalerBackend()
    b.open(FilterProps(
        framework="scaler", model=(), custom=f"factor:{factor}",
        input_spec=_spec(),
    ))
    return b


def _mlp_model(tmp_path, d=8, k=2.0):
    path = tmp_path / "mm.py"
    path.write_text(
        "import jax.numpy as jnp\n"
        "def get_model(options):\n"
        f"    return (lambda x: x * {k}), None\n"
    )
    return str(path)


class _Req:
    def __init__(self, frames):
        self.frames = frames


# ---------------------------------------------------------------------------
# scheduler: weighted-fair collection
# ---------------------------------------------------------------------------

class TestScheduler:
    def test_starvation_bound(self):
        """A flooded stream cannot keep a backlogged light stream out
        of ANY collection cycle: every round credits every backlogged
        stream, so the lights land in the very next batch."""
        sched = StreamScheduler()
        hot, l1, l2 = PlaneStream("hot"), PlaneStream("l1"), PlaneStream("l2")
        for s in (hot, l1, l2):
            sched.add(s)
        for i in range(64):
            hot.q.append(_Req([i]))
        l1.q.append(_Req(["a"]))
        l2.q.append(_Req(["b"]))
        batch = sched.collect(8)
        sids = [s.sid for s, _ in batch]
        assert "l1" in sids and "l2" in sids
        assert len(batch) == 8

    def test_weights_proportional(self):
        """weight=2 earns two slots per round where weight=1 earns one."""
        sched = StreamScheduler()
        a, b = PlaneStream("a", weight=1.0), PlaneStream("b", weight=2.0)
        sched.add(a)
        sched.add(b)
        for i in range(32):
            a.q.append(_Req([i]))
            b.q.append(_Req([i]))
        batch = sched.collect(9)
        counts = {"a": 0, "b": 0}
        for s, _ in batch:
            counts[s.sid] += 1
        assert counts["b"] == 2 * counts["a"]

    def test_fifo_per_stream(self):
        sched = StreamScheduler()
        a = PlaneStream("a")
        sched.add(a)
        for i in range(5):
            a.q.append(_Req([i]))
        batch = sched.collect(3)
        assert [r.frames[0] for _, r in batch] == [0, 1, 2]
        batch = sched.collect(3)
        assert [r.frames[0] for _, r in batch] == [3, 4]

    def test_window_atomic_under_frame_limit(self):
        """A request is a window: collection counts FRAMES and never
        splits a window, stopping before one that would overflow."""
        sched = StreamScheduler()
        a, b = PlaneStream("a"), PlaneStream("b")
        sched.add(a)
        sched.add(b)
        a.q.append(_Req([1, 2, 3]))
        b.q.append(_Req([4, 5, 6]))
        batch = sched.collect(4)
        # 3 frames taken; the second 3-frame window would overflow 4
        assert sum(len(r.frames) for _, r in batch) == 3
        assert sched.backlog == 3

    def test_fractional_weight_stays_work_conserving(self):
        """A lone backlogged stream with weight < 1 still fills the
        batch: weights scale RELATIVE share, never absolute pacing."""
        sched = StreamScheduler()
        slow = PlaneStream("slow", weight=0.1)
        sched.add(slow)
        for i in range(8):
            slow.q.append(_Req([i]))
        batch = sched.collect(4)
        assert len(batch) == 4

    def test_idle_stream_banks_no_credit(self):
        sched = StreamScheduler()
        a, b = PlaneStream("a"), PlaneStream("b")
        sched.add(a)
        sched.add(b)
        for i in range(8):
            a.q.append(_Req([i]))
        sched.collect(8)  # many rounds credit b while it idles
        assert b.deficit == 0.0


# ---------------------------------------------------------------------------
# plane core: parity, FIFO, fault isolation
# ---------------------------------------------------------------------------

class TestPlaneCore:
    def test_cross_stream_batch_parity_bitwise(self):
        """Batched cross-stream results must be bitwise identical to
        isolated per-frame invokes of the same backend."""
        iso = _scaler(3.0)
        shared = _scaler(3.0)
        plane = ModelPlane(
            "parity", PlaneConfig(max_batch=8, timeout_ms=1.0), [shared]
        )
        try:
            streams = [plane.attach(f"s{i}") for i in range(4)]
            frames = {
                i: [
                    np.arange(4, dtype=np.float32) + 10 * i + j
                    for j in range(6)
                ]
                for i in range(4)
            }
            outs = {}

            def drive(i, s):
                outs[i] = [
                    np.asarray(
                        plane.submit(s, Frame((x,))).tensors[0]
                    )
                    for x in frames[i]
                ]

            ts = [
                threading.Thread(target=drive, args=(i, s))
                for i, s in enumerate(streams)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            for i in range(4):
                for x, got in zip(frames[i], outs[i]):
                    (want,) = iso.invoke((x,))
                    assert np.array_equal(got, np.asarray(want))
                    assert got.dtype == np.asarray(want).dtype
            assert plane.stats()["dispatches"] >= 1
        finally:
            plane.close()
            iso.close()

    def test_per_stream_fifo_order(self):
        shared = _scaler(1.0)
        plane = ModelPlane(
            "fifo", PlaneConfig(max_batch=4, timeout_ms=0.5), [shared]
        )
        try:
            streams = [plane.attach(f"s{i}") for i in range(3)]
            seqs = {}

            def drive(i, s):
                got = []
                for j in range(20):
                    x = np.full(4, 100 * i + j, np.float32)
                    got.append(
                        float(
                            np.asarray(
                                plane.submit(s, Frame((x,))).tensors[0]
                            )[0]
                        )
                    )
                seqs[i] = got

            ts = [
                threading.Thread(target=drive, args=(i, s))
                for i, s in enumerate(streams)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            for i in range(3):
                assert seqs[i] == [100.0 * i + j for j in range(20)]
        finally:
            plane.close()

    def test_window_submission_round_trip(self):
        shared = _scaler(2.0)
        plane = ModelPlane(
            "win", PlaneConfig(max_batch=8, timeout_ms=0.5), [shared]
        )
        try:
            s = plane.attach("s0")
            windows = [
                (np.arange(4, dtype=np.float32) + j,) for j in range(5)
            ]
            outs = plane.submit_window(s, windows)
            assert len(outs) == 5
            for (x,), (y,) in zip(windows, outs):
                assert np.array_equal(np.asarray(y), x * 2.0)
            assert s.admitted == 5 and s.served == 5
        finally:
            plane.close()

    def test_fault_isolates_the_failing_stream(self):
        """A poisoned frame fails ITS stream's submit; batchmates from
        other streams still serve (the per-window split)."""

        class MarkerProgram:
            mode = "single"
            n_traces = 0

            def invoke(self, windows):
                outs = []
                for (x,) in windows:
                    if float(np.asarray(x)[0]) < 0:
                        raise RuntimeError("poisoned window")
                    outs.append((np.asarray(x) * 2.0,))
                return outs

            def invoke_one(self, w):
                return self.invoke([w])[0]

        plane = ModelPlane(
            "iso", PlaneConfig(max_batch=8, timeout_ms=2.0),
            backends=[], program=MarkerProgram(),
        )
        try:
            good, bad = plane.attach("good"), plane.attach("bad")
            results = {}

            def drive_good():
                results["good"] = [
                    np.asarray(
                        plane.submit(
                            good, Frame((np.full(4, j, np.float32),))
                        ).tensors[0]
                    )
                    for j in range(10)
                ]

            def drive_bad():
                errs = 0
                for j in range(10):
                    x = np.full(4, -1.0, np.float32)
                    try:
                        plane.submit(bad, Frame((x,)))
                    except RuntimeError:
                        errs += 1
                results["bad_errs"] = errs

            tg = threading.Thread(target=drive_good)
            tb = threading.Thread(target=drive_bad)
            tg.start(); tb.start(); tg.join(); tb.join()
            assert results["bad_errs"] == 10
            assert len(results["good"]) == 10
            for j, a in enumerate(results["good"]):
                assert np.array_equal(a, np.full(4, 2.0 * j, np.float32))
            assert bad.errors == 10 and good.errors == 0
        finally:
            plane.close()

    def test_close_gives_queued_requests_a_terminal_outcome(self):
        """A request queued at close time is either served or completed
        with PlaneClosedError — a waiter can never hang (the PR-6
        terminal-outcome discipline)."""
        shared = _scaler(1.0)
        plane = ModelPlane(
            "det", PlaneConfig(max_batch=8, timeout_ms=1.0), [shared]
        )
        s = plane.attach("s0")
        req = plane_mod._Req([(np.zeros(4, np.float32),)])
        with plane._cond:
            s.q.append(req)
        plane.close()
        assert req.done.wait(2.0)
        assert req.out is not None or isinstance(
            req.exc, plane_mod.PlaneClosedError
        )


# ---------------------------------------------------------------------------
# registry / config / property surface
# ---------------------------------------------------------------------------

class TestRegistryAndConfig:
    def test_refcounted_shared_backend(self):
        a = TensorFilter(framework="scaler", custom="factor:3", plane="rk1")
        b = TensorFilter(framework="scaler", custom="factor:3", plane="rk1")
        try:
            a.negotiate([_spec()])
            b.negotiate([_spec()])
            assert a.backend is b.backend
            assert plane_mod.get("rk1") is not None
            a.stop()
            assert plane_mod.get("rk1") is not None  # b still holds it
        finally:
            b.stop()
            a.stop()
        assert plane_mod.get("rk1") is None

    def test_signature_conflict_rejected(self):
        a = TensorFilter(framework="scaler", custom="factor:3", plane="rk2")
        a.negotiate([_spec()])
        try:
            b = TensorFilter(
                framework="scaler", custom="factor:9", plane="rk2"
            )
            with pytest.raises(ValueError, match="already bound"):
                b.negotiate([_spec()])
        finally:
            a.stop()

    def test_conflicting_modes_rejected(self):
        with pytest.raises(ValueError, match="shared-tensor-filter-key"):
            TensorFilter(framework="scaler", plane="x",
                         **{"shared-tensor-filter-key": "k"})
        with pytest.raises(ValueError, match="replicas"):
            TensorFilter(framework="scaler", plane="x", replicas=2)
        with pytest.raises(ValueError, match="fallback"):
            TensorFilter(framework="scaler", plane="x",
                         **{"fallback-framework": "passthrough"})

    def test_resolve_config_element_over_default(self, monkeypatch):
        f = TensorFilter(
            framework="scaler", plane="cfg",
            **{"plane-max-batch": "4", "plane-timeout-ms": "0.5",
               "plane-mode": "shard", "plane-devices": "2"},
        )
        cfg = resolve_plane_config([f])
        assert cfg.max_batch == 4 and cfg.timeout_ms == 0.5
        assert cfg.mode == "shard" and cfg.devices == 2
        monkeypatch.setenv("NNS_TPU_PLANE_MAX_BATCH", "16")
        f2 = TensorFilter(framework="scaler", plane="cfg2")
        assert resolve_plane_config([f2]).max_batch == 16

    def test_bad_plane_mode_rejected(self):
        # the filter resolves its plane config at CONSTRUCTION (to
        # window-match the local collector), so a bad mode fails there
        with pytest.raises(ValueError, match="plane-mode"):
            TensorFilter(framework="scaler", plane="m",
                         **{"plane-mode": "bogus"})

    def test_shard_over_more_devices_than_exist_is_an_error(self):
        """A plane asked to shard over more chips than jax has must
        refuse — it used to clamp to what exists and serve narrower
        than configured, in silence."""
        from nnstreamer_tpu.serving_plane.sharding import build_plane_program

        backend = ScalerBackend()
        backend.open(FilterProps(custom="factor:2.0"))
        cfg = PlaneConfig(mode="shard", devices=len(jax.devices()) + 1)
        with pytest.raises(ValueError, match="asks for"):
            build_plane_program([backend], cfg)
        ok = build_plane_program(
            [backend], PlaneConfig(mode="shard", devices=2)
        )
        assert isinstance(ok, MeshShardedProgram)

    def test_implicit_sharer_inherits_bound_config(self):
        """docs: 'the first attacher's resolved config binds the
        plane' — a later sharer with NO plane-* props inherits instead
        of colliding; explicitly conflicting knobs still fail."""
        a = TensorFilter(framework="scaler", custom="factor:3",
                         plane="inh1", **{"plane-max-batch": "32"})
        b = TensorFilter(framework="scaler", custom="factor:3",
                         plane="inh1")
        try:
            a.negotiate([_spec()])
            b.negotiate([_spec()])
            assert a.backend is b.backend
            assert b._plane.cfg.max_batch == 32  # inherited binding
            c = TensorFilter(framework="scaler", custom="factor:3",
                             plane="inh1", **{"plane-max-batch": "4"})
            with pytest.raises(ValueError, match="already bound"):
                c.negotiate([_spec()])
        finally:
            a.stop()
            b.stop()

    def test_device_pin_keeps_plane_batching(self, tmp_path):
        """plane= + device=N batches on chip N through the plane's own
        program — the pin is a FUSION barrier, not a batching barrier
        (without the plane_fn hook this silently degraded to a
        per-frame HostProgram loop)."""
        import jax

        from nnstreamer_tpu.serving_plane.sharding import (
            VmapProgram,
            build_plane_program,
        )

        model = _mlp_model(tmp_path)
        f = TensorFilter(framework="jax", model=model, input="4",
                         inputtype="float32", plane="pin1", device="1")
        try:
            f.negotiate([_spec()])
            prog = build_plane_program([f.backend], f._plane_cfg)
            assert isinstance(prog, VmapProgram)
            assert prog._device is jax.devices()[1]
            (out,) = prog.invoke(
                [(np.arange(4, dtype=np.float32),)]
            )[0]
            assert np.array_equal(
                np.asarray(out), np.arange(4, dtype=np.float32) * 2.0
            )
        finally:
            f.stop()

    def test_plane_defaults_local_batching_on(self):
        f = TensorFilter(framework="scaler", plane="d")
        from nnstreamer_tpu.pipeline.batching import resolve_batch_config

        cfg = resolve_batch_config([f])
        assert cfg.active  # local collector window-matched to the plane
        assert f.is_batch_capable()


# ---------------------------------------------------------------------------
# pipelines: executors sharing a plane, sanitizer accounting
# ---------------------------------------------------------------------------

def _run_streams(descs, timeout=60):
    pipes = [parse_pipeline(d) for d in descs]
    execs = [None] * len(pipes)
    errors = []

    def drive(i):
        try:
            execs[i] = pipes[i].run(timeout=timeout)
        except Exception as exc:  # noqa: BLE001 — assert below
            errors.append((i, exc))

    ts = [
        threading.Thread(target=drive, args=(i,))
        for i in range(len(pipes))
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
    return pipes, execs


class TestPipelines:
    def test_two_executors_one_plane(self):
        descs = [
            "tensorsrc dimensions=4 pattern=counter num-frames=25 ! "
            "tensor_filter framework=scaler custom=factor:2.0 "
            "plane=pp1 plane-max-batch=8 ! tensor_sink"
            for _ in range(2)
        ]
        pipes, execs = _run_streams(descs)
        for p in pipes:
            sink = next(
                e for e in p.elements if isinstance(e, TensorSink)
            )
            outs = [np.asarray(f.tensors[0]) for f in sink.frames]
            assert len(outs) == 25
            for j, a in enumerate(outs):
                assert np.array_equal(a, np.full(4, 2.0 * j, np.float32))
        rows = [
            row for ex in execs for row in ex.stats().values()
            if "plane_name" in row
        ]
        assert rows and rows[0]["plane_name"] == "pp1"
        assert rows[0]["plane_frames"] >= 25
        assert plane_mod.get("pp1") is None  # refcount drained

    def test_sanitizer_accounting_latch_per_stream(self, monkeypatch):
        """Clean EOS through a shared plane latches the sanitizer's
        offered == delivered accounting on every stream's filter node
        (and the run leaks no threads)."""
        monkeypatch.setenv("NNS_TPU_SANITIZE", "1")
        descs = [
            "tensorsrc dimensions=4 pattern=counter num-frames=15 ! "
            "tensor_filter framework=scaler custom=factor:2.0 "
            "plane=san1 plane-max-batch=4 ! tensor_sink"
            for _ in range(2)
        ]
        pipes, execs = _run_streams(descs)
        for ex in execs:
            assert ex.sanitizer is not None
            assert not ex.errors
            assert ex.totals()["balance"] == 0
            # NOTE: leaked_threads is not asserted — two sanitized
            # executors running concurrently legitimately see each
            # other's node threads in the external-thread diff

    def test_fault_policy_disposes_per_stream(self):
        """One stream feeds poisoned frames through a strict-shape
        chaos filter sharing the plane with a healthy stream: the
        poisoned stream's on-error=drop disposes ITS frames with
        accounting, the healthy stream delivers everything."""

        class MarkerProgram:
            mode = "single"
            n_traces = 0

            def invoke(self, windows):
                outs = []
                for (x,) in windows:
                    if float(np.asarray(x)[0]) >= 90.0:
                        raise RuntimeError("poisoned window")
                    outs.append((np.asarray(x),))
                return outs

            def invoke_one(self, w):
                return self.invoke([w])[0]

        # pre-register the plane with a marker program; filters attach
        # to it by name (the injected-program hook). A real backend
        # still rides along as the sharers' negotiation surface.
        cfg = PlaneConfig(max_batch=8, timeout_ms=1.0)
        plane = ModelPlane("fp1", cfg, backends=[_scaler(1.0)],
                           program=MarkerProgram())
        entry = {"plane": plane, "sig": None, "refs": 0,
                 "open_lock": threading.Lock()}
        plane_mod._planes["fp1"] = entry

        def acquire_patch(name, sig, cfg2, opener, cfg_explicit=True,
                          _orig=plane_mod.acquire):
            if name == "fp1":
                with plane_mod._registry_lock:
                    entry["refs"] += 1
                return plane
            return _orig(name, sig, cfg2, opener,
                         cfg_explicit=cfg_explicit)

        orig = plane_mod.acquire
        plane_mod.acquire = acquire_patch
        try:
            descs = [
                # healthy stream: counter frames 0..19 (< 90)
                "tensorsrc dimensions=4 pattern=counter num-frames=20 ! "
                "tensor_filter framework=scaler plane=fp1 "
                "plane-max-batch=8 ! tensor_sink",
                # poisoned stream: counter + 90 via a transform upstream
                "tensorsrc dimensions=4 pattern=counter num-frames=20 ! "
                "tensor_transform mode=arithmetic option=add:90.0 ! "
                "tensor_filter framework=scaler plane=fp1 "
                "plane-max-batch=8 on-error=drop name=poisoned ! "
                "tensor_sink",
            ]
            pipes, execs = _run_streams(descs)
            healthy_sink = next(
                e for e in pipes[0].elements if isinstance(e, TensorSink)
            )
            poisoned_sink = next(
                e for e in pipes[1].elements if isinstance(e, TensorSink)
            )
            assert len(healthy_sink.frames) == 20
            assert len(poisoned_sink.frames) == 0  # all dropped by policy
            tot = execs[1].totals()
            assert tot["dropped"].get("on-error-drop") == 20
            assert tot["balance"] == 0
        finally:
            plane_mod.acquire = orig
            plane_mod._planes.pop("fp1", None)
            plane.close()


# ---------------------------------------------------------------------------
# replica failover through the plane
# ---------------------------------------------------------------------------

class TestReplicas:
    def test_failover_through_plane(self):
        """mode=replicas over two chaos backends, one of which loses
        its device mid-run: every frame still serves (windows fail over
        whole), and the replica set records the failovers."""
        descs = [
            "tensorsrc dimensions=4 pattern=counter num-frames=30 ! "
            "tensor_filter framework=faulty "
            'custom="device_lost_at:3,only_replica:1" '
            "plane=rep1 plane-mode=replicas plane-devices=2 "
            "plane-max-batch=4 ! tensor_sink"
        ]
        pipes, execs = _run_streams(descs)
        sink = next(
            e for e in pipes[0].elements if isinstance(e, TensorSink)
        )
        assert len(sink.frames) == 30
        row = next(
            row for ex in execs for row in ex.stats().values()
            if "plane_name" in row
        )
        reps = row["plane_replicas"]
        assert reps["failovers"] >= 1
        assert reps["replicas"] == 2

    def test_exhaustion_raises_per_stream(self):
        """Both replicas dead: the stream's own error policy disposes
        (on-error=drop), the pipeline survives to EOS."""
        descs = [
            "tensorsrc dimensions=4 pattern=counter num-frames=10 ! "
            "tensor_filter framework=faulty "
            'custom="device_lost_at:1" '
            "plane=rep2 plane-mode=replicas plane-devices=2 "
            "plane-max-batch=2 on-error=drop "
            "retry-backoff-ms=1 ! tensor_sink"
        ]
        pipes, execs = _run_streams(descs)
        sink = next(
            e for e in pipes[0].elements if isinstance(e, TensorSink)
        )
        assert len(sink.frames) == 0
        assert execs[0].totals()["dropped"].get("on-error-drop") == 10


# ---------------------------------------------------------------------------
# mesh-sharded program
# ---------------------------------------------------------------------------

class TestSharded:
    def test_mesh_parity_with_single_device(self):
        import jax.numpy as jnp

        from nnstreamer_tpu.parallel.mesh import make_mesh
        from nnstreamer_tpu.pipeline.batching import default_buckets

        w = jnp.asarray(
            np.random.default_rng(0).standard_normal((8, 8))
            .astype(np.float32)
        )

        def fn(tensors):
            (x,) = tensors
            return (x @ w,)

        single = VmapProgram(fn, default_buckets(8))
        mesh = make_mesh(4, axes=("dp",))
        sharded = MeshShardedProgram(fn, mesh, max_batch=8)
        windows = [
            (np.random.default_rng(i).standard_normal((8,))
             .astype(np.float32),)
            for i in range(6)
        ]
        a = single.invoke(list(windows))
        b = sharded.invoke(list(windows))
        for (x,), (y,) in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    def test_oversized_batch_chunks_to_ladder(self):
        """A batch wider than the top bucket (explicit local max-batch
        beyond the plane's) chunks instead of computing a negative pad —
        which on a mesh-sharded program crashed the jit with a
        non-divisible global batch."""
        from nnstreamer_tpu.parallel.mesh import make_mesh
        from nnstreamer_tpu.pipeline.batching import default_buckets

        def double(ts):
            (x,) = ts
            return (x * 2.0,)

        windows = [
            (np.full(4, float(j), np.float32),) for j in range(5)
        ]
        for prog in (
            VmapProgram(double, default_buckets(4)),
            MeshShardedProgram(
                double, make_mesh(2, axes=("dp",)), max_batch=4
            ),
        ):
            outs = prog.invoke(list(windows))
            assert len(outs) == 5
            for j, (y,) in enumerate(outs):
                assert np.array_equal(
                    np.asarray(y), np.full(4, 2.0 * j, np.float32)
                )

    def test_shard_bucket_ladder_multiple_of_mesh(self):
        from nnstreamer_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(4, axes=("dp",))
        prog = MeshShardedProgram(lambda ts: ts, mesh, max_batch=8)
        assert prog.buckets == (4, 8)
        assert prog.bucket_for(3) == 4 and prog.bucket_for(5) == 8


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

class TestPlacement:
    def test_bound_respected(self):
        assert plan_placement([4, 4, 4, 4], 8, 2) == [0, 0, 1, 1]
        # chain locality: everything fits on one chip → one chip
        assert plan_placement([2, 2, 2], 8, 4) == [0, 0, 0]

    def test_no_fit_raises(self):
        with pytest.raises(PlacementError, match="over the per-device"):
            plan_placement([9], 8, 2)
        with pytest.raises(PlacementError, match="fits on no device"):
            plan_placement([4, 4, 4, 4, 4], 8, 2)

    def test_pins_are_hard_constraints(self):
        plan = plan_placement([2, 2, 2], 8, 4, pinned={1: 3})
        assert plan[1] == 3
        with pytest.raises(PlacementError, match="pinned"):
            plan_placement([8, 8], 8, 2, pinned={1: 0})

    def test_place_pipeline_splits_and_runs(self, tmp_path):
        model = _mlp_model(tmp_path)
        p = parse_pipeline(
            f"tensorsrc dimensions=4 pattern=counter num-frames=6 ! "
            f"tensor_filter framework=jax model={model} input=4 "
            f"inputtype=float32 name=f1 ! "
            f"tensor_filter framework=jax model={model} input=4 "
            f"inputtype=float32 name=f2 ! "
            f"tensor_sink"
        )
        from nnstreamer_tpu.serving_plane import place_pipeline

        # each stage ~32 activation bytes; a 50-byte bound forces the
        # second stage onto the next chip
        placement = place_pipeline(p, per_device_bytes=50, n_devices=2)
        assert placement == {"f1": 0, "f2": 1}
        assert p["f2"].backend._device is not None
        ex = p.run(timeout=60)
        sink = next(
            e for e in p.elements if isinstance(e, TensorSink)
        )
        outs = [np.asarray(f.tensors[0]) for f in sink.frames]
        assert len(outs) == 6
        for j, a in enumerate(outs):
            assert np.allclose(a, np.full(4, 4.0 * j, np.float32))

    def test_device_prop_pins_backend(self):
        f = TensorFilter(
            framework="scaler", custom="factor:2.0", device="1"
        )
        # rides the custom string into the backend open options
        assert "device:1" in f.fprops.custom

    def test_parse_bytes(self):
        from nnstreamer_tpu.serving_plane.placement import parse_bytes

        assert parse_bytes("256M") == 256 << 20
        assert parse_bytes("2K") == 2048
        assert parse_bytes("123") == 123


# ---------------------------------------------------------------------------
# lint + observability surface
# ---------------------------------------------------------------------------

class TestSurface:
    def test_w114_duplicate_model_fires(self, tmp_path):
        model = _mlp_model(tmp_path)
        r = lint(
            "tensorsrc dimensions=4 ! tee name=t "
            f"t. ! queue ! tensor_filter framework=jax model={model} "
            "input=4 inputtype=float32 name=a ! tensor_sink "
            f"t. ! queue ! tensor_filter framework=jax model={model} "
            "input=4 inputtype=float32 name=b ! tensor_sink"
        )
        assert "NNS-W114" in r.codes

    @pytest.mark.parametrize("fix", [
        "plane=p", "shared-tensor-filter-key=k",
    ])
    def test_w114_silent_with_sharing(self, fix, tmp_path):
        model = _mlp_model(tmp_path)
        r = lint(
            "tensorsrc dimensions=4 ! tee name=t "
            f"t. ! queue ! tensor_filter framework=jax model={model} "
            f"input=4 inputtype=float32 {fix} name=a ! tensor_sink "
            f"t. ! queue ! tensor_filter framework=jax model={model} "
            f"input=4 inputtype=float32 {fix} name=b ! tensor_sink"
        )
        assert "NNS-W114" not in r.codes

    def test_nns_top_models_view(self):
        from nnstreamer_tpu.obs.nns_top import render_models

        snap = {"nodes": {"f0": {
            "plane_name": "demo", "plane_mode": "single",
            "plane_devices": 1, "plane_streams": 3,
            "plane_queue_depth": 2, "plane_dispatches": 40,
            "plane_avg_batch": 5.5, "plane_occupancy_pct": 68.8,
            "plane_frames": 220,
            "plane_per_stream": {
                "s0": {"admitted": 80, "served": 78, "queued": 2,
                       "errors": 0, "weight": 1.0},
            },
        }, "f1": {"plane_name": "demo"}}}
        out = render_models(snap)
        assert "demo" in out and "s0" in out and "admitted=80" in out
        assert out.count("demo") == 1  # deduped across sharers
        assert "(no serving plane" in render_models({"nodes": {}})

    def test_plane_metrics_emitted(self, monkeypatch):
        from nnstreamer_tpu.obs import metrics as obs_metrics

        reg = obs_metrics.enable()
        try:
            shared = _scaler(1.0)
            plane = ModelPlane(
                "met1", PlaneConfig(max_batch=4, timeout_ms=0.5),
                [shared],
            )
            s = plane.attach("s0")
            plane.submit(s, Frame((np.zeros(4, np.float32),)))
            plane.close()
            h = reg.find("nns_plane_batch_occupancy", plane="met1")
            assert h is not None and h.count >= 1
            c = reg.find(
                "nns_plane_stream_served_total", plane="met1", stream="s0"
            )
            assert c is not None and c.value == 1
        finally:
            obs_metrics.disable()


# ---------------------------------------------------------------------------
# the multi-stream × multi-chip soak
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_multistream_multichip():
    """8 streams × a mesh-sharded plane over 4 virtual devices × a
    weighted mix, under sustained load: every stream's frames arrive,
    in order, with the plane's cross-stream batching engaged."""
    n, N = 8, 200
    descs = [
        f"tensorsrc dimensions=16 pattern=counter num-frames={N} ! "
        "tensor_filter framework=scaler custom=factor:2.0 plane=soak "
        "plane-mode=shard plane-devices=4 plane-max-batch=16 "
        f"plane-weight={1.0 + (i % 2)} ! tensor_sink"
        for i in range(n)
    ]
    pipes, execs = _run_streams(descs, timeout=300)
    for p in pipes:
        sink = next(e for e in p.elements if isinstance(e, TensorSink))
        outs = [np.asarray(f.tensors[0]) for f in sink.frames]
        assert len(outs) == N
        for j, a in enumerate(outs):
            assert np.array_equal(a, np.full(16, 2.0 * j, np.float32))
    row = next(
        row for ex in execs for row in ex.stats().values()
        if "plane_name" in row
    )
    assert row["plane_frames"] >= N
    assert plane_mod.get("soak") is None
