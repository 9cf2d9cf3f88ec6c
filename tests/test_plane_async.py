"""Async serving-plane submits (serving_plane/plane.py tickets +
the executor's plane window ring, docs/serving-plane.md): in-order
delivery at every ring depth, bitwise parity with blocking submits,
per-stream fault isolation of failed in-flight windows with totals
balance 0, a clean sanitizer latch, the LLM-through-plane path
(serving_plane/llm.py: greedy parity + the zero-gather pin), the
progress-scaled stall grant, and the NNS-W118 lint — plus the 8-stream
churn soak (slow).

Budget discipline: pipeline tests ride the scaler backend (no jit
compiles at all); the LLM test uses the smallest transformer config
and is the only cell that compiles."""

import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.backends.base import FilterProps
from nnstreamer_tpu.backends.fakes import ScalerBackend
from nnstreamer_tpu.elements.sink import TensorSink
from nnstreamer_tpu.pipeline.parse import parse_pipeline
from nnstreamer_tpu.serving_plane import plane as plane_mod
from nnstreamer_tpu.serving_plane.plane import (
    ModelPlane,
    PlaneClosedError,
    PlaneConfig,
)
from nnstreamer_tpu.tensors.frame import Frame
from nnstreamer_tpu.tensors.spec import TensorsSpec


def _spec(dims="4"):
    return TensorsSpec.from_strings(dims, "float32")


def _scaler(factor=3.0):
    b = ScalerBackend()
    b.open(FilterProps(
        framework="scaler", model=(), custom=f"factor:{factor}",
        input_spec=_spec(),
    ))
    return b


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


def _sink_values(pipe):
    sink = next(e for e in pipe.elements if isinstance(e, TensorSink))
    return [float(np.asarray(f.tensors[0])[0]) for f in sink.frames]


# ---------------------------------------------------------------------------
# ticket API: order, parity, accounting
# ---------------------------------------------------------------------------

class TestTickets:
    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_in_order_delivery_at_depth(self, depth):
        """Tickets redeemed oldest-first return each window's outputs
        in submission order at every ring depth (FIFO is structural:
        the plane pops each stream's queue left-to-right)."""
        plane = ModelPlane(
            "ord", PlaneConfig(max_batch=8, timeout_ms=0.5),
            [_scaler(2.0)],
        )
        try:
            s = plane.attach(f"d{depth}")
            ring = []
            got = []
            for j in range(12):
                w = [(np.full(4, float(j), np.float32),)]
                ring.append((j, plane.submit_window_async(s, w)))
                while len(ring) >= depth:
                    jj, req = ring.pop(0)
                    (out,) = plane.wait_window(s, req)
                    got.append((jj, float(np.asarray(out[0])[0])))
            while ring:
                jj, req = ring.pop(0)
                (out,) = plane.wait_window(s, req)
                got.append((jj, float(np.asarray(out[0])[0])))
            assert got == [(j, 2.0 * j) for j in range(12)]
            assert s.admitted == 12 and s.served == 12
            assert s.inflight == 0 and plane._inflight_total == 0
        finally:
            plane.close()

    def test_async_bitwise_parity_with_sync(self):
        """The same windows through async tickets and blocking submits
        produce bitwise-identical outputs (same program, same stacking
        — the ticket layer adds no math)."""
        plane = ModelPlane(
            "par", PlaneConfig(max_batch=8, timeout_ms=0.5),
            [_scaler(1.5)],
        )
        try:
            s1, s2 = plane.attach("sync"), plane.attach("async")
            rng = np.random.default_rng(7)
            windows = [
                [(rng.standard_normal(4).astype(np.float32),)]
                for _ in range(10)
            ]
            sync_outs = [
                plane.submit_window(s1, list(w)) for w in windows
            ]
            reqs = [
                plane.submit_window_async(s2, list(w)) for w in windows
            ]
            async_outs = [plane.wait_window(s2, r) for r in reqs]
            for a, b in zip(sync_outs, async_outs):
                assert np.array_equal(
                    np.asarray(a[0][0]), np.asarray(b[0][0])
                )
        finally:
            plane.close()

    def test_inflight_counters_and_gauge(self):
        """stream.inflight / the plane total track submitted-not-yet-
        collected tickets (the nns_plane_inflight_windows surface)."""
        plane = ModelPlane(
            "infl", PlaneConfig(max_batch=4, timeout_ms=0.0),
            [_scaler(1.0)],
        )
        try:
            s = plane.attach("s0")
            reqs = [
                plane.submit_window_async(
                    s, [(np.zeros(4, np.float32),)]
                )
                for _ in range(3)
            ]
            assert s.inflight == 3 and plane._inflight_total == 3
            assert plane.stats()["inflight"] == 3
            for r in reqs:
                plane.wait_window(s, r)
            assert s.inflight == 0 and plane._inflight_total == 0
            assert s.snapshot()["inflight"] == 0
        finally:
            plane.close()


# ---------------------------------------------------------------------------
# the stall grant (the plane.py "one more full window" fix)
# ---------------------------------------------------------------------------

class TestStallGrant:
    def test_wedged_service_thread_surfaces_fast_at_depth(self):
        """A wedged program (no dispatch progress) surfaces after at
        most ~2× submit_timeout_s even with a deep ring — depth must
        not scale the grant without progress (the masking the fix
        removes)."""

        class WedgeProgram:
            mode = "single"
            n_traces = 0

            def invoke(self, windows):
                time.sleep(1.0)
                return [w for w in windows]

            def invoke_one(self, w):
                return self.invoke([w])[0]

        plane = ModelPlane(
            "wedge",
            PlaneConfig(max_batch=4, timeout_ms=0.0,
                        submit_timeout_s=0.1),
            backends=[], program=WedgeProgram(),
        )
        s = plane.attach("s0")
        reqs = [
            plane.submit_window_async(s, [(np.zeros(4, np.float32),)])
            for _ in range(3)
        ]
        t0 = time.monotonic()
        with pytest.raises(PlaneClosedError):
            plane.wait_window(s, reqs[0])
        dt = time.monotonic() - t0
        # one unconditional extension only: ~2×0.1s, NOT (1+ahead)×
        assert dt < 1.0, f"wedge took {dt:.2f}s to surface"
        for r in reqs[1:]:
            with pytest.raises(PlaneClosedError):
                plane.wait_window(s, r)
        # the service thread is parked in the wedged program; close()
        # reaps what it can and the daemon thread dies with the sleep
        plane.close(join_timeout=0.1)

    def test_slow_but_progressing_plane_scales_the_grant(self):
        """A dispatch slower than submit_timeout_s but making progress
        must NOT fail a deep ring's tail ticket: the grant scales with
        the windows ahead while dispatches keep landing (the fixed
        2×timeout grant would false-positive here)."""

        class SlowProgram:
            mode = "single"
            n_traces = 0

            def invoke(self, windows):
                time.sleep(0.17)
                return [w for w in windows]

            def invoke_one(self, w):
                return self.invoke([w])[0]

        plane = ModelPlane(
            "slow",
            PlaneConfig(max_batch=1, timeout_ms=0.0,
                        submit_timeout_s=0.12),
            backends=[], program=SlowProgram(),
        )
        try:
            s = plane.attach("s0")
            reqs = [
                plane.submit_window_async(
                    s, [(np.zeros(4, np.float32),)]
                )
                for _ in range(3)
            ]
            # the LAST ticket waits ~3×0.17s ≈ 0.51s > 2×0.12s: only
            # the progress-scaled grant lets it complete
            for r in reqs:
                out = plane.wait_window(s, r)
                assert out is not None
            assert s.served == 3
        finally:
            plane.close()


# ---------------------------------------------------------------------------
# executor integration: pipelines with ring-depth
# ---------------------------------------------------------------------------

class TestPipelines:
    def test_async_pipeline_parity_and_order(self):
        """ring-depth=3 streams deliver every frame, in order, with
        values bitwise-equal to a blocking (depth 1) run of the same
        description."""
        def run(extra, plane):
            descs = [
                "tensorsrc dimensions=4 pattern=counter num-frames=30 ! "
                "tensor_filter framework=scaler custom=factor:2.0 "
                f"plane={plane} plane-max-batch=8 plane-timeout-ms=0.5 "
                f"{extra} ! tensor_sink"
                for _ in range(3)
            ]
            return _run_streams(descs)

        async_pipes, async_execs = run("ring-depth=3", "as1")
        sync_pipes, _ = run("", "bs1")
        want = [2.0 * j for j in range(30)]
        for pa, ps in zip(async_pipes, sync_pipes):
            assert _sink_values(pa) == want
            assert _sink_values(ps) == want
        for ex in async_execs:
            tot = ex.totals()
            assert tot["produced"] == tot["rendered"] == 30
            assert tot["balance"] == 0
        assert plane_mod.get("as1") is None  # refcount drained

    def test_async_fault_isolation_totals_balance(self):
        """One stream's poisoned frames fail their in-flight windows;
        the window splits per frame through THAT stream's on-error=drop
        gate (all 20 dropped with accounting, balance 0) while the
        healthy async stream delivers everything."""

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

        cfg = PlaneConfig(max_batch=8, timeout_ms=1.0)
        plane = ModelPlane("fa1", cfg, backends=[_scaler(1.0)],
                           program=MarkerProgram())
        entry = {"plane": plane, "sig": None, "refs": 0, "cfg": cfg,
                 "open_lock": threading.Lock()}
        plane_mod._planes["fa1"] = entry

        def acquire_patch(name, sig, cfg2, opener, cfg_explicit=True,
                          _orig=plane_mod.acquire):
            if name == "fa1":
                with plane_mod._registry_lock:
                    entry["refs"] += 1
                return plane
            return _orig(name, sig, cfg2, opener,
                         cfg_explicit=cfg_explicit)

        orig = plane_mod.acquire
        plane_mod.acquire = acquire_patch
        try:
            descs = [
                "tensorsrc dimensions=4 pattern=counter num-frames=20 ! "
                "tensor_filter framework=scaler plane=fa1 "
                "plane-max-batch=8 ring-depth=2 ! tensor_sink",
                "tensorsrc dimensions=4 pattern=counter num-frames=20 ! "
                "tensor_transform mode=arithmetic option=add:90.0 ! "
                "tensor_filter framework=scaler plane=fa1 "
                "plane-max-batch=8 ring-depth=2 on-error=drop "
                "name=poisoned ! tensor_sink",
            ]
            pipes, execs = _run_streams(descs)
            assert _sink_values(pipes[0]) == [float(j) for j in range(20)]
            assert len(_sink_values(pipes[1])) == 0
            tot = execs[1].totals()
            assert tot["dropped"].get("on-error-drop") == 20
            assert tot["balance"] == 0
            healthy_tot = execs[0].totals()
            assert healthy_tot["balance"] == 0
        finally:
            plane_mod.acquire = orig
            plane_mod._planes.pop("fa1", None)
            plane.close()

    def test_sanitizer_latch_clean_async(self, monkeypatch):
        """Clean EOS through async rings latches the sanitizer's
        offered == delivered accounting on every stream."""
        monkeypatch.setenv("NNS_TPU_SANITIZE", "1")
        descs = [
            "tensorsrc dimensions=4 pattern=counter num-frames=15 ! "
            "tensor_filter framework=scaler custom=factor:2.0 "
            "plane=sas1 plane-max-batch=4 ring-depth=3 ! tensor_sink"
            for _ in range(2)
        ]
        pipes, execs = _run_streams(descs)
        for ex in execs:
            assert ex.sanitizer is not None
            assert not ex.errors
            assert ex.totals()["balance"] == 0
        for p in pipes:
            assert len(_sink_values(p)) == 15

    def test_ring_depth_resolves_from_plane_inflight_config(
        self, monkeypatch
    ):
        """[plane] inflight (env NNS_TPU_PLANE_INFLIGHT) is the
        per-stream default; the element ring-depth property wins."""
        from nnstreamer_tpu.elements.filter import TensorFilter

        monkeypatch.setenv("NNS_TPU_PLANE_INFLIGHT", "2")
        f = TensorFilter(framework="scaler", plane="cfg1")
        assert f.plane_inflight == 2
        g = TensorFilter(
            framework="scaler", plane="cfg1", **{"ring-depth": "4"}
        )
        assert g.plane_inflight == 4
        monkeypatch.delenv("NNS_TPU_PLANE_INFLIGHT")
        h = TensorFilter(framework="scaler", plane="cfg1")
        assert h.plane_inflight == 1  # blocking default


# ---------------------------------------------------------------------------
# LLM pumps through a plane (serving_plane/llm.py)
# ---------------------------------------------------------------------------

class TestLlmPlane:
    def test_greedy_parity_and_zero_gather(self):
        """Two serversink/serversrc pairs share one plane-managed paged
        batcher: every generation matches solo greedy decode bitwise,
        SLO request rows stay per stream, and the block-native decode
        path stays gather-free through the plane."""
        from nnstreamer_tpu.elements.llm_serve import (
            LlmServerSink,
            LlmServerSrc,
        )
        from nnstreamer_tpu.elements.sink import AppSink
        from nnstreamer_tpu.elements.sources import AppSrc
        from nnstreamer_tpu.models import decode as dec
        from nnstreamer_tpu.pipeline.graph import Pipeline
        from nnstreamer_tpu.serving_plane import llm as llm_plane
        from nnstreamer_tpu.tensors.spec import TensorFormat

        opts = "vocab:127,d_model:16,n_heads:2,n_layers:1,seed:9"

        rng = np.random.default_rng(11)
        # ONE prompt length: the solo-decode reference compiles one
        # program instead of one per length (tier-1 budget)
        prompts = {
            f"s{k}r{i}": rng.integers(1, 127, (6,)).astype(np.int32)
            for k in range(2) for i in range(2)
        }
        pipes, ends = [], {}
        for k in range(2):
            src = AppSrc(spec=TensorsSpec(format=TensorFormat.FLEXIBLE))
            sink = LlmServerSink(**{
                "id": f"tpl{k}", "model": "zoo:transformer_lm",
                "custom": opts, "n-slots": 2, "max-len": 16,
                "prompt-len": 8, "max-new-tokens": 4, "pump": 2,
                "plane": "test_llm", "block-size": 8, "kv-blocks": 8,
            })
            osrc = LlmServerSrc(**{"id": f"tpl{k}"})
            osink = AppSink()
            p = Pipeline().chain(src, sink)
            p.chain(osrc, osink)
            p.start()
            pipes.append(p)
            ends[k] = (src, osink, osrc)
        results, stats = {}, {}
        try:
            pl = llm_plane.get("test_llm")
            assert pl is not None and len(pl._sched) == 2
            # greedy oracle off the SHARED batcher's own params (same
            # seed; avoids a second model init for the reference)
            params = pl.cb.params

            def alone(prompt, n):
                toks = dec.generate(
                    params, np.asarray(prompt, np.int32)[None, :], 2, n
                )
                return [int(t) for t in np.asarray(toks)[0]]

            for k, (src, _, _) in ends.items():
                for name, pr in prompts.items():
                    if name.startswith(f"s{k}"):
                        src.push(Frame(
                            (pr,),
                            meta={"req": name, "deadline_ms": 60000},
                        ))
                src.end_of_stream()
            for k, (_, osink, osrc) in ends.items():
                for _ in range(2):
                    f = osink.pop(timeout=120)
                    assert f is not None, "llm plane drained early"
                    results[f.meta["req"]] = [
                        int(t) for t in np.asarray(f.tensors[0])[0]
                    ]
                stats[k] = osrc.serving_stats()
        finally:
            for p in pipes:
                p.stop()
        for name, pr in prompts.items():
            assert results[name] == alone(pr, 4), f"{name} diverged"
        for k in range(2):
            st = stats[k]
            # the plane serves the same paged decode a solo sink does
            assert st["attn_impl"] == "xla" and "kv_attn" not in st
            # per-stream SLO ledgers: each src reports ONLY its own
            reqs = st["requests"]
            assert len(reqs) == 2
            assert all(
                r.get("deadline_s") is not None for r in reqs.values()
            )
            assert st["stream_served"] == 2
        assert llm_plane.get("test_llm") is None  # refcount drained

    def test_plane_rejects_incompatible_modes(self):
        from nnstreamer_tpu.elements.base import ElementError
        from nnstreamer_tpu.elements.llm_serve import _LlmServer

        kw = dict(
            model="zoo:transformer_lm",
            options={"vocab": "127", "d_model": "16", "n_heads": "2",
                     "n_layers": "1"},
            n_slots=2, max_len=32, prompt_len=16, default_new=4,
        )
        with pytest.raises(ElementError, match="kv-layout=paged"):
            _LlmServer(**kw, plane="bad1", kv_layout="slot")
        with pytest.raises(ElementError, match="speculate"):
            _LlmServer(**kw, plane="bad2", kv_layout="paged",
                       speculate=4)
        with pytest.raises(ElementError, match="stream"):
            _LlmServer(**kw, plane="bad3", kv_layout="paged",
                       stream=True)


# ---------------------------------------------------------------------------
# NNS-W118 (both ways)
# ---------------------------------------------------------------------------

class TestW118:
    def test_fires_on_multi_stream_depth1(self):
        from nnstreamer_tpu.analysis.lint import lint

        desc = (
            "tensorsrc dimensions=4 num-frames=1 ! tensor_filter "
            "framework=scaler custom=factor:2.0 plane=w1 ! tensor_sink "
            "tensorsrc dimensions=4 num-frames=1 ! tensor_filter "
            "framework=scaler custom=factor:2.0 plane=w1 ! tensor_sink"
        )
        r = lint(desc)
        assert "NNS-W118" in [d.code for d in r.report.diagnostics]

    def test_fires_on_ring_depth_without_batching(self):
        from nnstreamer_tpu.analysis.lint import lint

        r = lint(
            "tensorsrc dimensions=4 num-frames=1 ! tensor_filter "
            "framework=scaler custom=factor:2.0 plane=w2 ring-depth=3 "
            "batching=false ! tensor_sink"
        )
        assert "NNS-W118" in [d.code for d in r.report.diagnostics]

    def test_silent_with_ring_and_single_stream(self):
        from nnstreamer_tpu.analysis.lint import lint

        # single stream at depth 1: nothing to overlap across — silent
        r = lint(
            "tensorsrc dimensions=4 num-frames=1 ! tensor_filter "
            "framework=scaler custom=factor:2.0 plane=w3 ! tensor_sink"
        )
        assert "NNS-W118" not in [d.code for d in r.report.diagnostics]
        # multi-stream with rings armed: the fixed shape — silent
        desc = (
            "tensorsrc dimensions=4 num-frames=1 ! tensor_filter "
            "framework=scaler custom=factor:2.0 plane=w4 ring-depth=2 "
            "! tensor_sink "
            "tensorsrc dimensions=4 num-frames=1 ! tensor_filter "
            "framework=scaler custom=factor:2.0 plane=w4 ring-depth=2 "
            "! tensor_sink"
        )
        r = lint(desc)
        assert "NNS-W118" not in [d.code for d in r.report.diagnostics]


# ---------------------------------------------------------------------------
# the churn soak
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_8stream_async_churn():
    """8 async streams (mixed ring depths and weights) × 200 frames
    under sustained load: every stream's frames arrive, in order, with
    the in-flight rings engaged and the accounting balanced."""
    n, N = 8, 200
    descs = [
        f"tensorsrc dimensions=16 pattern=counter num-frames={N} ! "
        "tensor_filter framework=scaler custom=factor:2.0 plane=asoak "
        f"plane-max-batch=16 ring-depth={1 + (i % 3)} "
        f"plane-weight={1.0 + (i % 2)} max-batch=2 ! tensor_sink"
        for i in range(n)
    ]
    pipes, execs = _run_streams(descs, timeout=300)
    for p in pipes:
        sink = next(e for e in p.elements if isinstance(e, TensorSink))
        vals = [float(np.asarray(f.tensors[0])[0]) for f in sink.frames]
        assert vals == [2.0 * j for j in range(N)]
    for ex in execs:
        tot = ex.totals()
        assert tot["produced"] == tot["rendered"] == N
        assert tot["balance"] == 0
    assert plane_mod.get("asoak") is None
