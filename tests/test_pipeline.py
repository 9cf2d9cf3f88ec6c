"""Pipeline runtime tests: graph building, negotiation, fusion, executor.

Mirrors reference coverage in tests/nnstreamer_plugins/unittest_plugins.cc
(programmatic pipelines with appsrc/appsink) and the SSAT pipeline tests.
"""

import numpy as np
import pytest

from nnstreamer_tpu.elements.base import NegotiationError
from nnstreamer_tpu.elements.sources import AppSrc, TensorSrc, VideoTestSrc
from nnstreamer_tpu.elements.converter import TensorConverter
from nnstreamer_tpu.elements.transform import TensorTransform
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.elements.sink import AppSink, FakeSink, TensorSink
from nnstreamer_tpu.elements.flow import Queue, Tee
from nnstreamer_tpu.pipeline.graph import Pipeline
from nnstreamer_tpu.tensors.spec import DType, TensorsSpec


def run_chain(*elems, timeout=30):
    p = Pipeline().chain(*elems)
    p.run(timeout=timeout)
    return p


class TestBasicChain:
    def test_video_to_sink(self):
        src = VideoTestSrc(width=32, height=24, **{"num-frames": 5})
        conv = TensorConverter()
        sink = TensorSink()
        run_chain(src, conv, sink)
        assert sink.rendered == 5
        assert sink.eos_seen
        assert sink.frames[0].tensors[0].shape == (1, 24, 32, 3)
        assert sink.frames[0].tensors[0].dtype == np.uint8

    def test_deterministic_source(self):
        def collect():
            src = VideoTestSrc(width=8, height=8, **{"num-frames": 3})
            conv = TensorConverter()
            sink = TensorSink()
            run_chain(src, conv, sink)
            return [np.asarray(f.tensors[0]) for f in sink.frames]

        a, b = collect(), collect()
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_pts_synthesized(self):
        src = VideoTestSrc(width=8, height=8, **{"num-frames": 3}, framerate="10/1")
        conv = TensorConverter()
        sink = TensorSink()
        run_chain(src, conv, sink)
        pts = [f.pts for f in sink.frames]
        assert pts == [0, 100_000_000, 200_000_000]

    def test_frames_per_tensor_batching(self):
        src = VideoTestSrc(width=8, height=8, **{"num-frames": 6})
        conv = TensorConverter(**{"frames-per-tensor": 3})
        sink = TensorSink()
        run_chain(src, conv, sink)
        assert sink.rendered == 2
        assert sink.frames[0].tensors[0].shape == (3, 8, 8, 3)

    def test_partial_batch_dropped(self):
        src = VideoTestSrc(width=8, height=8, **{"num-frames": 5})
        conv = TensorConverter(**{"frames-per-tensor": 3})
        sink = TensorSink()
        run_chain(src, conv, sink)
        assert sink.rendered == 1

    def test_frames_per_tensor_device_frames_batch_on_device(self):
        """Device-born frames batch via jnp.stack INSIDE the converter
        (one async device op) — never through np.asarray, which would
        cost a D2H round trip per frame on the chained-device path the
        batching exists to accelerate. Values must match the host path
        exactly."""
        import jax

        outs = {}
        for dev in (False, True):
            src = VideoTestSrc(
                width=8, height=8, device=str(dev).lower(),
                **{"num-frames": 6},
            )
            conv = TensorConverter(**{"frames-per-tensor": 3})
            sink = TensorSink()
            run_chain(src, conv, sink)
            assert sink.rendered == 2
            t = sink.frames[0].tensors[0]
            if dev:
                # the converter's OUTPUT stays device-resident; the
                # sink's to_host materializes it (egress boundary)
                assert sink.frames[0].tensors[0].shape == (3, 8, 8, 3)
            outs[dev] = np.asarray(t)
        np.testing.assert_array_equal(outs[False], outs[True])


class TestTransform:
    def _run(self, mode, option, data, dims="4", types="float32"):
        src = AppSrc(iterable=[(data,)], spec=TensorsSpec.from_strings(dims, types))
        tr = TensorTransform(mode=mode, option=option)
        sink = TensorSink()
        run_chain(src, tr, sink)
        return np.asarray(sink.frames[0].tensors[0])

    def test_typecast(self):
        out = self._run("typecast", "uint8", np.array([1.7, 2.2, 3.9, 4.0], np.float32))
        assert out.dtype == np.uint8
        np.testing.assert_array_equal(out, [1, 2, 3, 4])

    def test_arithmetic_chain(self):
        out = self._run(
            "arithmetic",
            "typecast:float32,add:-127.5,div:127.5",
            np.array([0, 127.5, 255, 51], np.float32),
        )
        np.testing.assert_allclose(out, [-1.0, 0.0, 1.0, -0.6], atol=1e-6)

    def test_transpose(self):
        # reference option 1:0:2:3 swaps the two innermost dims
        data = np.arange(24, dtype=np.float32).reshape(1, 2, 3, 4)
        src = AppSrc(iterable=[(data,)], spec=TensorsSpec.from_strings("4:3:2:1", "float32"))
        tr = TensorTransform(mode="transpose", option="1:0:2:3")
        sink = TensorSink()
        run_chain(src, tr, sink)
        out = np.asarray(sink.frames[0].tensors[0])
        np.testing.assert_array_equal(out, data.transpose(0, 1, 3, 2))

    def test_dimchg(self):
        # dimchg 0:2 moves innermost (channels) to position 2: NHWC→NCHW-ish
        data = np.arange(24, dtype=np.float32).reshape(1, 2, 3, 4)
        src = AppSrc(iterable=[(data,)], spec=TensorsSpec.from_strings("4:3:2:1", "float32"))
        tr = TensorTransform(mode="dimchg", option="0:2")
        sink = TensorSink()
        run_chain(src, tr, sink)
        out = np.asarray(sink.frames[0].tensors[0])
        assert out.shape == (1, 4, 2, 3)

    def test_clamp(self):
        out = self._run("clamp", "0:1", np.array([-2.0, 0.5, 3.0, 1.0], np.float32))
        np.testing.assert_allclose(out, [0.0, 0.5, 1.0, 1.0])

    def test_stand_default(self):
        x = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
        out = self._run("stand", "default", x)
        np.testing.assert_allclose(out.mean(), 0.0, atol=1e-6)
        np.testing.assert_allclose(out.std(), 1.0, atol=1e-4)

    def test_bad_mode_raises(self):
        with pytest.raises(ValueError):
            TensorTransform(mode="nonsense")


class TestFilterInPipeline:
    def test_fused_chain_filter(self):
        src = VideoTestSrc(width=16, height=16, **{"num-frames": 4})
        conv = TensorConverter()
        tr = TensorTransform(mode="typecast", option="float32")
        filt = TensorFilter(framework="scaler", custom="factor:0.5")
        sink = TensorSink()
        p = Pipeline().chain(src, conv, tr, filt, sink)
        plan = p.compile_plan()
        # converter + transform + filter fuse into ONE segment (the
        # converter's HWC→NHWC reshape is traceable since r3)
        assert any(len(seg.ops) == 3 for seg in plan.segments)
        p.run(timeout=60)
        assert sink.rendered == 4

    def test_filter_output_parity_with_single(self):
        from nnstreamer_tpu.single import SingleShot

        data = np.random.default_rng(0).random((1, 8, 8, 3)).astype(np.float32)
        src = AppSrc(iterable=[(data,)], spec=TensorsSpec.from_strings("3:8:8:1", "float32"))
        filt = TensorFilter(framework="average")
        sink = TensorSink()
        run_chain(src, filt, sink)
        with SingleShot(
            framework="average",
            input_spec=TensorsSpec.from_strings("3:8:8:1", "float32"),
        ) as s:
            (want,) = s.invoke(data)
        np.testing.assert_allclose(
            np.asarray(sink.frames[0].tensors[0]), np.asarray(want), rtol=1e-6
        )

    def test_input_output_combination(self):
        data = np.ones((1, 4), np.float32)
        extra = np.full((1, 2), 7.0, np.float32)
        src = AppSrc(
            iterable=[(data, extra)],
            spec=TensorsSpec.from_strings("4:1,2:1", "float32,float32"),
        )
        filt = TensorFilter(
            framework="scaler",
            custom="factor:2",
            **{"input-combination": "i0", "output-combination": "o0,i1"},
        )
        sink = TensorSink()
        run_chain(src, filt, sink)
        f = sink.frames[0]
        assert f.num_tensors == 2
        np.testing.assert_allclose(np.asarray(f.tensors[0]), 2.0)
        np.testing.assert_allclose(np.asarray(f.tensors[1]), 7.0)


class TestTeeAndQueue:
    def test_tee_two_branches(self):
        src = TensorSrc(dimensions="4", **{"num-frames": 5})
        tee = Tee(name="t")
        s1, s2 = TensorSink(name="s1"), TensorSink(name="s2")
        q1, q2 = Queue(), Queue()
        p = Pipeline()
        p.chain(src, tee)
        p.link(tee, q1).link(q1, s1)
        p.link(tee, q2).link(q2, s2)
        p.run(timeout=30)
        assert s1.rendered == 5 and s2.rendered == 5

    def test_queue_splits_fusion(self):
        src = TensorSrc(dimensions="4", **{"num-frames": 2})
        t1 = TensorTransform(mode="arithmetic", option="add:1")
        q = Queue()
        t2 = TensorTransform(mode="arithmetic", option="mul:3")
        sink = TensorSink()
        p = Pipeline().chain(src, t1, q, t2, sink)
        plan = p.compile_plan()
        assert all(len(seg.ops) == 1 for seg in plan.segments)
        p.run(timeout=30)
        np.testing.assert_allclose(np.asarray(sink.frames[0].tensors[0]), 3.0)
        np.testing.assert_allclose(np.asarray(sink.frames[1].tensors[0]), 6.0)


class TestNegotiationErrors:
    def test_filter_on_media_link(self):
        src = VideoTestSrc(width=8, height=8)
        filt = TensorFilter(framework="passthrough")
        p = Pipeline().chain(src, filt, FakeSink())
        with pytest.raises(NegotiationError, match="tensor_converter"):
            p.negotiate()

    def test_unlinked_pad(self):
        p = Pipeline()
        p.add(TensorTransform(mode="typecast", option="uint8"))
        with pytest.raises(NegotiationError):
            p.negotiate()

    def test_cycle_detected(self):
        a = TensorTransform(mode="typecast", option="float32")
        b = TensorTransform(mode="typecast", option="float32")
        p = Pipeline().link(a, b).link(b, a)
        with pytest.raises(NegotiationError, match="cycle"):
            p.negotiate()


class TestErrorPropagation:
    def test_runtime_error_surfaces(self):
        def boom(frame, options):
            raise RuntimeError("decoder exploded")

        from nnstreamer_tpu.elements.decoder import (
            TensorDecoder,
            register_custom_decoder,
            unregister_custom_decoder,
        )

        register_custom_decoder("boom", boom)
        try:
            src = TensorSrc(dimensions="2", **{"num-frames": 2})
            dec = TensorDecoder(mode="custom-code", option1="boom")
            p = Pipeline().chain(src, dec, FakeSink())
            with pytest.raises(RuntimeError, match="decoder exploded"):
                p.run(timeout=30)
        finally:
            unregister_custom_decoder("boom")


class TestCustomConverter:
    def test_custom_code_converter(self):
        """mode=custom-code:<name> runs a registered in-process callable
        (reference nnstreamer_converter_custom_register)."""
        from nnstreamer_tpu.elements.converter import (
            register_custom_converter,
            unregister_custom_converter,
        )

        def flatten(frame, props):
            img = np.asarray(frame.tensors[0])
            return frame.with_tensors((img.reshape(1, -1).astype(np.int32),))

        register_custom_converter("flat", flatten)
        try:
            src = VideoTestSrc(width=8, height=8, **{"num-frames": 3})
            conv = TensorConverter(mode="custom-code:flat")
            sink = TensorSink()
            run_chain(src, conv, sink)
            assert sink.rendered == 3
            assert sink.frames[0].tensors[0].shape == (1, 8 * 8 * 3)
            assert sink.frames[0].tensors[0].dtype == np.int32
        finally:
            unregister_custom_converter("flat")

    def test_unregistered_custom_converter_fails_negotiation(self):
        src = VideoTestSrc(width=8, height=8, **{"num-frames": 1})
        conv = TensorConverter(mode="custom-code:nope")
        p = Pipeline().chain(src, conv, FakeSink())
        with pytest.raises(NegotiationError, match="not registered"):
            p.negotiate()


class TestAppSink:
    def test_pop_api(self):
        src = TensorSrc(dimensions="3", **{"num-frames": 3})
        sink = AppSink()
        p = Pipeline().chain(src, sink)
        p.start()
        seen = 0
        while True:
            f = sink.pop(timeout=30)
            if f is None:
                break
            seen += 1
        p.stop()
        assert seen == 3


class TestSinkSyncWindow:
    def test_sync_window_preserves_count_and_order(self):
        def collect(window):
            src = VideoTestSrc(width=8, height=8, **{"num-frames": 7})
            conv = TensorConverter()
            sink = TensorSink(**{"sync-window": window})
            run_chain(src, conv, sink)
            assert sink.eos_seen
            return [np.asarray(f.tensors[0]) for f in sink.frames]

        ref = collect(1)
        windowed = collect(4)
        assert len(windowed) == len(ref) == 7
        for a, b in zip(ref, windowed):
            np.testing.assert_array_equal(a, b)

    def test_sync_window_flushes_partial_window_at_eos(self):
        src = VideoTestSrc(width=8, height=8, **{"num-frames": 3})
        conv = TensorConverter()
        sink = TensorSink(**{"sync-window": 16})  # window larger than stream
        run_chain(src, conv, sink)
        assert sink.rendered == 3
        assert sink.eos_seen


class TestDevicePlacement:
    def test_two_filters_on_different_devices(self):
        """SURVEY §7 build order 5: per-stage chip placement; inter-stage
        hop is a device_put over the interconnect (ICI on TPU; the CPU
        mesh validates placement semantics)."""
        import jax

        from nnstreamer_tpu.single import SingleShot

        devs = jax.devices()
        assert len(devs) >= 2
        with SingleShot(
            framework="jax", model="zoo:add", custom="const:1,dims:4,device:0"
        ) as s0, SingleShot(
            framework="jax", model="zoo:add", custom="const:2,dims:4,device:1"
        ) as s1:
            x = np.ones((4,), np.float32)
            mid = s0.invoke(x)[0]
            assert list(mid.devices()) == [devs[0]]
            out = s1.invoke(mid)[0]
            assert list(out.devices()) == [devs[1]]
            np.testing.assert_allclose(np.asarray(out), x + 3)

    def test_pipeline_stage_placement(self):
        import jax

        src = TensorSrc(dimensions="8", types="float32", **{"num-frames": 2})
        f0 = TensorFilter(framework="jax", model="zoo:add",
                          custom="const:1,device:0")
        f1 = TensorFilter(framework="jax", model="zoo:add",
                          custom="const:1,device:1")
        sink = TensorSink()
        run_chain(src, f0, Queue(), f1, sink)
        assert sink.rendered == 2

    def test_device_out_of_range(self):
        from nnstreamer_tpu.backends.base import BackendError
        from nnstreamer_tpu.single import SingleShot

        with pytest.raises(Exception, match="out of range"):
            SingleShot(framework="jax", model="zoo:add",
                       custom="dims:4,device:99").open()


class TestDeviceResidentPath:
    """r3: device-born sources and device-computed decodes — the
    zero-host-copy pipeline spine behind the pipeline_fps bench."""

    def test_sink_window_batch_fetch_matches_per_frame(self):
        """sync-window sinks batch-fetch the window in ONE stacked
        transfer (executor SinkNode flush); rendered values must be
        byte-identical to the sync-window=1 per-frame path, partial
        final windows included."""
        def run(window):
            src = VideoTestSrc(
                width=8, height=8, device=True, **{"num-frames": 5}
            )
            conv = TensorConverter()
            tr = TensorTransform(mode="arithmetic", option="add:3")
            sink = TensorSink(**{"sync-window": window})
            p = Pipeline().chain(src, conv, tr, sink)
            p.run(timeout=60)
            assert sink.rendered == 5
            return [np.asarray(f.tensors[0]) for f in sink.frames]

        a, b = run(1), run(4)  # 4: one full window + partial flush
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    @pytest.mark.parametrize("pattern", ["gradient", "counter", "solid"])
    def test_videotestsrc_device_matches_host(self, pattern):
        """device=true frames are byte-identical to the host pattern
        (golden tests stay valid whichever side generates)."""
        kw = {"num-frames": 3, "width": 8, "height": 6, "pattern": pattern}
        host = VideoTestSrc(**kw)
        dev = VideoTestSrc(device=True, **kw)
        host.start()
        dev.start()
        for _ in range(3):
            a, b = host.generate(), dev.generate()
            np.testing.assert_array_equal(
                np.asarray(a.tensors[0]), np.asarray(b.tensors[0])
            )

    def test_decoder_fuses_into_filter_segment(self):
        """tensor_decoder mode=image_labeling (no labels file) is
        traceable: conv+filter+decoder compile to ONE segment, and the
        fused argmax matches the host decode path."""
        from nnstreamer_tpu.elements.decoder import TensorDecoder

        def build(device):
            src = VideoTestSrc(
                width=16, height=16, device=device, **{"num-frames": 4}
            )
            conv = TensorConverter()
            tr = TensorTransform(mode="typecast", option="float32")
            filt = TensorFilter(framework="scaler", custom="factor:0.5")
            dec = TensorDecoder(mode="image_labeling")
            sink = TensorSink()
            p = Pipeline().chain(src, conv, tr, filt, dec, sink)
            return p, sink

        p, sink = build(device=True)
        plan = p.compile_plan()
        assert any(len(seg.ops) == 4 for seg in plan.segments)
        p.run(timeout=60)
        fused_out = [np.asarray(f.tensors[0]) for f in sink.frames]

        # host reference: same logits through the subplugin's decode()
        p2, sink2 = build(device=False)
        dec2 = p2["tensor_decoder1"] if "tensor_decoder1" in getattr(
            p2, "_by_name", {}
        ) else next(
            e for e in p2.elements if e.FACTORY_NAME == "tensor_decoder"
        )
        dec2._traceable_fn = None  # force the host path
        p2.run(timeout=60)
        host_out = [np.asarray(f.tensors[0]) for f in sink2.frames]
        assert len(fused_out) == len(host_out) == 4
        for a, b in zip(fused_out, host_out):
            assert a.dtype == np.uint32
            np.testing.assert_array_equal(a, b)


def test_sink_collects_e2e_latency_for_stamped_frames():
    """videotestsrc stamp-wall=true → SinkNode records one e2e latency
    per rendered frame (the bench's pipeline_p50_e2e_ms source)."""
    from nnstreamer_tpu.pipeline.executor import SinkNode

    src = VideoTestSrc(width=8, height=8,
                       **{"num-frames": 5, "stamp-wall": "true"})
    conv = TensorConverter()
    sink = TensorSink()
    p = Pipeline().chain(src, conv, sink)
    ex = p.run(timeout=30)
    node = next(n for n in ex.nodes if isinstance(n, SinkNode))
    assert len(node.latencies) == 5
    assert all(l >= 0 for l in node.latencies)
    # unstamped pipelines collect nothing
    p2 = Pipeline().chain(
        VideoTestSrc(width=8, height=8, **{"num-frames": 2}),
        TensorConverter(), TensorSink(),
    )
    ex2 = p2.run(timeout=30)
    node2 = next(n for n in ex2.nodes if isinstance(n, SinkNode))
    assert not node2.latencies


class TestForwardingElimination:
    """tee and queue do no per-frame work; the executor wires their
    producers straight to their consumers (r4) — same frames, fewer
    threads and hops."""

    def test_tee_and_queue_leave_no_nodes(self):
        from nnstreamer_tpu.pipeline.parse import parse_pipeline

        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=6 ! tee name=t "
            "t. ! queue ! tensor_filter framework=passthrough ! m.sink_0 "
            "t. ! queue ! tensor_filter framework=scaler "
            "custom=factor:2.0 ! m.sink_1 "
            "tensor_mux name=m sync-mode=nosync ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        names = {n.name for n in ex.nodes}
        assert not any("tee" in n or "queue" in n for n in names)
        # src, 2 fused filters, mux, sink
        assert len(ex.nodes) == 5
        sink = p["out"]
        assert sink.rendered == 6
        # branch 0 passthrough vs branch 1 scaled ×2 of the same frame
        for f in sink.frames:
            a, b = np.asarray(f.tensors[0]), np.asarray(f.tensors[1])
            np.testing.assert_allclose(b, a * 2.0)

    def test_queue_sizes_rewritten_channel(self):
        from nnstreamer_tpu.pipeline.parse import parse_pipeline

        p = parse_pipeline(
            "tensorsrc dimensions=2 num-frames=3 ! "
            "queue max-size-buffers=7 ! "
            "tensor_filter framework=passthrough ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        fused = next(n for n in ex.nodes if "filter" in n.name)
        assert fused.in_queues[0]._max == 7
        assert p["out"].rendered == 3

    def test_queue_chain_keeps_tighter_depth(self):
        """q1 ! q2 collapses to ONE channel honoring the tighter of the
        two depths (r4 advisor: taking q2's size unconditionally dropped
        q1's bound and silently widened the channel)."""
        from nnstreamer_tpu.pipeline.parse import parse_pipeline

        for chain, want in (
            ("queue max-size-buffers=3 ! queue max-size-buffers=9", 3),
            ("queue max-size-buffers=9 ! queue max-size-buffers=3", 3),
        ):
            p = parse_pipeline(
                "tensorsrc dimensions=2 num-frames=3 ! "
                f"{chain} ! "
                "tensor_filter framework=passthrough ! tensor_sink name=out"
            )
            ex = p.run(timeout=60)
            fused = next(n for n in ex.nodes if "filter" in n.name)
            assert fused.in_queues[0]._max == want
            assert p["out"].rendered == 3

    def test_queue_chain_depth_elimination_order_invariant(self):
        """Element ADD order (= elimination order) must not change the
        collapsed depth: when the downstream queue is eliminated first,
        its bound rides the outgoing-link override and the upstream
        queue's pass must still combine with it, not overwrite it."""
        from nnstreamer_tpu.elements.flow import Queue
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.elements.sources import TensorSrc
        from nnstreamer_tpu.pipeline.graph import Pipeline

        for q1_size, q2_size in ((9, 3), (3, 9)):
            src = TensorSrc(dimensions="2", **{"num-frames": "3"})
            q1 = Queue(**{"max-size-buffers": str(q1_size)})
            q2 = Queue(**{"max-size-buffers": str(q2_size)})
            sink = TensorSink(name="out")
            p = Pipeline()
            p.add(src, q2, q1, sink)  # downstream queue added FIRST
            p.link(src, q1)
            p.link(q1, q2)
            p.link(q2, sink)
            ex = p.run(timeout=60)
            sink_node = next(n for n in ex.nodes if "out" in n.name)
            assert sink_node.in_queues[0]._max == 3
            assert sink.rendered == 3

    def test_queue_still_splits_fusion(self):
        """An explicit queue between traceable ops must keep forcing a
        segment split (its planning role) even though its node is gone:
        two compile units either way — two FusedNodes, or since
        compiled chains (chain_mode=auto) ONE ChainNode whose chain
        still holds the two segments the queue split."""
        from nnstreamer_tpu.pipeline.parse import parse_pipeline

        p = parse_pipeline(
            "tensorsrc dimensions=2 num-frames=2 ! "
            "tensor_filter framework=passthrough ! queue ! "
            "tensor_filter framework=passthrough ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        from nnstreamer_tpu.pipeline.executor import ChainNode, FusedNode

        segments = [
            seg
            for n in ex.nodes if isinstance(n, ChainNode)
            for seg in n.chain.segments
        ] + [n for n in ex.nodes if isinstance(n, FusedNode)]
        assert len(segments) == 2  # split held
        assert p["out"].rendered == 2


def test_chan_stress_no_loss_no_deadlock():
    """Hammer the SPSC channel's park/wake edges (Dekker flags +
    low-water hysteresis) from two threads with adversarial sizes:
    every item must arrive, in order, without deadlock."""
    import threading

    from nnstreamer_tpu.pipeline.executor import _Chan

    for maxsize in (1, 2, 3, 64):
        ch = _Chan(maxsize)
        stop = threading.Event()
        N = 20000
        got = []

        def consume():
            while len(got) < N:
                got.append(ch.get(stop))

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        for i in range(N):
            ch.put(i, stop)
        t.join(timeout=60)
        assert not t.is_alive(), f"consumer deadlocked at maxsize={maxsize}"
        assert got == list(range(N)), f"loss/reorder at maxsize={maxsize}"
