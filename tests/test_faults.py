"""Fault-tolerance layer (pipeline/faults.py, docs/fault-tolerance.md):
per-element error policies end-to-end under chaos injection — drop/retry/
route accounting over a 200-frame stream, backoff timing bounds, dead-letter
routing + error meta, batch-split retry, the stall watchdog, the filter's
circuit-breaker fallback, the failed-batcher latch, and edge reconnect.

Wall-time discipline: every sleep-bearing scenario is bounded (< ~2 s) —
the tier-1 suite brushes its budget and this file sits early in the
alphabet.
"""

import socket
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.pipeline.faults import (
    FaultPolicy,
    PipelineStallError,
    backoff_s,
    resolve_fault_policy,
)
from nnstreamer_tpu.pipeline.parse import parse_pipeline

N_FRAMES = 200
CHAOS_FILTER = (
    "tensor_filter name=f framework=faulty custom=fail_rate:0.2,seed:7"
)


def _chaos_pipeline(policy_props, tail=""):
    return parse_pipeline(
        f"tensorsrc dimensions=4 num-frames={N_FRAMES} pattern=counter ! "
        f"{CHAOS_FILTER} {policy_props} ! tensor_sink name=out {tail}"
    )


# ---------------------------------------------------------------- policies
class TestPolicies:
    def test_drop_completes_with_exact_accounting(self):
        p = _chaos_pipeline("on-error=drop")
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        delivered = len(p["out"].frames)
        assert s["error_dropped"] > 0
        # dropped + routed + delivered == offered
        assert delivered + s["error_dropped"] + s["error_routed"] == N_FRAMES
        totals = ex.totals()
        assert totals["balance"] == 0
        assert totals["dropped"]["on-error-drop"] == s["error_dropped"]

    def test_retry_delivers_every_frame(self):
        p = _chaos_pipeline("on-error=retry retry-max=8 retry-backoff-ms=0.5")
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        assert len(p["out"].frames) == N_FRAMES
        assert s["error_retries"] > 0
        assert s["error_dropped"] == 0 and s["error_routed"] == 0

    def test_route_dead_letters_to_error_pad(self):
        p = _chaos_pipeline(
            "on-error=route", tail="f.src_1 ! tensor_sink name=dlq"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        main, dlq = p["out"].frames, p["dlq"].frames
        assert len(dlq) > 0
        assert len(main) + len(dlq) == N_FRAMES
        s = ex.stats()["f"]
        assert s["error_routed"] == len(dlq)
        assert len(main) + s["error_dropped"] + s["error_routed"] == N_FRAMES
        # routed frames reach the sink, so pipeline totals stay balanced
        assert ex.totals()["balance"] == 0
        # error frames carry the original tensors + structured error meta
        err = dlq[0]
        assert err.meta["error"] is True
        assert err.meta["error_element"] == "f"
        assert err.meta["error_type"] == "BackendError"
        assert "injected failure" in err.meta["error_msg"]
        assert err.tensors[0].shape == main[0].tensors[0].shape

    def test_stop_fails_fast_with_original_exception(self):
        from nnstreamer_tpu.backends.base import BackendError

        p = parse_pipeline(
            f"tensorsrc dimensions=4 num-frames=20 pattern=counter ! "
            "tensor_filter framework=faulty custom=fail_every_n:5 "
            "on-error=stop ! tensor_sink"
        )
        with pytest.raises(BackendError, match="injected failure"):
            p.run(timeout=30)

    def test_default_is_stop(self):
        from nnstreamer_tpu.backends.base import BackendError

        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=20 pattern=counter ! "
            "tensor_filter framework=faulty custom=fail_every_n:5 ! "
            "tensor_sink"
        )
        with pytest.raises(BackendError):
            p.run(timeout=30)

    def test_retry_exhaustion_degrades_to_drop_not_crash(self):
        # a permanently failing element: retry budget runs out per frame,
        # the frame drops, the pipeline survives
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=10 pattern=counter ! "
            "tensor_filter name=f framework=faulty custom=fail_rate:1.0 "
            "on-error=retry retry-max=1 retry-backoff-ms=0.2 ! "
            "tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        assert len(p["out"].frames) == 0
        assert ex.stats()["f"]["error_dropped"] == 10

    def test_retry_exhaustion_routes_when_error_pad_linked(self):
        # a retry element also grows the error pad: exhausted frames land
        # in the dead-letter sink instead of vanishing
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=10 pattern=counter ! "
            "tensor_filter name=f framework=faulty custom=fail_rate:1.0 "
            "on-error=retry retry-max=1 retry-backoff-ms=0.2 ! "
            "tensor_sink name=out f.src_1 ! tensor_sink name=dlq"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        assert len(p["out"].frames) == 0
        assert len(p["dlq"].frames) == 10
        s = ex.stats()["f"]
        assert s["error_routed"] == 10 and s["error_dropped"] == 0


# ------------------------------------------------------------------ backoff
class TestBackoff:
    def test_backoff_bounds_exponential_jittered_capped(self):
        import random

        policy = FaultPolicy(
            on_error="retry", retry_max=10, backoff_ms=10.0,
            backoff_cap_ms=50.0,
        )
        rng = random.Random(1)
        for attempt in range(8):
            full = min(10.0 * 2 ** attempt, 50.0) / 1000.0
            for _ in range(16):
                d = backoff_s(attempt, policy, rng)
                assert 0.5 * full <= d <= full

    def test_observed_backoff_within_configured_bounds(self):
        # every 4th invoke fails once: each failing frame retries exactly
        # once with attempt-0 backoff in [0.5, 1.0] x 5 ms
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=40 pattern=counter ! "
            "tensor_filter name=f framework=faulty custom=fail_every_n:4 "
            "on-error=retry retry-max=3 retry-backoff-ms=5 ! "
            "tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        s = ex.stats()["f"]
        assert len(p["out"].frames) == 40
        assert s["error_retries"] > 0
        per_retry_ms = s["error_backoff_ms"] / s["error_retries"]
        assert 2.5 <= per_retry_ms <= 5.0


# -------------------------------------------------------------- batch split
class TestBatchSplit:
    def test_host_batched_window_splits_per_frame(self):
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=60 pattern=counter ! "
            "tensor_filter name=f framework=faulty "
            "custom=fail_every_n:7,batchable:true batching=true "
            "max-batch=8 on-error=drop ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        delivered = len(p["out"].frames)
        # one bad frame never discards its batchmates
        assert delivered + s["error_dropped"] == 60
        assert 0 < s["error_dropped"] < 60

    def test_fused_batch_split_reruns_per_frame(self):
        from nnstreamer_tpu.pipeline.executor import Executor

        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=40 pattern=counter ! "
            "tensor_filter framework=scaler custom=factor:2.0 "
            "batching=true max-batch=8 batch-timeout-ms=5 on-error=drop ! "
            "tensor_sink name=out"
        )
        plan = p.compile_plan()
        (seg,) = plan.segments
        orig = seg.process_batch
        calls = {"n": 0}

        def flaky(frames, cfg):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected batch failure")
            return orig(frames, cfg)

        seg.process_batch = flaky
        ex = Executor(plan)
        ex.start()
        assert ex.wait(30)
        ex.stop()
        assert not ex.errors
        # the failed batch re-ran per-frame: nothing was lost with it
        assert len(p["out"].frames) == 40
        vals = sorted(int(f.tensors[0][0]) for f in p["out"].frames)
        assert vals == sorted(range(0, 80, 2))  # counter pattern x2.0


# ----------------------------------------------------------------- watchdog
class TestStallWatchdog:
    def test_hang_becomes_typed_stall_error(self, monkeypatch):
        monkeypatch.setenv("NNS_TPU_EXECUTOR_WATCHDOG_TIMEOUT_MS", "200")
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=30 pattern=counter ! "
            "tensor_chaos hang-on-frame=5 hang-ms=1200 ! tensor_sink"
        )
        with pytest.raises(PipelineStallError) as ei:
            p.run(timeout=10)
        exc = ei.value
        assert exc.timeout_ms == 200
        assert any("tensor_chaos" in name for name in exc.snapshot)
        # the snapshot localizes the hang: the chaos node has queued input
        chaos = next(s for n, s in exc.snapshot.items() if "chaos" in n)
        assert sum(chaos["queued"]) > 0

    def test_no_false_positive_on_retry_backoff(self, monkeypatch):
        # a node parked in legitimate retry backoff LONGER than the
        # watchdog timeout is recovering, not hung
        monkeypatch.setenv("NNS_TPU_EXECUTOR_WATCHDOG_TIMEOUT_MS", "150")
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=12 pattern=counter ! "
            "tensor_filter name=f framework=faulty custom=fail_every_n:4 "
            "on-error=retry retry-max=2 retry-backoff-ms=250 ! "
            "tensor_sink name=out"
        )
        ex = p.run(timeout=30)
        assert not ex.errors and not ex.stalled
        assert len(p["out"].frames) == 12

    def test_no_false_positive_on_healthy_pipeline(self, monkeypatch):
        monkeypatch.setenv("NNS_TPU_EXECUTOR_WATCHDOG_TIMEOUT_MS", "200")
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=50 pattern=counter ! "
            "tensor_transform mode=typecast option=float32 ! "
            "tensor_sink name=out"
        )
        ex = p.run(timeout=30)
        assert not ex.errors and not ex.stalled
        assert len(p["out"].frames) == 50


# -------------------------------------------------------- fallback breaker
class TestFallbackCircuitBreaker:
    def test_swap_then_recover(self):
        # primary fails its first 3 invokes then heals; retry absorbs the
        # pre-open failures, the fallback serves while open, a probe
        # closes the circuit again — every frame is delivered
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=40 pattern=counter ! "
            "tensor_filter name=f framework=faulty custom=fail_first_n:3 "
            "on-error=retry retry-max=4 retry-backoff-ms=0.5 "
            "fallback-framework=passthrough fallback-after=3 "
            "fallback-probe-every=8 ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        assert len(p["out"].frames) == 40
        s = ex.stats()["f"]
        assert s["cb_circuit_opens"] == 1
        assert s["cb_circuit_closes"] == 1
        assert 0 < s["cb_fallback_invokes"] <= 8
        assert s["cb_fallback_active"] == 0  # recovered

    def test_fallback_is_fusion_barrier(self):
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=2 pattern=counter ! "
            "tensor_filter framework=scaler custom=factor:2.0 "
            "fallback-framework=passthrough ! tensor_sink"
        )
        plan = p.compile_plan()
        assert plan.segments == []  # degradable filter runs host-path


# ------------------------------------------------------------ chaos element
class TestChaosElement:
    def test_corruption_drives_downstream_policy(self):
        # tensor_chaos truncates every 4th frame's tensors; the strict
        # faulty backend rejects them; the filter's drop policy skips them
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=32 pattern=counter ! "
            "tensor_chaos corrupt-every-n=4 ! "
            "tensor_filter name=f framework=faulty "
            "custom=strict_shapes:true on-error=drop ! tensor_sink name=out"
        )
        ex = p.run(timeout=30)
        assert not ex.errors
        assert len(p["out"].frames) == 24  # 32 - 8 corrupted
        assert ex.stats()["f"]["error_dropped"] == 8

    def test_chaos_own_policy_routes(self):
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=20 pattern=counter ! "
            "tensor_chaos name=c fail-every-n=5 on-error=route ! "
            "tensor_sink name=out c.src_1 ! tensor_sink name=dlq"
        )
        ex = p.run(timeout=30)
        assert not ex.errors
        assert len(p["out"].frames) == 16
        assert len(p["dlq"].frames) == 4
        assert p["dlq"].frames[0].meta["error_type"] == "ElementError"


# ------------------------------------------------------------ config layer
class TestConfigDefaults:
    def test_executor_default_policy_from_env(self, monkeypatch):
        monkeypatch.setenv("NNS_TPU_EXECUTOR_ON_ERROR", "drop")
        monkeypatch.setenv("NNS_TPU_EXECUTOR_RETRY_MAX", "5")
        policy = resolve_fault_policy([])
        assert policy.on_error == "drop" and policy.retry_max == 5

    def test_element_property_outranks_config(self, monkeypatch):
        from nnstreamer_tpu.elements.transform import TensorTransform

        monkeypatch.setenv("NNS_TPU_EXECUTOR_ON_ERROR", "drop")
        t = TensorTransform(
            mode="typecast", option="float32", **{"on-error": "retry"}
        )
        assert resolve_fault_policy([t]).on_error == "retry"

    def test_bad_on_error_value_rejected(self):
        from nnstreamer_tpu.elements.transform import TensorTransform

        with pytest.raises(ValueError, match="on-error"):
            TensorTransform(
                mode="typecast", option="float32",
                **{"on-error": "explode"},
            )


# ----------------------------------------------------------- failed batcher
class TestBatcherFailureLatch:
    def test_failed_pump_latches_typed_error(self):
        import jax

        from nnstreamer_tpu.models import transformer as tfm
        from nnstreamer_tpu.models.serving import (
            BatcherFailedError,
            ContinuousBatcher,
        )

        params = tfm.init_params(
            jax.random.PRNGKey(0), vocab=67, d_model=32, n_heads=2,
            n_layers=1,
        )
        b = ContinuousBatcher(
            params, n_heads=2, n_slots=2, max_len=32, prompt_len=8
        )
        rid = b.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
        assert rid is not None

        def boom(*a, **k):
            raise RuntimeError("device launch failed mid-flight")

        b._pump_greedy = boom
        b._pump_sampling = boom
        with pytest.raises(RuntimeError, match="mid-flight"):
            b.step()
        # donated state is gone: every later call reports the latch, not
        # a cryptic deleted-buffer error
        with pytest.raises(BatcherFailedError, match="mid-flight"):
            b.step()
        with pytest.raises(BatcherFailedError):
            b.submit(np.array([4, 5], np.int32), max_new_tokens=2)
        with pytest.raises(BatcherFailedError):
            b.step_pump(2)


# ------------------------------------------------------------ edge reconnect
class TestEdgeReconnect:
    def test_client_start_retries_until_server_up(self):
        from nnstreamer_tpu.edge.query import TensorQueryClient
        from nnstreamer_tpu.edge.transport import PyTransport

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        server = PyTransport()

        def delayed():
            time.sleep(0.3)
            server.listen("127.0.0.1", port)
            got = server.recv(timeout=5)
            if got is not None:
                server.send(got[0], got[1])  # echo

        t = threading.Thread(target=delayed, daemon=True)
        t.start()
        c = TensorQueryClient(
            "c", **{"dest-port": port, "timeout": 5, "retry-max": 8,
                    "retry-backoff-ms": 30}
        )
        c.negotiate([None])
        try:
            c.start()  # server is down for the first ~0.3 s
            from nnstreamer_tpu.tensors.frame import Frame

            f = Frame((np.arange(4, dtype=np.float32),))
            reply = c.process(f)
            np.testing.assert_allclose(
                np.asarray(reply.tensors[0]), f.tensors[0]
            )
        finally:
            c.stop()
            server.close()
            t.join(timeout=2)

    def test_no_retry_fails_fast(self):
        from nnstreamer_tpu.edge.query import TensorQueryClient
        from nnstreamer_tpu.elements.base import ElementError

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        c = TensorQueryClient("c", **{"dest-port": port, "timeout": 1})
        c.negotiate([None])
        t0 = time.monotonic()
        with pytest.raises(ElementError, match="cannot reach"):
            c.start()
        assert time.monotonic() - t0 < 2.0
