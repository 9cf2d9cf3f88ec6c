"""Device-plane resilience (pipeline/device_faults.py, parallel/
replicas.py, docs/resilience.md): fault classification, the OOM
degrade-and-reprobe ladder, the compile/dispatch fallback circuit,
replica failover with exact frame accounting, and the warm-restart
drain/snapshot/resume round-trip — all driven by the deterministic
chaos injectors (FaultyBackend device modes, tensor_chaos
device-fault-kind).

Wall-time discipline: the tier-1 portion stays under ~5 s (tiny frame
counts, ladder-rung jit programs only); the mixed-fault soak is marked
``slow``.
"""

import os
import time

import numpy as np
import pytest

from nnstreamer_tpu.pipeline.device_faults import (
    BucketGovernor,
    DeviceCircuit,
    DeviceCompileError,
    DeviceFaultError,
    DeviceLostError,
    DeviceOOMError,
    ReplicaExhaustedError,
    classify_device_fault,
    resolve_device_policy,
)
from nnstreamer_tpu.pipeline.executor import Executor
from nnstreamer_tpu.pipeline.parse import parse_pipeline


@pytest.fixture(autouse=True)
def _sanitize(monkeypatch):
    """Every pipeline in this file runs under the runtime sanitizer:
    the degradation paths must keep offered == delivered + dropped +
    routed latched per node, or the run fails at EOS."""
    monkeypatch.setenv("NNS_TPU_SANITIZE", "1")


# ------------------------------------------------------------- classifier
class _FakeXlaRuntimeError(Exception):
    pass


# the classifier matches on the class NAME (jaxlib moves the class path
# between releases)
_FakeXlaRuntimeError.__name__ = "XlaRuntimeError"


class TestClassifier:
    def test_typed_faults_classify_by_kind(self):
        assert classify_device_fault(DeviceOOMError("x")) == "oom"
        assert classify_device_fault(DeviceCompileError("x")) == "compile"
        assert classify_device_fault(DeviceLostError("x")) == "device_lost"
        assert classify_device_fault(DeviceFaultError("x")) == "transient"

    def test_ordinary_errors_are_not_device_faults(self):
        for exc in (ValueError("bad input"), RuntimeError("user code"),
                    KeyError("k")):
            assert classify_device_fault(exc) is None

    @pytest.mark.parametrize("msg,kind", [
        ("RESOURCE_EXHAUSTED: out of memory allocating 2.1G", "oom"),
        ("Compilation failure: unsupported HLO", "compile"),
        ("failed to connect to TPU driver", "device_lost"),
        ("INTERNAL: something ephemeral", "transient"),
        # OOM *during* compilation is memory pressure, not a broken
        # program: shrinking helps, recompiling the same thing doesn't
        ("compilation failure: ran out of memory while allocating", "oom"),
    ])
    def test_xla_message_sniffing(self, msg, kind):
        assert classify_device_fault(_FakeXlaRuntimeError(msg)) == kind


# -------------------------------------------------------- bucket governor
class TestBucketGovernor:
    def _gov(self, ladder=(1, 2, 4, 8), cooldown=10.0):
        clock = [0.0]
        g = BucketGovernor(ladder, cooldown_s=cooldown,
                           clock=lambda: clock[0])
        return g, clock

    def test_oom_halves_to_next_rung_and_remembers(self):
        g, _ = self._gov()
        assert g.cap() == 8 and not g.degraded
        assert g.on_oom(8) == 4
        assert g.cap() == 4 and g.degraded
        assert g.on_oom(4) == 2
        assert g.cap() == 2
        assert g.snapshot()["ceiling"] == 2
        assert g.ooms == 2

    def test_bucket_one_oom_returns_none(self):
        g, _ = self._gov()
        g.on_oom(2)
        assert g.on_oom(1) is None  # nothing left to shrink

    def test_zero_cooldown_disables_reprobe_no_livelock(self):
        """cooldown <= 0 must mean NEVER re-probe: a zero cooldown that
        offered the probe rung on every cap() call would livelock the
        service loop (dispatch at probe width -> OOM -> retry at probe
        width, forever)."""
        g, clock = self._gov(cooldown=0.0)
        assert g.on_oom(8) == 4
        for _ in range(5):
            assert g.cap() == 4     # never the probe rung
            clock[0] += 1000.0
        assert g.cap() == 4

    def test_reprobe_after_cooldown_reclaims_one_rung(self):
        g, clock = self._gov(cooldown=10.0)
        g.on_oom(8)                 # ceiling 4
        assert g.cap() == 4         # cooldown not elapsed: no probe
        clock[0] = 11.0
        assert g.cap() == 8         # probe window: one rung up
        assert g.on_ok(8) is True   # probe confirmed
        assert g.ceiling == 8 and not g.degraded
        assert g.reprobes == 1

    def test_failed_probe_pushes_cooldown_out(self):
        g, clock = self._gov(cooldown=10.0)
        g.on_oom(8)
        clock[0] = 11.0
        assert g.cap() == 8         # probing
        g.on_oom(8)                 # probe OOMs: stay at 4
        assert g.ceiling == 4
        assert g.cap() == 4         # cooldown re-armed at t=11
        clock[0] = 22.0
        assert g.cap() == 8         # next probe window

    def test_narrow_dispatch_during_probe_does_not_confirm(self):
        g, clock = self._gov(cooldown=10.0)
        g.on_oom(8)
        clock[0] = 11.0
        assert g.on_ok(2) is False  # narrower than the ceiling: no-op
        assert g.ceiling == 4

    def test_non_ladder_width_snaps_to_rung(self):
        """The host path dispatches arbitrary widths (no bucket
        padding): a success between rungs must not set a non-ladder
        ceiling — cap()'s ladder walk crashed on ceiling=3."""
        g, clock = self._gov(cooldown=10.0)
        g.on_oom(4)                 # ceiling 2
        clock[0] = 11.0
        assert g.cap() == 4         # probe window open
        assert g.on_ok(3) is False  # rung(3) == 2 == ceiling: no-op
        assert g.ceiling == 2
        assert g.cap() == 4         # ladder walk still intact
        assert g.on_ok(6) is True   # 6 rows confirm rung 4
        assert g.ceiling == 4

    def test_restore_rearms_ceiling_and_cooldown(self):
        g, clock = self._gov()
        g.on_oom(8)
        g.on_oom(4)
        snap = g.snapshot()
        g2, clock2 = self._gov()
        g2.restore(snap)
        assert g2.ceiling == 2 and g2.degraded
        assert g2.ooms == snap["ooms"]
        assert g2.cap() == 2        # cooldown armed: no instant probe
        clock2[0] = 11.0
        assert g2.cap() == 4        # but it can still recover


# --------------------------------------------------------- device circuit
class TestDeviceCircuit:
    def test_compile_opens_immediately(self):
        c = DeviceCircuit(after=3)
        assert c.record_fault("compile") is True
        assert c.open and c.opens == 1

    def test_transient_opens_after_consecutive(self):
        c = DeviceCircuit(after=3)
        assert c.record_fault("transient") is False
        c.record_ok()  # success resets the streak
        assert c.record_fault("transient") is False
        assert c.record_fault("transient") is False
        assert c.record_fault("transient") is True
        assert c.kinds == {"transient": 4}

    def test_probe_cadence_and_close(self):
        c = DeviceCircuit(after=1, probe_every=3)
        c.record_fault("device_lost")
        assert [c.should_probe() for _ in range(6)] == [
            False, False, True, False, False, True
        ]
        c.close()
        assert not c.open and c.closes == 1

    def test_snapshot_restore_round_trip(self):
        c = DeviceCircuit(after=1)
        c.record_fault("compile")
        c.eager_invokes = 7
        c2 = DeviceCircuit(after=1)
        c2.restore(c.snapshot())
        assert c2.open and c2.faults == 1
        assert c2.kinds == {"compile": 1} and c2.eager_invokes == 7


# ----------------------------------------------------------------- policy
class TestPolicyResolution:
    def test_defaults(self):
        pol = resolve_device_policy([])
        assert pol["oom-policy"] == "degrade"
        assert pol["device-fallback"] is True
        assert pol["device-fallback-after"] == 3

    def test_element_overrides_and_env(self, monkeypatch):
        from nnstreamer_tpu.elements.filter import TensorFilter

        monkeypatch.setenv("NNS_TPU_EXECUTOR_DEVICE_FALLBACK_AFTER", "7")
        f = TensorFilter(framework="passthrough", input="4",
                         **{"oom-policy": "stop",
                            "device-fallback": "false"})
        pol = resolve_device_policy([f])
        assert pol["oom-policy"] == "stop"
        assert pol["device-fallback"] is False
        assert pol["device-fallback-after"] == 7

    def test_invalid_oom_policy_raises(self):
        from nnstreamer_tpu.elements.filter import TensorFilter

        f = TensorFilter(framework="passthrough", input="4",
                         **{"oom-policy": "panic"})
        with pytest.raises(ValueError, match="oom-policy"):
            resolve_device_policy([f])


# ------------------------------------------------------------ replica set
class TestReplicaSet:
    def test_round_robin_over_healthy(self):
        from nnstreamer_tpu.parallel.replicas import ReplicaSet

        seen = []
        rs = ReplicaSet([lambda x, i=i: seen.append(i) or x
                         for i in range(3)])
        for v in range(6):
            rs.dispatch(v)
        assert seen == [0, 1, 2, 0, 1, 2]

    def test_failover_then_bench_then_probe_recovery(self):
        from nnstreamer_tpu.parallel.replicas import ReplicaSet

        state = {"dead": True}

        def flaky(x):
            if state["dead"]:
                raise DeviceLostError("gone")
            return ("r0", x)

        rs = ReplicaSet([flaky, lambda x: ("r1", x)],
                        unhealthy_after=2, probe_every=4)
        outs = [rs.dispatch(i) for i in range(6)]
        # every frame reached SOME replica (failover, never loss)
        assert all(o[0] == "r1" for o in outs)
        assert rs.healthy_count == 1
        assert rs.failovers >= 2
        state["dead"] = False          # the device comes back
        outs = [rs.dispatch(i) for i in range(8)]
        assert rs.healthy_count == 2   # a probe re-admitted replica 0
        assert any(o[0] == "r0" for o in outs)

    def test_non_device_error_propagates_unclassified(self):
        from nnstreamer_tpu.parallel.replicas import ReplicaSet

        def bad(x):
            raise ValueError("bad input")

        rs = ReplicaSet([bad, lambda x: x])
        with pytest.raises(ValueError):
            rs.dispatch(1)
        assert rs.healthy_count == 2   # says nothing about health

    def test_exhaustion_raises_with_cause(self):
        from nnstreamer_tpu.parallel.replicas import ReplicaSet

        def dead(x):
            raise DeviceOOMError("oom")

        rs = ReplicaSet([dead, dead], unhealthy_after=1)
        with pytest.raises(ReplicaExhaustedError) as ei:
            rs.dispatch(1)
        assert isinstance(ei.value.__cause__, DeviceOOMError)
        assert rs.exhaustions == 1

    def test_recovery_not_starved_by_permanently_dead_low_index(self):
        """Replica 0 dead for good, replica 1 benched but recovered:
        with nothing healthy the plan must rotate over EVERY benched
        replica — always probing sick[0] exhausted forever although
        replica 1 would serve."""
        from nnstreamer_tpu.parallel.replicas import ReplicaSet

        calls = {"r1": 0}

        def dead(x):
            raise DeviceLostError("gone for good")

        def flaky(x):
            calls["r1"] += 1
            if calls["r1"] == 1:
                raise DeviceLostError("one-off")
            return ("r1", x)

        rs = ReplicaSet([dead, flaky], unhealthy_after=1, probe_every=4)
        with pytest.raises(ReplicaExhaustedError):
            rs.dispatch(0)               # benches both
        assert rs.healthy_count == 0
        assert rs.dispatch(1) == ("r1", 1)   # r1 re-admitted, frame served
        assert rs.healthy_count == 1
        assert rs.dispatch(2) == ("r1", 2)

    def test_fresh_bench_waits_full_probe_cadence(self):
        """The probe counter must only accumulate while something is
        benched: healthy dispatches idling it high would probe a
        just-benched (still dead) replica on the very next frame."""
        from nnstreamer_tpu.parallel.replicas import ReplicaSet

        state = {"dead": False}
        calls = {"r0": 0}

        def flaky(x):
            calls["r0"] += 1
            if state["dead"]:
                raise DeviceLostError("gone")
            return ("r0", x)

        rs = ReplicaSet([flaky, lambda x: ("r1", x)],
                        unhealthy_after=1, probe_every=4)
        for v in range(20):            # long healthy stretch
            rs.dispatch(v)
        state["dead"] = True
        rs.dispatch(100)               # faults, benches r0, fails over
        assert rs.healthy_count == 1
        benched_at = calls["r0"]
        rs.dispatch(101)               # next frame: NO immediate probe
        assert calls["r0"] == benched_at
        for v in range(4):             # cadence elapses -> probe fires
            rs.dispatch(v)
        assert calls["r0"] == benched_at + 1

    def test_probe_rotates_across_benched_replicas(self):
        """With a healthy survivor, periodic recovery probes alternate
        across the benched replicas instead of pinning the lowest
        index."""
        from nnstreamer_tpu.parallel.replicas import ReplicaSet

        probed = []

        def sick_a(x):
            probed.append("a")
            raise DeviceLostError("a")

        def sick_b(x):
            probed.append("b")
            raise DeviceLostError("b")

        rs = ReplicaSet([sick_a, sick_b, lambda x: x],
                        unhealthy_after=1, probe_every=2)
        for v in range(8):
            rs.dispatch(v)
        # both benched replicas saw probes after the initial bench
        assert set(probed[2:]) == {"a", "b"}


# ----------------------------------------------- OOM degrade (pipelines)
class TestOOMDegrade:
    def test_fused_batched_oom_shrinks_bucket_and_completes(self):
        """Acceptance: injected OOM → the batch bucket shrinks to the
        rung the device fits, every frame still arrives, and the
        sanitizer's per-node accounting latch stays green."""
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=100 pattern=counter ! "
            "tensor_filter name=f framework=faulty "
            "custom=traceable:true,oom_above_rows:2 "
            "batching=true max-batch=8 batch-timeout-ms=2 ! "
            "tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        assert s["frames"] == 100
        assert len(p["out"].frames) == 100      # degrade, never drop
        assert s["oom_events"] >= 1
        assert s["batch_ceiling"] == 2          # the rung that fits
        assert s["device_degraded"] == 1
        assert ex.totals()["balance"] == 0
        # in order, too: OOM retries must not reorder the stream
        vals = [int(f.tensors[0][0]) for f in p["out"].frames]
        assert vals == sorted(vals)

    def test_host_batched_oom_rides_the_same_ladder(self):
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=60 pattern=counter ! "
            "tensor_filter name=f framework=faulty "
            "custom=batchable:true,oom_above_rows:2 "
            "batching=true max-batch=8 batch-timeout-ms=2 ! "
            "tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        assert len(p["out"].frames) == 60
        assert s["oom_events"] >= 1 and s["batch_ceiling"] == 2
        assert ex.totals()["balance"] == 0

    def test_oom_policy_stop_keeps_fail_fast(self):
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=40 pattern=counter ! "
            "tensor_filter name=f framework=faulty oom-policy=stop "
            "device-fallback=false "
            "custom=traceable:true,oom_above_rows:2 "
            "batching=true max-batch=8 batch-timeout-ms=2 ! "
            "tensor_sink name=out"
        )
        with pytest.raises(DeviceOOMError):
            p.run(timeout=60)


# ------------------------------------------- compile/dispatch fallback
class TestCompileFallback:
    def test_compile_failure_serves_eager_and_surfaces_degraded(self):
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=50 pattern=counter ! "
            "tensor_filter name=f framework=faulty "
            "custom=traceable:true,compile_fail:true ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        assert len(p["out"].frames) == 50       # eager path served all
        assert s["device_degraded"] == 1
        assert s["device_fault_kinds"].get("compile", 0) >= 1
        assert s["device_eager_invokes"] == 50
        assert s["device_circuit_opens"] == 1

    def test_compile_failure_at_build_opens_circuit_before_frames(self):
        """The batched warmup is the only thing that compiles at build —
        a deterministic compile fault there must escape the
        warmup-is-an-optimization swallow and open the circuit at
        PAUSED state, not stall mid-stream (an EOS-only pipeline shows
        the fault was recorded with zero frames served)."""
        p = parse_pipeline(
            "tensorsrc name=src dimensions=4 num-frames=0 ! "
            "tensor_filter name=f framework=faulty "
            "custom=traceable:true,compile_fail:true "
            "batching=true max-batch=4 batch-timeout-ms=2 ! "
            "tensor_sink name=out"
        )
        ex = p.run(timeout=30)
        assert not ex.errors
        s = ex.stats()["f"]
        assert s["device_degraded"] == 1
        assert s["device_fault_kinds"].get("compile", 0) >= 1
        assert s["frames"] == 0

    def test_probe_closes_circuit_when_compile_recovers(self, monkeypatch):
        monkeypatch.setenv("NNS_TPU_EXECUTOR_DEVICE_PROBE_EVERY", "8")
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=60 pattern=counter ! "
            "tensor_filter name=f framework=faulty "
            "custom=traceable:true,compile_fail:true,compile_fail_first_n:1 "
            "! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        assert len(p["out"].frames) == 60
        assert s["device_degraded"] == 0        # recovered
        assert s["device_eager_invokes"] == 8   # exactly one probe beat
        assert s["device_circuit_opens"] == 1

    def test_fallback_off_propagates_to_error_policy(self):
        """device-fallback=false: the typed fault is an ordinary element
        error — PR-3 policies (here: drop) dispose of the frames."""
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=30 pattern=counter ! "
            "tensor_chaos name=c device-fault-kind=device_lost "
            "device-fault-every-n=5 on-error=drop ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["c"]
        assert s["error_dropped"] == 6          # frames 5,10,...,30
        assert len(p["out"].frames) == 24
        assert ex.totals()["balance"] == 0

    def test_chaos_device_fault_needs_kind(self):
        from nnstreamer_tpu.elements.chaos import TensorChaos

        with pytest.raises(ValueError, match="device-fault-kind"):
            TensorChaos(**{"device-fault-every-n": "5"})


# -------------------------------------------------------- replica failover
class TestReplicaFailover:
    def test_one_replica_lost_stream_survives_with_exact_accounting(self):
        """Acceptance: device loss in a 2-replica setup → every frame
        reaches a terminal outcome (here: delivered via the surviving
        replica) and throughput recovers on the survivor."""
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=100 pattern=counter ! "
            "tensor_filter name=f framework=faulty replicas=2 "
            "replica-unhealthy-after=2 "
            "custom=device_lost_at:3,only_replica:0 ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        assert len(p["out"].frames) == 100      # no frame lost to the
        assert ex.totals()["balance"] == 0      # dying replica
        assert s["rep_healthy"] == 1
        assert s["rep_failovers"] >= 1
        # the survivor carried the load after the bench
        assert s["rep_served"][1] > 90

    def test_exhaustion_disposes_through_error_policy(self):
        """offered == delivered + dropped + routed must hold when BOTH
        replicas die: ReplicaExhaustedError falls to on-error=drop and
        every undeliverable frame is accounted, none lost."""
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=40 pattern=counter ! "
            "tensor_filter name=f framework=faulty replicas=2 "
            "replica-unhealthy-after=1 custom=device_lost_at:5 "
            "on-error=drop ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        s = ex.stats()["f"]
        delivered = len(p["out"].frames)
        assert s["rep_healthy"] == 0
        assert delivered + s["error_dropped"] + s["error_routed"] == 40
        assert ex.totals()["balance"] == 0

    def test_exhaustion_routes_to_dead_letter(self):
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=40 pattern=counter ! "
            "tensor_filter name=f framework=faulty replicas=2 "
            "replica-unhealthy-after=1 custom=device_lost_at:5 "
            "on-error=route ! tensor_sink name=out "
            "f.src_1 ! tensor_sink name=dlq"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        main, dlq = p["out"].frames, p["dlq"].frames
        assert len(main) + len(dlq) == 40
        assert len(dlq) > 0
        assert dlq[0].meta["error_type"] == "ReplicaExhaustedError"
        assert ex.totals()["balance"] == 0

    def test_partial_replica_open_failure_closes_opened_tail(self):
        """A replica that fails to open mid-build must not leak the
        replicas already opened before it: a retried first frame would
        otherwise stack a fresh copy of every model arena per attempt.
        Replica 0 (== self.backend) stays up — stop() owns it."""
        from nnstreamer_tpu.elements.filter import TensorFilter

        f = TensorFilter(framework="framecounter", replicas="3",
                         input="4", inputtype="float32")
        opened, closed = [], []
        orig = f._open_backend

        def tracked(custom_extra=""):
            if len(opened) == 2:  # replicas 0 and 1 already up
                raise RuntimeError("replica 2 open failed")
            b = orig(custom_extra)
            opened.append(b)
            real_close = b.close
            b.close = lambda: (closed.append(b), real_close())
            return b

        f._open_backend = tracked
        with pytest.raises(RuntimeError, match="replica 2"):
            f._ensure_replicas()
        assert closed == [opened[1]]
        assert f._replica_set is None and f._replica_backends == []
        f.stop()
        assert opened[0] in closed  # stop() still closes replica 0

    def test_replicas_reject_fallback_circuit(self):
        """replicas=N dispatches before the fallback circuit is ever
        consulted — accepting fallback-framework beside it would
        silently never open the fallback backend."""
        from nnstreamer_tpu.elements.filter import TensorFilter

        with pytest.raises(ValueError, match="fallback"):
            TensorFilter(framework="framecounter", replicas="2",
                         **{"fallback-framework": "passthrough"},
                         input="4", inputtype="float32")

    def test_replicas_reject_shared_key(self):
        from nnstreamer_tpu.elements.filter import TensorFilter

        with pytest.raises(ValueError, match="replicas"):
            TensorFilter(framework="passthrough", replicas="2",
                         **{"shared-tensor-filter-key": "k"})


# ------------------------------------------------- warm restart round-trip
class TestWarmRestart:
    DESC = (
        "tensorsrc name=src dimensions=4 num-frames={n} pattern=counter ! "
        "tensor_filter name=f framework=framecounter ! tensor_sink name=out"
    )

    def test_drain_snapshot_resume_in_place(self):
        """Acceptance: drain() parks the graph at a frame boundary,
        snapshot() captures exact per-element state, resume() restarts
        frame flow — nothing lost, nothing duplicated."""
        p = parse_pipeline(self.DESC.format(n=3000))
        ex = p.start()
        time.sleep(0.1)
        assert ex.drain(timeout=15) is True
        snap = ex.snapshot()
        mid = len(p["out"].frames)
        # frame-boundary consistency: the counter equals frames seen
        assert snap["elements"]["f"]["backend"]["count"] == mid
        assert snap["nodes"]["f"]["frames"] == mid
        ex.resume()
        assert ex.wait(60), ex.errors
        assert not ex.errors
        vals = [int(f.tensors[0][0]) for f in p["out"].frames]
        assert vals == list(range(3000))   # contiguous across the pause
        assert ex.totals()["balance"] == 0

    def test_warm_restart_into_fresh_executor(self, tmp_path):
        """Drain, persist the snapshot (atomic-replace file), rebuild
        the pipeline from scratch, restore before start: per-element
        state and node stats continue exactly where the old process
        stopped."""
        p1 = parse_pipeline(self.DESC.format(n=5000))
        ex1 = p1.start()
        time.sleep(0.1)
        assert ex1.drain(timeout=15) is True
        path = str(tmp_path / "warm.json")
        snap = ex1.save_snapshot(path)
        n1 = snap["elements"]["f"]["backend"]["count"]
        assert n1 > 0
        ex1.stop()

        p2 = parse_pipeline(self.DESC.format(n=20))
        ex2 = Executor(p2.compile_plan())
        ex2.restore(Executor.read_snapshot(path))
        ex2.start()
        assert ex2.wait(30), ex2.errors
        vals = [int(f.tensors[0][0]) for f in p2["out"].frames]
        assert vals == list(range(n1, n1 + 20))     # counter continued
        assert ex2.stats()["f"]["frames"] == n1 + 20  # stats carried

    def test_restart_remembers_oom_ceiling(self, tmp_path):
        """A restarted pipeline must not re-discover the OOM boundary by
        OOMing again: the restored governor starts at the safe rung."""
        desc = (
            "tensorsrc name=src dimensions=4 num-frames={n} "
            "pattern=counter ! "
            "tensor_filter name=f framework=faulty "
            "custom=traceable:true,oom_above_rows:2 "
            "batching=true max-batch=8 batch-timeout-ms=2 ! "
            "tensor_sink name=out"
        )
        p1 = parse_pipeline(desc.format(n=60))
        ex1 = p1.run(timeout=60)
        assert not ex1.errors
        s1 = ex1.stats()["f"]
        assert s1["oom_events"] >= 1 and s1["batch_ceiling"] == 2
        snap = ex1.snapshot()

        p2 = parse_pipeline(desc.format(n=60))
        ex2 = Executor(p2.compile_plan())
        ex2.restore(snap)
        ex2.start()
        assert ex2.wait(60), ex2.errors
        s2 = ex2.stats()["f"]
        assert len(p2["out"].frames) == 60
        # restored ooms counter carried over, and NO new OOM happened:
        # the remembered ceiling kept every dispatch inside capacity
        assert s2["oom_events"] == s1["oom_events"]
        assert s2["batch_ceiling"] == 2

    def test_restore_before_first_frame_keeps_replica_health(self):
        """Executor.restore on a fresh executor runs before the first
        frame — the replica set builds lazily AFTER that, so the health
        snapshot must stash and apply when the set comes up, never
        silently drop (a restarted pipeline would re-serve the benched
        replica and re-discover its sickness frame by frame)."""
        from nnstreamer_tpu.elements.filter import TensorFilter

        f = TensorFilter(framework="faulty", replicas="2",
                         input="4", inputtype="float32")
        f.state_restore({"replica_set": {"healthy": [False, True],
                                         "failovers": 7}})
        rs = f._ensure_replicas()
        assert [r.healthy for r in rs.replicas] == [False, True]
        assert rs.failovers == 7
        f.stop()

    def test_replica_backend_state_rides_the_snapshot(self):
        """Replicas 1..N-1 are independent stateful backend copies —
        snapshot/restore must carry each one's state, not just replica
        0's (a warm-restarted 2-replica framecounter would otherwise
        alternate a warm and a reset count, round-robin)."""
        from nnstreamer_tpu.elements.filter import TensorFilter

        f1 = TensorFilter(framework="framecounter", replicas="2",
                          input="4", inputtype="float32")
        f1._ensure_replicas()
        f1.backend._count = 5
        f1._replica_backends[1]._count = 3
        snap = f1.state_snapshot()
        f1.stop()
        assert snap["replica_backends"] == [{"count": 3}]

        f2 = TensorFilter(framework="framecounter", replicas="2",
                          input="4", inputtype="float32")
        f2.state_restore(snap)      # before first frame: stashes
        f2._ensure_replicas()       # lazily built set applies it
        assert f2.backend._count == 5
        assert f2._replica_backends[1]._count == 3
        f2.stop()

    def test_restore_section_survives_until_target_builds(self):
        """restore() on a started executor can land before the service
        loop has built the governor (_build_resilience runs inside
        run()): the governor/circuit sections must stay stashed for the
        loop's own post-build apply, never be consumed into the void."""
        p = parse_pipeline(
            "tensorsrc name=src dimensions=4 num-frames=10 ! "
            "tensor_filter name=f framework=faulty "
            "custom=traceable:true batching=true max-batch=8 ! "
            "tensor_sink name=out"
        )
        ex = Executor(p.compile_plan())
        n = next(nd for nd in ex.nodes if nd.name == "f")
        n.restore_state({"frames": 4, "governor": {
            "ceiling": 2, "max": 8, "ooms": 3, "reprobes": 0}})
        n._apply_pending_restore()          # the race: governor not built
        assert n._pending_restore is not None
        assert "governor" in n._pending_restore
        from nnstreamer_tpu.pipeline.device_faults import BucketGovernor

        n.bucket_governor = BucketGovernor([1, 2, 4, 8])
        n._apply_pending_restore()          # the loop's post-build call
        assert n.bucket_governor.ceiling == 2
        assert n.bucket_governor.ooms == 3
        assert n._pending_restore is None

    def test_drain_settle_outlasts_slow_invokes(self):
        """A slow invoke in flight must not masquerade as quiescence:
        the settle window auto-sizes past the slowest observed invoke,
        so after drain() returns True NOTHING is still running and the
        snapshot really is frame-boundary consistent."""
        p = parse_pipeline(
            "tensorsrc name=src dimensions=4 num-frames=400 ! "
            "tensor_chaos name=c delay-ms=80 delay-every-n=1 ! "
            "tensor_sink name=out"
        )
        ex = p.start()
        time.sleep(0.9)            # several delayed invokes observed
        assert ex.drain(timeout=30) is True
        mid = ex.snapshot()["nodes"]["c"]["frames"]
        time.sleep(0.3)            # an in-flight invoke would land here
        assert ex.snapshot()["nodes"]["c"]["frames"] == mid
        assert len(p["out"].frames) == mid
        ex.resume()
        ex.stop()

    def test_drain_timeout_returns_false_and_pipeline_survives(self):
        p = parse_pipeline(
            "tensorsrc name=src dimensions=4 num-frames=60 "
            "pattern=counter ! "
            "tensor_chaos name=c delay-ms=20 delay-every-n=1 ! "
            "tensor_sink name=out"
        )
        ex = p.start()
        # 60 frames * 20 ms can't settle in 0.2 s: drain times out
        assert ex.drain(timeout=0.2) is False
        ex.resume()
        assert ex.wait(60), ex.errors
        assert len(p["out"].frames) == 60


# ----------------------------------------------------------- lint NNS-W112
class TestReplicaLint:
    def test_w112_flags_replicas_without_failover_policy(self):
        from nnstreamer_tpu.analysis.lint import lint

        bare = lint(
            "tensorsrc dimensions=4 num-frames=10 ! "
            "tensor_filter framework=faulty replicas=2 ! tensor_sink"
        )
        assert "NNS-W112" in bare.report.codes

    def test_w112_quiet_with_policy_or_single_instance(self):
        from nnstreamer_tpu.analysis.lint import lint

        with_policy = lint(
            "tensorsrc dimensions=4 num-frames=10 ! "
            "tensor_filter framework=faulty replicas=2 on-error=drop ! "
            "tensor_sink"
        )
        assert "NNS-W112" not in with_policy.report.codes
        single = lint(
            "tensorsrc dimensions=4 num-frames=10 ! "
            "tensor_filter framework=faulty ! tensor_sink"
        )
        assert "NNS-W112" not in single.report.codes


# ------------------------------------------------ persistent compile cache
class TestCompileCache:
    @pytest.fixture
    def place_cache(self, monkeypatch):
        """Place the cache the way an operator would — the standard
        JAX_COMPILATION_CACHE_DIR, which jax reads into its config at
        import (mirrored here, since jax is long imported) — and run
        the program's once-per-process setup again. Restores the
        worker's own cache afterwards."""
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        from nnstreamer_tpu import compile_cache

        prev = jax.config.jax_compilation_cache_dir

        def place(cache_dir):
            if cache_dir is None:
                monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            else:
                monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
                jax.config.update("jax_compilation_cache_dir", str(cache_dir))
            compilation_cache.reset_cache()
            monkeypatch.setattr(compile_cache, "_done", False)
            compile_cache.ensure_compile_cache()
            return jax.config.jax_compilation_cache_dir

        yield place
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()

    def test_cache_dir_placed_from_outside_or_fixed(self, place_cache,
                                                    tmp_path):
        import jax

        from nnstreamer_tpu import compile_cache

        # JAX_COMPILATION_CACHE_DIR set: that directory, untouched — no
        # subdirectory, nothing else named in code
        assert place_cache(tmp_path / "xla") == str(tmp_path / "xla")
        # corruption tolerance: a bad entry logs + recompiles, never
        # raises (jax_raise_persistent_cache_errors forced off)
        assert jax.config.jax_raise_persistent_cache_errors is False
        # unset: the FIXED path inside the checkout (the path is part of
        # every entry's key), per-host subdirectory on the CPU backend
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
        assert place_cache(None).startswith(compile_cache.DEFAULT_DIR + os.sep)

    def test_corrupt_cache_entry_never_crashes(self, place_cache, tmp_path):
        cache = tmp_path / "xla"
        cache.mkdir()
        # seed the directory with garbage "entries" before any compile
        (cache / "jit_f-deadbeef").write_bytes(b"\x00garbage\xff" * 16)
        (cache / "truncated").write_bytes(b"")
        place_cache(cache)
        p = parse_pipeline(
            "tensorsrc dimensions=4 num-frames=10 pattern=counter ! "
            "tensor_filter framework=passthrough ! tensor_sink name=out"
        )
        ex = p.run(timeout=60)
        assert not ex.errors
        assert len(p["out"].frames) == 10


# ------------------------------------------------------------------- soak
@pytest.mark.slow
def test_mixed_device_chaos_soak():
    """Standing chaos soak: OOM pressure on a fused batched segment,
    periodic transient device faults from tensor_chaos under a retry
    policy, and a 2-replica stage losing one replica — 2000 frames,
    exact accounting, sanitizer latch green."""
    p = parse_pipeline(
        "tensorsrc dimensions=4 num-frames=2000 pattern=counter ! "
        "tensor_chaos name=c device-fault-kind=transient "
        "device-fault-every-n=97 on-error=retry retry-max=4 "
        "retry-backoff-ms=0.2 ! "
        "tensor_filter name=rep framework=faulty replicas=2 "
        "replica-unhealthy-after=2 "
        "custom=device_lost_at:40,only_replica:1 ! "
        "tensor_filter name=f framework=faulty "
        "custom=traceable:true,oom_above_rows:4 "
        "batching=true max-batch=16 batch-timeout-ms=1 ! "
        "tensor_sink name=out"
    )
    ex = p.run(timeout=300)
    assert not ex.errors
    s = ex.stats()
    assert len(p["out"].frames) == 2000
    assert ex.totals()["balance"] == 0
    assert s["f"]["oom_events"] >= 1
    assert s["f"]["batch_ceiling"] == 4
    assert s["rep"]["rep_healthy"] == 1
    assert s["rep"]["rep_failovers"] >= 1
    assert s["c"]["error_retries"] >= 20
    vals = [int(f.tensors[0][0]) for f in p["out"].frames]
    assert vals == sorted(vals)
