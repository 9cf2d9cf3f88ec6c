"""nns-xray: chain compile-unit inference, the jaxpr lint walkers
(NNS-W120..W124), the static cost model verified against the runtime
TransferTally, the kernel dispatch table, and the CLI."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu import config as config_mod
from nnstreamer_tpu.analysis.costmodel import (
    configured_device_bound,
    plan_transfer_boundaries,
    predict_frame_transfers,
    spec_bytes,
)
from nnstreamer_tpu.analysis.diagnostics import LintReport
from nnstreamer_tpu.analysis.xray import (
    _segment_pass,
    cache_key_finding,
    donation_finding,
    dispatch_table,
    dtype_findings,
    host_callback_prims,
    xray,
)
from nnstreamer_tpu.pipeline.batching import BatchConfig
from nnstreamer_tpu.pipeline.parse import parse_pipeline

# one chain end to end: two device-capable segments joined across a
# queue (device-passthrough) — 16x16 RGB = 768 bytes/frame
ONE_CHAIN = (
    "videotestsrc device=true num-frames=4 width=16 height=16 ! "
    "tensor_converter ! tensor_filter framework=scaler ! queue ! "
    "tensor_filter framework=scaler ! fakesink"
)

# the control: a host-bound filter (hostscaler: numpy, no traceable_fn)
# severs the chain — every frame round-trips through host mid-stream
HOST_SPLIT = (
    "videotestsrc device=true num-frames=4 width=16 height=16 ! "
    "tensor_converter ! tensor_filter framework=scaler ! "
    "tensor_filter name=hostop framework=hostscaler ! "
    "tensor_filter framework=scaler ! fakesink"
)

FRAME_BYTES = 16 * 16 * 3

# a single fused segment with a STATIC tensor input spec (tensorsrc, not
# video caps), so _negotiated_sig() is concrete — the jaxpr-walker tests
# trace and mutate this one
SEG_DESC = (
    "tensorsrc dimensions=16 types=float32 num-frames=1 ! "
    "tensor_filter framework=scaler ! fakesink"
)


# ------------------------------------------------------- chain inference
class TestChains:
    def test_single_chain_through_queue(self):
        r = xray(ONE_CHAIN)
        assert not r.degraded and not r.errors
        assert len(r.chains) == 1
        assert len(r.chains[0].segments) == 2  # queue splits segments...
        assert r.codes == []  # ...but not the chain

    def test_plan_chains_partition_segments(self):
        plan = parse_pipeline(ONE_CHAIN).compile_plan()
        chains = plan.chains()
        members = [id(s) for ch in chains for s in ch.segments]
        assert sorted(members) == sorted(id(s) for s in plan.segments)
        assert len(members) == len(set(members))  # exactly one chain each

    def test_host_split_makes_two_chains(self):
        r = xray(HOST_SPLIT)
        assert len(r.chains) == 2
        assert "NNS-W120" in r.codes
        w120 = [d for d in r.diagnostics if d.code == "NNS-W120"]
        assert w120[0].element == "hostop"
        # the message names both severed chains
        assert all(c.name in w120[0].message for c in r.chains)

    @pytest.mark.slow
    def test_composite_face_cascade_is_one_chain(self):
        # the PR-12 detect->crop->landmark cascade: converter, detector,
        # crop-resize and landmark all land in ONE compile unit with
        # zero predicted host transfer (acceptance pin)
        desc = (
            "videotestsrc pattern=gradient num-frames=1 device=true "
            "width=128 height=128 ! tensor_converter ! "
            "tensor_filter framework=jax model=zoo:face_detect "
            'custom="output:regions+image,threshold:0.0,frame_size:128:128" '
            "! tensor_transform mode=crop-resize option=112:112 ! queue ! "
            "tensor_filter framework=jax model=zoo:face_landmark "
            'custom="batch:16" ! fakesink'
        )
        r = xray(desc)
        assert not r.degraded
        assert len(r.chains) == 1
        assert r.chains[0].n_ops == 4
        assert r.codes == []
        assert r.predicted == {"h2d": 0, "d2h": 0}
        assert r.predicted_tpu == {"h2d": 0, "d2h": 0}
        assert r.chains[0].cost.params_bytes > 0  # real opened weights


# ------------------------------------- cost model vs the runtime tally
class TestTransferPrediction:
    def test_zero_transfer_chain_predicts_and_measures_zero(self):
        r = xray(ONE_CHAIN)
        assert r.predicted == {"h2d": 0, "d2h": 0}
        assert r.boundaries == []
        ex = parse_pipeline(ONE_CHAIN).run(timeout=60)
        assert ex.transfer_totals() == {"h2d": 0, "d2h": 0}
        chk = ex.transfer_crosscheck()
        assert chk["delta"] == {"h2d": 0, "d2h": 0}

    def test_host_split_prediction_matches_measured_tally(self):
        r = xray(HOST_SPLIT)
        d2h = [b for b in r.boundaries if b.direction == "d2h"]
        assert len(d2h) == 1 and d2h[0].reason == "producer-fetch"
        assert d2h[0].bytes_per_frame == FRAME_BYTES
        assert r.predicted == {"h2d": 0, "d2h": FRAME_BYTES}
        ex = parse_pipeline(HOST_SPLIT).run(timeout=60)
        chk = ex.transfer_crosscheck()
        assert chk["measured"]["d2h"] == 4 * FRAME_BYTES
        assert chk["predicted"] == chk["measured"]
        assert chk["delta"] == {"h2d": 0, "d2h": 0}

    def test_reading_sink_is_a_sink_fetch_boundary(self):
        desc = ONE_CHAIN.replace("fakesink", "tensor_sink")
        r = xray(desc)
        d2h = [b for b in r.boundaries if b.direction == "d2h"]
        assert len(d2h) == 1 and d2h[0].reason == "sink-fetch"
        assert r.predicted["d2h"] == FRAME_BYTES

    def test_tpu_view_adds_source_staging(self):
        # a HOST source feeding a device segment: free on local CPU
        # (stage_frame is passthrough), one h2d staging per frame on TPU
        desc = ONE_CHAIN.replace("videotestsrc device=true ", "videotestsrc ")
        r = xray(desc)
        assert r.predicted["h2d"] == 0
        assert r.predicted_tpu["h2d"] == FRAME_BYTES

    def test_media_spec_bytes_estimate(self):
        p = parse_pipeline(ONE_CHAIN)
        src = next(e for e in p.elements if e.name.startswith("videotestsrc"))
        plan = p.compile_plan()
        assert plan is not None  # negotiation ran; src out spec is media
        assert spec_bytes(src.out_specs[0]) == FRAME_BYTES


# ------------------------------------------------- jaxpr lint walkers
class TestJaxprWalkers:
    def test_dtype_promotion_flagged(self):
        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(
                lambda x: jnp.sin(x.astype(jnp.float64))
            )(jax.ShapeDtypeStruct((4,), jnp.float32))
            msgs = dtype_findings(jaxpr)
        assert msgs and "float64" in msgs[0]

    def test_clean_f32_math_unflagged(self):
        jaxpr = jax.make_jaxpr(lambda x: jnp.sin(x) * 2.0)(
            jax.ShapeDtypeStruct((4,), jnp.float32)
        )
        assert dtype_findings(jaxpr) == []

    def test_wide_input_excuses_wide_math(self):
        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(lambda x: x + 1.0)(
                jax.ShapeDtypeStruct((4,), jnp.float64)
            )
            assert dtype_findings(jaxpr) == []

    def test_declared_output_drift_flagged(self):
        jaxpr = jax.make_jaxpr(lambda x: (x * 2.0,))(
            jax.ShapeDtypeStruct((4,), jnp.float32)
        )
        msgs = dtype_findings(jaxpr, declared_out=(np.int8,))
        assert msgs and "int8" in msgs[0]

    def test_host_callback_prims_found(self):
        def f(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct((4,), np.float32), x
            )

        jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32))
        assert host_callback_prims(jaxpr) == ["pure_callback"]

    def test_callback_in_segment_fires_w120(self):
        plan = parse_pipeline(SEG_DESC).compile_plan()
        seg = plan.segments[0]
        sig = seg._negotiated_sig()
        assert sig is not None

        def with_callback(*tensors):
            out = jax.pure_callback(
                lambda a: a,
                jax.ShapeDtypeStruct(sig[0][0], sig[0][1]),
                tensors[0],
            )
            return (out,)

        seg._compose = lambda: with_callback
        report = LintReport()
        _segment_pass(seg, report, [])
        assert "NNS-W120" in report.codes


# ---------------------------------------------- W121 cache-key hazards
class TestCacheKeys:
    def _seg(self):
        return parse_pipeline(SEG_DESC).compile_plan().segments[0]

    def test_flexible_spec_under_batching_is_unbounded(self):
        seg = self._seg()
        seg._negotiated_sig = lambda: None
        seg.batch_config = BatchConfig(
            enabled=True, max_batch=8, buckets=(1, 2, 4, 8)
        )
        msg = cache_key_finding(seg)
        assert msg is not None and "unbounded" in msg
        report = LintReport()
        _segment_pass(seg, report, [])
        assert "NNS-W121" in report.codes

    def test_bucket_ladder_explosion_flagged(self):
        seg = self._seg()
        seg.donate = True
        seg.batch_config = BatchConfig(
            enabled=True, max_batch=40, buckets=tuple(range(1, 41))
        )
        msg = cache_key_finding(seg)
        assert msg is not None and "82" in msg

    def test_healthy_ladder_clean(self):
        seg = self._seg()
        seg.batch_config = BatchConfig(
            enabled=True, max_batch=8, buckets=(1, 2, 4, 8)
        )
        assert cache_key_finding(seg) is None


# --------------------------------------------- W123 defeated donation
class TestDonation:
    DESC = (
        "tensorsrc dimensions=512:512:3 types=uint8 num-frames=1 ! "
        "tensor_filter framework=scaler ! fakesink"
    )

    def _seg(self):
        return parse_pipeline(self.DESC).compile_plan().segments[0]

    def _arm(self, seg):
        # the donating batched path: stacked windows donate everywhere
        seg.donate = True
        seg.ring_depth = 2
        seg.batch_config = BatchConfig(
            enabled=True, max_batch=2, buckets=(2,)
        )

    def test_no_reusable_output_fires(self):
        seg = self._seg()
        self._arm(seg)
        # output dtype differs from every input: nothing aliasable
        seg._compose = lambda: (
            lambda *ts: tuple(t.astype(jnp.float32) * 0.5 for t in ts)
        )
        msg = donation_finding(seg)
        assert msg is not None and "donated" in msg
        report = LintReport()
        _segment_pass(seg, report, [])
        assert "NNS-W123" in report.codes

    def test_matching_output_is_reusable_and_clean(self):
        seg = self._seg()
        self._arm(seg)  # default compose preserves shape and dtype
        assert donation_finding(seg) is None

    def test_per_frame_path_never_donates_on_cpu(self):
        seg = self._seg()
        seg.donate = True
        seg.ring_depth = 2  # no batching: the CPU per-frame path
        seg._compose = lambda: (
            lambda *ts: tuple(t.astype(jnp.float32) for t in ts)
        )
        if jax.default_backend() == "cpu":
            assert donation_finding(seg) is None


# ------------------------------------------------ W124 resident bound
class TestResidentBound:
    def test_bound_breach_fires_w124(self, monkeypatch):
        monkeypatch.setenv("NNS_TPU_PLANE_MEMORY_PER_DEVICE", "1024")
        config_mod.reload_conf()
        try:
            assert configured_device_bound() == 1024
            r = xray(ONE_CHAIN)
            assert "NNS-W124" in r.codes
            w124 = [d for d in r.diagnostics if d.code == "NNS-W124"][0]
            assert "memory_per_device" in w124.message
        finally:
            monkeypatch.delenv("NNS_TPU_PLANE_MEMORY_PER_DEVICE")
            config_mod.reload_conf()

    def test_no_bound_no_finding(self):
        assert configured_device_bound() is None
        assert "NNS-W124" not in xray(ONE_CHAIN).codes


# -------------------------------------------------- dispatch counters
class TestDispatch:
    def test_tally_records_resolved_impl(self):
        from nnstreamer_tpu.ops import dispatch as disp
        from nnstreamer_tpu.ops.image import resize_bilinear

        before = disp.tally.snapshot()
        resize_bilinear(jnp.zeros((8, 8, 3), jnp.float32), 4, 4)
        engaged = disp.engaged_impls("resize_bilinear", before)
        want = "pallas" if jax.default_backend() == "tpu" else "jnp"
        assert engaged == [want]

    def test_dispatch_table_probes_every_dual_path_op(self):
        rows = {r["op"]: r for r in dispatch_table()}
        assert set(rows) == {
            "crop_and_resize", "resize_bilinear", "nms",
            "block_attention", "serving_attention",
        }
        here = "pallas" if jax.default_backend() == "tpu" else "jnp"
        for op in ("crop_and_resize", "resize_bilinear", "nms",
                   "block_attention"):
            assert rows[op]["auto_on_tpu"] == "pallas"
            # the record lands at the branch point, so even a probe
            # that fails numerically proves its dispatch
            assert rows[op]["measured"] == [here], rows[op]
        assert rows["serving_attention"]["auto_here"] in ("pallas", "xla")
        assert rows["serving_attention"]["measured"] == []

    def test_no_probe_skips_measurement(self):
        rows = dispatch_table(run=False)
        assert all(r["measured"] == [] and r["error"] is None for r in rows)


# ----------------------------------------------------------------- CLI
class TestCli:
    def test_clean_pipeline_exits_zero(self, capsys):
        from nnstreamer_tpu.analysis.xray_cli import main

        assert main([ONE_CHAIN]) == 0
        out = capsys.readouterr().out
        assert "compile units: 1" in out

    def test_warnings_exit_one_strict_two(self, capsys):
        from nnstreamer_tpu.analysis.xray_cli import main

        assert main([HOST_SPLIT]) == 1
        assert main(["--strict", HOST_SPLIT]) == 2

    def test_json_report(self, capsys):
        from nnstreamer_tpu.analysis.xray_cli import main

        assert main(["--json", HOST_SPLIT]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert len(doc["chains"]) == 2
        assert doc["predicted"] == {"h2d": 0, "d2h": FRAME_BYTES}
        assert any(d["code"] == "NNS-W120" for d in doc["diagnostics"])

    def test_dispatch_flag(self, capsys):
        from nnstreamer_tpu.analysis.xray_cli import main

        assert main(["--dispatch", "--no-probe"]) == 0
        out = capsys.readouterr().out
        assert "crop_and_resize" in out and "block_attention" in out

    def test_self_check_flag(self, capsys):
        from nnstreamer_tpu.analysis.xray_cli import main

        assert main(["--self-check"]) == 0
        assert "OK" in capsys.readouterr().out


# ----------------------------------------------------- degraded mode
class TestDegraded:
    def test_missing_model_degrades_not_diagnoses(self):
        r = xray(
            "videotestsrc ! tensor_converter ! "
            "tensor_filter framework=jax model=/does/not/exist.pkl ! "
            "fakesink"
        )
        assert r.degraded
        assert r.codes == []
        assert r.exit_code == 0
        assert any("compile_plan failed" in n for n in r.notes)

    def test_parse_failure_is_an_error(self):
        r = xray("videotestsrc ! ! fakesink")
        assert r.errors and r.exit_code == 2

    def test_crosscheck_flag_reads_env(self, monkeypatch):
        from nnstreamer_tpu.pipeline import transfer

        monkeypatch.setenv("NNS_XRAY_CROSSCHECK", "1")
        assert transfer.xray_crosscheck_enabled()
        monkeypatch.setenv("NNS_XRAY_CROSSCHECK", "0")
        assert not transfer.xray_crosscheck_enabled()
