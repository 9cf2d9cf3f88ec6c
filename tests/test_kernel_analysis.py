"""nns-kscope (analysis/kernels.py): hand-computed VMEM residency,
both-ways NNS-W127/W128 on synthetic specs, the NNS-W129 lint pass,
engagement proof (including the forced-fallback drill), the registry
differential sweep, the CLI, and bench.py's pallas-evidence warnings."""

import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

from nnstreamer_tpu.analysis import lint
from nnstreamer_tpu.analysis.kernels import (
    analyze,
    analyze_case,
    differential_sweep,
    engage,
)
from nnstreamer_tpu.ops.pallas import registry as kreg
from nnstreamer_tpu.ops.pallas._compat import DISABLE_ENV, pallas_ok

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(blocks, grid, scratch=(), prefetch=(), flops=0, cases=None):
    """A synthetic KernelSpec (NOT registered) for analyzer unit tests."""
    plan = kreg.LaunchPlan(
        grid=grid, blocks=tuple(blocks), scratch=tuple(scratch),
        prefetch=tuple(prefetch), flops=flops,
    )
    return kreg.KernelSpec(
        name="synthetic", module=__name__, ops=("nms",),
        dtypes=("float32",),
        cases=cases or (kreg.ShapeCase("only", {}),),
        plan=lambda params: plan,
        run_case=lambda params: (0.0, 0.0, 1e-6),
        probe=lambda: None,
    )


class TestVmemModel:
    """The residency arithmetic, checked by hand: one varying input
    (double-buffered), one constant input (single-buffered, fetched
    once), the output, scratch, and SMEM prefetch."""

    def _case(self, bound=None):
        # every index map also receives the scalar-prefetch arrays, as
        # under pltpu.PrefetchScalarGridSpec
        blocks = [
            kreg.BlockDesc("x", "in", (32, 128), (8, 128), "float32",
                           lambda i, pos: (i, 0)),
            kreg.BlockDesc("w", "in", (8, 128), (8, 128), "float32",
                           lambda i, pos: (0, 0)),
            kreg.BlockDesc("o", "out", (32, 128), (8, 128), "float32",
                           lambda i, pos: (i, 0)),
        ]
        spec = _spec(
            blocks, grid=(4,),
            scratch=(kreg.ScratchDesc("acc", (8, 128)),),
            prefetch=(kreg.PrefetchDesc(
                "pos", (4,), make=lambda: np.zeros((4,), np.int32)),),
            flops=1000,
        )
        return analyze_case(spec, "only", bound=bound)

    def test_hand_computed_bytes(self):
        r = self._case(bound=1 << 24)
        by = {b.name: b for b in r.blocks}
        # 8*128*4 B per buffer; varying blocks double-buffer
        assert by["x"].block_bytes == 4096
        assert by["x"].buffers == 2 and by["x"].vmem_bytes == 8192
        assert by["w"].buffers == 1 and by["w"].vmem_bytes == 4096
        assert by["o"].buffers == 2
        # fetches by index-map transition: x/o once per step, w once
        assert by["x"].fetches == 4 and by["w"].fetches == 1
        assert r.scratch_bytes == 8 * 128 * 4
        assert r.vmem_bytes == 8192 + 4096 + 8192 + 4096
        assert r.smem_bytes == 4 * 4  # (4,) int32 prefetch lives in SMEM
        assert r.cost.hbm_read_bytes == 4 * 4096 + 4096
        assert r.cost.hbm_write_bytes == 4 * 4096
        assert r.cost.flops == 1000
        assert not r.over_budget and not r.misaligned and not r.hazards

    def test_row_shape(self):
        row = self._case(bound=1 << 24).to_row()
        for key in ("kernel", "case", "grid", "vmem_bytes", "over_budget",
                    "hbm_read_bytes", "flops", "arithmetic_intensity",
                    "misaligned", "hazards"):
            assert key in row
        assert row["over_budget"] is False and row["misaligned"] == []

    def test_w127_fires_when_over_bound_and_only_then(self):
        spec = _spec(
            [kreg.BlockDesc("x", "in", (32, 128), (8, 128), "float32",
                            lambda i: (i, 0))],
            grid=(4,),
        )
        _, rep = analyze([spec], bound=8191)  # 2 buffers x 4096 B > bound
        assert [d.code for d in rep.diagnostics] == ["NNS-W127"]
        _, rep = analyze([spec], bound=8192)
        assert rep.diagnostics == []


class TestAlignment:
    def _one(self, array, block, dtype="float32"):
        spec = _spec(
            [kreg.BlockDesc("x", "in", array, block, dtype,
                            lambda i: tuple(0 for _ in block))],
            grid=(1,),
        )
        return analyze_case(spec, "only", bound=1 << 30)

    def test_lane_misalignment_flagged(self):
        r = self._one((8, 256), (8, 100))
        assert any("lane" in p for p in r.blocks[0].problems)

    def test_sublane_misalignment_by_dtype(self):
        # f32 sublane 8: 5 rows of a 40-row axis misaligns
        assert self._one((40, 128), (5, 128)).misaligned
        # int8 sublane 32: 16 rows misaligns; f32 16 rows is fine
        assert self._one((64, 128), (16, 128), "int8").misaligned
        assert not self._one((64, 128), (16, 128)).misaligned

    def test_whole_axis_exempt_unit_dim_is_not(self):
        """The TPU compiler's block rule, both ways: a dim that IS the
        whole axis passes whatever its size; a unit dim that is not —
        the one-head ``(1, 1, 1, d)`` block the decode kernels used to
        take over ``[B, 1, H, d]``, which Mosaic refuses — is flagged."""
        assert not self._one((8, 100), (8, 100)).misaligned
        assert not self._one((1, 100), (1, 100)).misaligned
        bf16 = self._one((32, 256), (16, 128), "bfloat16")
        assert not bf16.misaligned  # bf16 sublane is exactly 16
        one_head = self._one((4, 1, 16, 128), (1, 1, 1, 128))
        assert any("second-minor" in p for p in one_head.blocks[0].problems)
        all_heads = self._one((4, 1, 16, 128), (1, 1, 16, 128))
        assert not all_heads.misaligned
        assert self._one((8, 100), (1, 100)).misaligned
        _, rep = analyze([_spec(
            [kreg.BlockDesc("q", "in", (4, 1, 16, 128), (1, 1, 1, 128),
                            "float32", lambda i: (0, 0, 0, 0))],
            grid=(1,),
        )], bound=1 << 30)
        assert [d.code for d in rep.diagnostics] == ["NNS-W128"]

    def test_w128_fires_on_misalignment_and_only_then(self):
        bad = _spec(
            [kreg.BlockDesc("x", "in", (8, 256), (8, 100), "float32",
                            lambda i: (0, 0))],
            grid=(1,),
        )
        _, rep = analyze([bad], bound=1 << 30)
        assert [d.code for d in rep.diagnostics] == ["NNS-W128"]


class TestIndexMapHazards:
    def test_out_of_bounds_pick(self):
        spec = _spec(
            [kreg.BlockDesc("x", "in", (16, 128), (8, 128), "float32",
                            lambda i: (i, 0))],   # 2 blocks, grid walks 4
            grid=(4,),
        )
        r = analyze_case(spec, "only", bound=1 << 30)
        assert any("outside" in p for p in r.blocks[0].problems)

    def test_arity_mismatch_and_raise(self):
        spec = _spec(
            [
                kreg.BlockDesc("short", "in", (8, 128), (8, 128), "float32",
                               lambda i: (0,)),
                kreg.BlockDesc("boom", "in", (8, 128), (8, 128), "float32",
                               lambda i: (1 // 0, 0)),
            ],
            grid=(2,),
        )
        r = analyze_case(spec, "only", bound=1 << 30)
        by = {b.name: b for b in r.blocks}
        assert any("coordinates" in p for p in by["short"].problems)
        assert any("raised" in p for p in by["boom"].problems)

    def test_prefetch_shape_drift_is_a_hazard(self):
        spec = _spec(
            [kreg.BlockDesc("x", "in", (8, 128), (8, 128), "float32",
                            lambda i, tbl: (0, 0))],
            grid=(1,),
            prefetch=(kreg.PrefetchDesc(
                "tbl", (4,), make=lambda: np.zeros((5,), np.int32)),),
        )
        r = analyze_case(spec, "only", bound=1 << 30)
        assert any("drifts" in h for h in r.hazards)
        _, rep = analyze([spec], bound=1 << 30)
        assert "NNS-W128" in [d.code for d in rep.diagnostics]

    def test_index_maps_get_real_prefetch_values(self):
        """make() values (not zeros) feed the maps — a block-table map
        that would go OOB on zeros stays clean on the real table."""
        spec = _spec(
            [kreg.BlockDesc("kv", "in", (32, 128), (8, 128), "float32",
                            lambda i, tbl: (int(tbl[i]), 0))],
            grid=(2,),
            prefetch=(kreg.PrefetchDesc(
                "tbl", (2,), make=lambda: np.asarray([3, 1], np.int32)),),
        )
        r = analyze_case(spec, "only", bound=1 << 30)
        assert not r.blocks[0].problems and not r.hazards
        assert r.blocks[0].fetches == 2


class TestRegistryAnalysis:
    def test_every_registered_case_is_clean(self):
        """The acceptance invariant: the shipped registry has no
        over-VMEM case, no misaligned tile, no index-map hazard."""
        reports, rep = analyze()
        assert rep.diagnostics == [], rep.render()
        names = {r.kernel for r in reports}
        assert names == set(kreg.names())
        assert len(reports) >= len(names)  # every kernel swept >=1 case

    def test_largest_case_has_headroom_but_not_10x(self):
        """The grid includes realistic near-budget shapes — the analyzer
        is exercised in the regime where the answer matters."""
        reports, _ = analyze()
        biggest = max(r.vmem_bytes for r in reports)
        assert biggest > 4 << 20, "no case within 4x of the 16 MiB bound"

    def test_supports_dtype(self):
        assert kreg.supports_dtype("resize_bilinear", "uint8")
        assert not kreg.supports_dtype("resize_bilinear", np.float64)
        assert kreg.supports_dtype("no_such_kernel", np.float64)


class TestDegrade:
    def test_unsupported_dtype_degrades_with_logged_reason(self, caplog):
        with caplog.at_level("WARNING", logger="nnstreamer_tpu.ops.pallas"):
            ok, reason = pallas_ok("resize_bilinear", "float64")
        assert not ok and "float64" in reason
        assert any("fallback" in r.message for r in caplog.records)

    def test_kill_switch_degrades_everything(self, monkeypatch):
        monkeypatch.setenv(DISABLE_ENV, "1")
        ok, reason = pallas_ok("flash_attention", "float32")
        assert not ok and DISABLE_ENV in reason

    def test_healthy_request_passes(self, monkeypatch):
        monkeypatch.delenv(DISABLE_ENV, raising=False)
        assert pallas_ok("decode_attention", "float32") == (True, "")


class TestEngage:
    def test_healthy_kernel_engages_pallas_only(self, monkeypatch):
        monkeypatch.delenv(DISABLE_ENV, raising=False)
        (row,) = engage([kreg.get("resize_bilinear")])
        assert row["ok"] and row["impls"] == ["pallas"]
        assert row["error"] is None

    def test_forced_fallback_fails_the_row(self, monkeypatch):
        monkeypatch.setenv(DISABLE_ENV, "1")
        (row,) = engage([kreg.get("resize_bilinear")])
        assert not row["ok"] and "pallas" not in row["impls"]


class TestDifferentialSweep:
    def test_one_case_parity(self):
        spec = kreg.get("resize_bilinear")
        narrow = dataclasses.replace(spec, cases=(spec.cases[0],))
        (row,) = differential_sweep([narrow], full=True)
        assert row["ok"], row["error"]
        assert row["max_err"] <= 1e-5

    def test_failure_becomes_a_row_not_a_raise(self):
        spec = _spec(
            [kreg.BlockDesc("x", "in", (8, 128), (8, 128), "float32",
                            lambda i: (0, 0))],
            grid=(1,),
            cases=(kreg.ShapeCase("only", {}, tier1=True),),
        )
        broken = dataclasses.replace(
            spec, run_case=lambda params: (np.ones(3), np.zeros(3), 1e-6)
        )
        (row,) = differential_sweep([broken])
        assert not row["ok"] and "AssertionError" in row["error"]

    @pytest.mark.slow
    def test_full_registry_sweep(self):
        rows = differential_sweep(full=True)
        bad = [r for r in rows if not r["ok"]]
        assert not bad, bad
        assert len(rows) == sum(len(s.cases) for s in kreg.all_specs())


class TestPallasRequestLint:
    """NNS-W129: requested pallas that would silently dispatch jnp."""

    RESIZE = (
        "videotestsrc width=64 height=48 num-buffers=1 ! tensor_converter ! "
        "tensor_transform mode=resize option=24:32 impl=pallas ! tensor_sink"
    )
    LLM = "appsrc dimensions=4 ! tensor_llm_serversink id=lint-probe attn-impl=pallas"

    def test_healthy_requests_are_quiet(self, monkeypatch):
        monkeypatch.delenv(DISABLE_ENV, raising=False)
        assert lint(self.RESIZE).codes == []
        assert lint(self.LLM).codes == []

    def test_unsupported_dtype_flagged(self, monkeypatch):
        monkeypatch.delenv(DISABLE_ENV, raising=False)
        bad = (
            "videotestsrc width=64 height=48 num-buffers=1 ! "
            "tensor_converter ! tensor_transform mode=typecast "
            "option=float64 ! tensor_transform mode=resize option=24:32 "
            "impl=pallas ! tensor_sink"
        )
        result = lint(bad)
        assert result.codes == ["NNS-W129"]
        assert result.exit_code == 1

    def test_mode_with_no_kernel_flagged(self):
        nokernel = (
            "tensorsrc dimensions=4 num-frames=1 ! tensor_transform "
            "mode=typecast option=float32 impl=pallas ! tensor_sink"
        )
        assert lint(nokernel).codes == ["NNS-W129"]

    def test_kill_switch_flags_both_element_kinds(self, monkeypatch):
        monkeypatch.setenv(DISABLE_ENV, "1")
        assert lint(self.RESIZE).codes == ["NNS-W129"]
        assert lint(self.LLM).codes == ["NNS-W129"]


class TestCli:
    def _main(self, argv):
        from nnstreamer_tpu.analysis.kscope_cli import main

        return main(argv)

    def test_json_report_clean(self, capsys):
        assert self._main(["--json", "--kernel", "nms"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert data["exit_code"] == 0 and data["diagnostics"] == []
        assert {r["kernel"] for r in data["cases"]} == {"nms"}

    def test_unknown_kernel_exits_2(self, capsys):
        assert self._main(["--kernel", "nope"]) == 2
        assert "registered" in capsys.readouterr().err

    def test_strict_promotes_warnings(self, monkeypatch, capsys):
        monkeypatch.setattr(
            "nnstreamer_tpu.analysis.kernels.configured_vmem_bound",
            lambda: 1,
        )
        assert self._main(["--quiet", "--kernel", "nms"]) == 1
        assert self._main(["--quiet", "--strict", "--kernel", "nms"]) == 2
        capsys.readouterr()

    def test_engage_json(self, monkeypatch, capsys):
        monkeypatch.delenv(DISABLE_ENV, raising=False)
        assert self._main(
            ["--engage", "--kernel", "resize_bilinear", "--json"]) == 0
        (row,) = json.loads(capsys.readouterr().out)
        assert row["impls"] == ["pallas"]

    def test_engage_nonzero_on_forced_fallback(self, monkeypatch, capsys):
        monkeypatch.setenv(DISABLE_ENV, "1")
        assert self._main(["--engage", "--kernel", "resize_bilinear"]) == 1
        assert "FELL BACK" in capsys.readouterr().out

    def test_self_check_single_kernel(self, capsys):
        assert self._main(
            ["--self-check", "--kernel", "resize_bilinear", "--quiet"]) == 0
        assert "OK" in capsys.readouterr().out


class TestBenchPallasEvidence:
    """bench.py --gate pallas-tally warnings (pure helper, synthetic
    records)."""

    @pytest.fixture(scope="class")
    def bench(self):
        spec = importlib.util.spec_from_file_location(
            "bench_mod", os.path.join(REPO, "bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _rec(self, platform, dispatch):
        cell = {"value": 1.0}
        if dispatch is not None:
            cell["dispatch"] = dispatch
        return {"platform": platform,
                "cells": {"composite_face_fps": cell}}

    def test_fallback_only_tpu_evidence_warns(self, bench):
        warns = bench._pallas_tally_warnings(
            self._rec("tpu", {"crop_and_resize:jnp": 3}))
        assert len(warns) == 1 and "crop_and_resize" in warns[0]
        assert "nns-kscope --engage" in warns[0]

    def test_engaged_or_inapplicable_records_stay_quiet(self, bench):
        assert bench._pallas_tally_warnings(
            self._rec("tpu", {"crop_and_resize:pallas": 2,
                              "crop_and_resize:jnp": 1})) == []
        assert bench._pallas_tally_warnings(
            self._rec("cpu", {"crop_and_resize:jnp": 3})) == []
        # pre-capture-tpu reference: no dispatch evidence either way
        assert bench._pallas_tally_warnings(self._rec("tpu", None)) == []

    def test_gated_cells_reference_real_kernels(self, bench):
        for ops in bench.PALLAS_CELLS.values():
            for op in ops:
                assert kreg.find(op) is not None
