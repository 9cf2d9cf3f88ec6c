"""Style gate (the reference's gst-indent/pre-commit role, SURVEY.md §2.5):
the in-tree checker must pass over the whole tree, and every registered
builtin element's PROPERTIES schema must cover the properties its code
reads (nns-lint --self-check)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tree_is_style_clean():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_style.py"),
         "--no-self-check", REPO],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, f"style problems:\n{proc.stdout}"


def test_element_property_schemas_cover_code():
    """nns-lint --self-check: an element property readable by code but
    absent from PROPERTIES would be invisible to the linter — fail the
    gate (in-process; tools/check_style.py runs the same check)."""
    from nnstreamer_tpu.analysis.selfcheck import self_check

    problems = self_check()
    assert not problems, "\n".join(problems)


def test_race_lint_clean_on_package():
    """nns-san --race over nnstreamer_tpu/ must report ZERO findings:
    regressions in the repo's concurrency idioms (unlocked shared
    counters, silent service-loop swallows, _Chan pairing violations)
    fail the suite from now on (tools/check_style.py runs the same
    gate on whole-tree runs)."""
    from nnstreamer_tpu.analysis.racecheck import run_race_lint

    report = run_race_lint([os.path.join(REPO, "nnstreamer_tpu")])
    assert not report.diagnostics, report.render()


def test_obs_metric_catalog_covers_code():
    """nns-obs self-check: every metric the package emits is cataloged
    in obs.metrics.METRIC_CATALOG, every cataloged metric has an
    emitter, and docs/observability.md documents every name
    (tools/check_style.py runs the same gate on whole-tree runs)."""
    from nnstreamer_tpu.analysis.selfcheck import obs_self_check

    problems = obs_self_check()
    assert not problems, "\n".join(problems)


def test_span_catalog_covers_code():
    """Span self-check: every ``nns.*`` name the package passes to
    trace.span / trace.instant is in trace.SPAN_CATALOG, every cataloged
    span has an emitter, and docs/observability.md documents every name
    (the benchmark's readers select trace events by these names)."""
    from nnstreamer_tpu.analysis.selfcheck import span_self_check

    problems = span_self_check()
    assert not problems, "\n".join(problems)


def test_san_diagnostic_catalog_covers_code():
    """nns-san --self-check: every emitted code is cataloged, every
    cataloged code has an emitter, slugs stay unique, and the sanitizer
    doc covers the NNS-R/NNS-S codes."""
    from nnstreamer_tpu.analysis.selfcheck import san_self_check

    problems = san_self_check()
    assert not problems, "\n".join(problems)


def test_xray_chain_codes_wired_both_ways():
    """nns-xray --self-check: every chain diagnostic (NNS-W120..W124)
    is cataloged, has an emitter in analysis/xray.py, and is documented
    in docs/chain-analysis.md AND docs/linting.md; conversely the chain
    doc mentions no unknown codes (tools/check_style.py runs the same
    gate on whole-tree runs)."""
    from nnstreamer_tpu.analysis.selfcheck import xray_self_check

    problems = xray_self_check()
    assert not problems, "\n".join(problems)


def test_kscope_kernel_codes_and_registry_wired_both_ways():
    """nns-kscope --self-check wiring: every kernel diagnostic
    (NNS-W127..W129) is cataloged, has an emitter in
    analysis/kernels.py, and is documented in docs/kernel-analysis.md
    AND docs/linting.md; every public ops/pallas kernel entry point has
    a KernelSpec of the same name and vice versa; and the registered
    dispatch ops equal ops/dispatch.KNOWN_OPS both ways
    (tools/check_style.py runs the same gate on whole-tree runs)."""
    from nnstreamer_tpu.analysis.selfcheck import kscope_self_check

    problems = kscope_self_check()
    assert not problems, "\n".join(problems)


def test_disagg_codes_wired_both_ways():
    """nns-disagg --self-check wiring: NNS-W130 is cataloged, has an
    emitter in analysis/lint.py, and is documented in docs/linting.md
    AND docs/llm-serving.md; both disagg metrics are in METRIC_CATALOG
    with live emitters (tools/check_style.py runs the same gate on
    whole-tree runs)."""
    from nnstreamer_tpu.analysis.selfcheck import disagg_self_check

    problems = disagg_self_check()
    assert not problems, "\n".join(problems)


@pytest.mark.slow
def test_documented_pipelines_xray_clean():
    """Every pipeline string embedded in examples/ and docs/ must xray
    clean of the chain diagnostics W120-W124 — a shipped snippet firing
    one is either a bad example or a false positive
    (tools/check_style.py runs the same gate on whole-tree runs; slow:
    it compiles ~20 documented pipelines, and tier-1 seconds displace
    passing dots at the truncated tail of the 870 s budget)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_style", os.path.join(REPO, "tools", "check_style.py")
    )
    check_style = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check_style)
    assert check_style.documented_pipeline_strings(), "sweep found nothing"
    problems = check_style.run_xray_docs_gate()
    assert not problems, "\n".join(problems)
