#!/usr/bin/env python
"""In-tree style checker — the role of the reference's gst-indent /
pre-commit hooks (tools/development/, SURVEY.md §2.5), self-contained so it
runs with no network or extra deps.

Rules for tracked .py files (and the C++ under native/):
- no tabs, no trailing whitespace, LF line endings, final newline
- max line length 100 (the repo style; docstring URLs exempt)
- no merge-conflict markers
- `nns-lint --self-check` passes: every registered builtin element's
  PROPERTIES schema covers the properties its code reads (whole-tree
  runs only — explicit path args stay stdlib-fast; --no-self-check
  forces it off entirely)
- `nns-san --race nnstreamer_tpu/` is clean: the package source obeys
  its own concurrency idioms (same whole-tree-only gating)
- `nns-xray --self-check` passes (chain diagnostics W120-W125 wired
  emitters<->catalog<->docs both ways) and every pipeline string in
  examples/ and docs/ xrays clean of the chain diagnostics (same
  whole-tree-only gating)
- `nns-kscope --self-check` wiring passes (kernel diagnostics
  W127-W129 wired emitters<->catalog<->docs, pallas registry complete
  against the package and dispatch.KNOWN_OPS; the interpret-mode
  parity sweep stays in the test suite, not here)

Usage: python tools/check_style.py [paths...]   (default: repo tree)
Exit 0 clean, 1 with findings listed one per line.
"""

from __future__ import annotations

import os
import re
import sys

MAX_LEN = 100
_SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", "golden", "build",
              "dist", ".eggs"}
_EXTS = (".py", ".cpp", ".cc", ".h", ".hpp", ".proto", ".toml")
_CONFLICT = re.compile(r"^(<{7}|={7}|>{7})( |$)")
_GENERATED = ("_pb2.py", "_pb2_grpc.py")
_URL = re.compile(r"https?://\S+")


def check_file(path: str) -> list:
    problems = []
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as exc:
        return [f"{path}: unreadable: {exc}"]
    if b"\r\n" in blob:
        problems.append(f"{path}: CRLF line endings")
    if blob and not blob.endswith(b"\n"):
        problems.append(f"{path}: missing final newline")
    text = blob.decode("utf-8", errors="replace")
    for i, line in enumerate(text.split("\n"), 1):
        if "\t" in line:
            problems.append(f"{path}:{i}: tab character")
        if line != line.rstrip():
            problems.append(f"{path}:{i}: trailing whitespace")
        if len(line) > MAX_LEN and not _URL.search(line):
            problems.append(f"{path}:{i}: line longer than {MAX_LEN} "
                            f"({len(line)})")
        if _CONFLICT.match(line):
            problems.append(f"{path}:{i}: merge conflict marker")
    return problems


def iter_files(roots):
    for root in roots:
        if os.path.isfile(root):
            yield root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for fn in filenames:
                if fn.endswith(_EXTS) and not fn.endswith(_GENERATED):
                    yield os.path.join(dirpath, fn)


def run_self_check() -> list:
    """Run nns-lint --self-check in-process: schema gaps are style
    problems (an element property without a PROPERTIES entry is invisible
    to gst-inspect-style tooling and to the static analyzer)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from nnstreamer_tpu.analysis.selfcheck import self_check
    except Exception as exc:  # pragma: no cover - broken tree
        return [f"nns-lint --self-check could not run: {exc}"]
    return [f"self-check: {p}" for p in self_check()]


def run_obs_self_check() -> list:
    """Run the nns-obs metric-catalog and span-catalog self-checks
    in-process: a metric or span emitted but uncataloged (or cataloged
    but undocumented) is invisible to dashboards, to the benchmark's
    readers and to docs/observability.md readers."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from nnstreamer_tpu.analysis.selfcheck import (
            obs_self_check,
            span_self_check,
        )
    except Exception as exc:  # pragma: no cover - broken tree
        return [f"obs self-check could not run: {exc}"]
    return [f"obs: {p}" for p in obs_self_check() + span_self_check()]


def run_race_lint_gate() -> list:
    """Run nns-san --race over the package in-process: a concurrency-
    idiom violation (unlocked shared counter, silent service-loop
    swallow, broken _Chan pairing, ...) is a style problem from now on."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from nnstreamer_tpu.analysis.racecheck import run_race_lint
    except Exception as exc:  # pragma: no cover - broken tree
        return [f"nns-san --race could not run: {exc}"]
    report = run_race_lint([os.path.join(repo, "nnstreamer_tpu")])
    return [f"race: {d}" for d in report.diagnostics]


def run_xray_self_check() -> list:
    """Run nns-xray --self-check in-process: a chain diagnostic
    (NNS-W120..W125) missing from the catalog, without an emitter, or
    undocumented in docs/chain-analysis.md + docs/linting.md is a style
    problem — as is a doc mentioning a code that doesn't exist."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from nnstreamer_tpu.analysis.selfcheck import xray_self_check
    except Exception as exc:  # pragma: no cover - broken tree
        return [f"nns-xray --self-check could not run: {exc}"]
    return [f"xray: {p}" for p in xray_self_check()]


def run_kscope_self_check() -> list:
    """Run nns-kscope's wiring self-check in-process: a kernel
    diagnostic (NNS-W127..W129) missing from the catalog, without an
    emitter, or undocumented in docs/kernel-analysis.md +
    docs/linting.md is a style problem — as is a public ops/pallas
    kernel without a registered KernelSpec, or a dispatch op outside
    the registry's coverage."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from nnstreamer_tpu.analysis.selfcheck import kscope_self_check
    except Exception as exc:  # pragma: no cover - broken tree
        return [f"nns-kscope --self-check could not run: {exc}"]
    return [f"kscope: {p}" for p in kscope_self_check()]


def run_disagg_self_check() -> list:
    """Run nns-disagg's wiring self-check in-process: the disagg lint
    code (NNS-W130) missing from the catalog, without an emitter, or
    undocumented in docs/linting.md + docs/llm-serving.md is a style
    problem — as is either disagg metric missing from METRIC_CATALOG
    or without a live emitter."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from nnstreamer_tpu.analysis.selfcheck import disagg_self_check
    except Exception as exc:  # pragma: no cover - broken tree
        return [f"nns-disagg --self-check could not run: {exc}"]
    return [f"disagg: {p}" for p in disagg_self_check()]


def documented_pipeline_strings() -> list:
    """(source, description) for every pipeline launch string embedded
    in examples/*.py and docs/*.md — double-quoted launch strings plus
    paragraph-joined blocks, validated by the real tokenizer (the same
    heuristic as the tests' lint-clean sweep)."""
    import ast

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from nnstreamer_tpu.pipeline.parse import ParseError, scan_description

    def pipelineish(text):
        if " ! " not in text:
            return False
        try:
            items = scan_description(text)
        except (ParseError, ValueError):
            return False
        n_elems = sum(1 for it in items if it[0] in ("element", "caps"))
        return n_elems >= 2 and any(it[0] == "bang" for it in items)

    def candidates(text):
        seen = set()
        flat = " ".join(ln.strip().rstrip("\\").strip()
                        for ln in text.splitlines())
        for m in re.finditer(r'"([^"]+ ! [^"]+)"', flat):
            cand = m.group(1).strip()
            if cand not in seen and pipelineish(cand):
                seen.add(cand)
                yield cand
        for para in re.split(r"\n\s*\n", text):
            joined = " ".join(ln.strip().rstrip("\\").strip()
                              for ln in para.strip().splitlines())
            joined = joined.strip().strip('"').replace('\\"', '"')
            if joined not in seen and pipelineish(joined):
                seen.add(joined)
                yield joined

    found = []
    ex_dir = os.path.join(repo, "examples")
    for fn in sorted(os.listdir(ex_dir)):
        if not fn.endswith(".py"):
            continue
        with open(os.path.join(ex_dir, fn)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                for cand in candidates(node.value):
                    found.append((fn, cand))
    doc_dir = os.path.join(repo, "docs")
    for fn in sorted(os.listdir(doc_dir)):
        if not fn.endswith(".md"):
            continue
        with open(os.path.join(doc_dir, fn)) as f:
            for cand in candidates(f.read()):
                found.append((fn, cand))
    return found


def run_xray_docs_gate() -> list:
    """Every pipeline a doc or example shows must xray CLEAN of the
    chain diagnostics: a documented launch string firing W120-W125
    is either a bad example or a false positive — both are gate
    failures (acceptance: zero false chain findings on shipped
    snippets). Unanalyzable pipelines degrade to notes and pass."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from nnstreamer_tpu.analysis.xray import xray
    except Exception as exc:  # pragma: no cover - broken tree
        return [f"nns-xray docs gate could not run: {exc}"]
    chain_codes = {f"NNS-W12{i}" for i in range(6)}
    problems = []
    for src, desc in documented_pipeline_strings():
        result = xray(desc)
        for d in result.diagnostics:
            if d.code in chain_codes:
                problems.append(
                    f"xray-docs: {src}: {desc[:60]!r}: {d.code} "
                    f"[{d.element}]"
                )
    return problems


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    no_self_check = "--no-self-check" in args
    args = [a for a in args if a != "--no-self-check"]
    # explicit path args = quick per-file run: stay stdlib-only; the
    # package-importing self-check rides the whole-tree (gate) run
    whole_tree = not args
    args = args or [
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ]
    problems = []
    for path in iter_files(args):
        problems.extend(check_file(path))
    if whole_tree and not no_self_check:
        problems.extend(run_self_check())
        problems.extend(run_obs_self_check())
        problems.extend(run_race_lint_gate())
        problems.extend(run_xray_self_check())
        problems.extend(run_kscope_self_check())
        problems.extend(run_disagg_self_check())
        problems.extend(run_xray_docs_gate())
    for p in problems:
        print(p)
    if problems:
        print(f"{len(problems)} style problem(s)", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
