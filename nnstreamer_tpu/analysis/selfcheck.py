"""nns-lint --self-check: the PROPERTIES schemas must cover the code.

Every registered builtin element reads its configuration through
``get_property("...")`` / ``props.pop("...")``; this check scans each
element class's source for those literals and fails if any read property
is missing from the class's merged ``PROPERTIES`` schema. The style gate
(tools/check_style.py, tests/test_style.py) runs it, so a new element (or
a new property on an old one) cannot land without schema coverage — the
same role as the reference's gst-inspect property introspection staying
in sync with the GObject param specs by construction.
"""

from __future__ import annotations

import inspect
import os
import re
from typing import Dict, List, Set

from nnstreamer_tpu import registry
from nnstreamer_tpu.elements.base import PROPS_ANY

_PROP_READ = re.compile(r"""(?:get_property|props\.pop)\(\s*["']([^"']+)["']""")

# Properties consumed positionally/indirectly that the scan cannot see but
# the schema intentionally documents anyway — nothing to do for these.


def scan_class_properties(cls: type) -> Set[str]:
    """Property names the class source reads (dash-normalized). Walks the
    MRO so inherited reads (base Element, Sink) are attributed too."""
    names: Set[str] = set()
    for klass in cls.__mro__:
        if klass is object:
            continue
        try:
            src = inspect.getsource(klass)
        except (OSError, TypeError):  # pragma: no cover - builtins only
            continue
        for m in _PROP_READ.finditer(src):
            names.add(m.group(1).replace("_", "-"))
    return names


def self_check() -> List[str]:
    """Return a list of problems (empty = all schemas cover their code)."""
    problems: List[str] = []
    seen: Dict[type, str] = {}
    for name in registry.available(registry.KIND_ELEMENT):
        try:
            cls = registry.get(registry.KIND_ELEMENT, name)
        except KeyError:  # restricted by runtime config
            continue
        if cls in seen:  # aliases (videotestsrc/testsrc) check once
            continue
        seen[cls] = name
        schema = cls.property_schema()
        if PROPS_ANY in schema:
            continue
        for prop in sorted(scan_class_properties(cls)):
            if prop not in schema:
                problems.append(
                    f"{name} ({cls.__module__}.{cls.__name__}): property "
                    f"{prop!r} is read by the code but missing from "
                    "PROPERTIES"
                )
    return problems


# -- nns-san --self-check: the diagnostic catalog must cover the code -------

_CODE_REF = re.compile(r"""["'](NNS-[EWRS]\d{3})["']""")


def _emitted_codes() -> Set[str]:
    """Every diagnostic code referenced by an analyzer/sanitizer module
    (the emitters; the catalog module itself doesn't count)."""
    import importlib

    out: Set[str] = set()
    for name in (
        # importlib (not `import a.b as m`): analysis.__init__ re-binds
        # `lint` to the function, and the as-import would grab that
        "nnstreamer_tpu.analysis.kernels",
        "nnstreamer_tpu.analysis.lint",
        "nnstreamer_tpu.analysis.racecheck",
        "nnstreamer_tpu.analysis.xray",
        "nnstreamer_tpu.pipeline.sanitize",
    ):
        mod = importlib.import_module(name)
        out |= set(_CODE_REF.findall(inspect.getsource(mod)))
    return out


def san_self_check() -> List[str]:
    """Validate the diagnostic catalog against the code (the nns-san
    mirror of the element-schema self-check): every code an analyzer can
    emit exists in the catalog, every catalog code has an emitter, slugs
    are unique, severities match the E/W prefix convention, and the
    sanitizer doc covers the nns-san codes."""
    import os

    from nnstreamer_tpu.analysis.diagnostics import CATALOG, Severity

    problems: List[str] = []
    emitted = _emitted_codes()
    for code in sorted(emitted - set(CATALOG)):
        problems.append(f"code {code} is emitted but not in the catalog")
    for code in sorted(set(CATALOG) - emitted):
        problems.append(f"catalog code {code} has no emitter in the code")
    slugs: Dict[str, str] = {}
    for code, (sev, slug, _desc) in CATALOG.items():
        if slug in slugs:
            problems.append(
                f"slug {slug!r} used by both {slugs[slug]} and {code}"
            )
        slugs[slug] = code
        if code.startswith("NNS-E") and sev is not Severity.ERROR:
            problems.append(f"{code} has an E prefix but severity {sev}")
        if code.startswith("NNS-W") and sev is not Severity.WARNING:
            problems.append(f"{code} has a W prefix but severity {sev}")
    doc = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        "docs", "sanitizer.md",
    )
    if os.path.isfile(doc):  # repo checkouts only; wheels ship no docs
        with open(doc, encoding="utf-8") as f:
            text = f.read()
        for code in sorted(CATALOG):
            if code.startswith(("NNS-R", "NNS-S")) and code not in text:
                problems.append(
                    f"{code} is not documented in docs/sanitizer.md"
                )
    return problems


# -- nns-obs self-check: the metric catalog must cover the code -------------

_METRIC_EMIT = re.compile(
    r"""(?:counter|gauge|histogram)\(\s*\n?\s*["'](nns_[a-z0-9_]+)["']"""
)


def _repo_root() -> str:
    import os

    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


def _package_sources(skip: str):
    """Text of every ``.py`` file of the package but ``skip`` (a catalog
    module does not count as an emitter of its own names)."""
    import os

    pkg_root = os.path.join(_repo_root(), "nnstreamer_tpu")
    skip = os.path.join(pkg_root, skip)
    for dirpath, dirnames, filenames in os.walk(pkg_root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            path = os.path.join(dirpath, fn)
            if fn.endswith(".py") and not os.path.samefile(path, skip):
                with open(path, encoding="utf-8") as f:
                    yield f.read()


def _catalog_problems(kind: str, catalog, emitted: Set[str],
                      catalog_name: str) -> List[str]:
    """Emitters ⟷ catalog ⟷ docs/observability.md, for one kind of name."""
    problems = [
        f"{kind} {name} is emitted but not in {catalog_name}"
        for name in sorted(emitted - set(catalog))
    ] + [
        f"catalog {kind} {name} has no emitter in the package"
        for name in sorted(set(catalog) - emitted)
    ]
    doc = os.path.join(_repo_root(), "docs", "observability.md")
    if os.path.isfile(doc):  # repo checkouts only; wheels ship no docs
        with open(doc, encoding="utf-8") as f:
            text = f.read()
        problems += [
            f"{kind} {name} is not documented in docs/observability.md"
            for name in sorted(catalog) if f"`{name}`" not in text
        ]
    return problems


def obs_self_check() -> List[str]:
    """Validate the nns-obs metric catalog against the code and the docs
    (the metrics mirror of san_self_check): every metric name the
    package emits through a registry call exists in METRIC_CATALOG,
    every cataloged metric has an emitter, and docs/observability.md
    documents every cataloged name."""
    from nnstreamer_tpu.obs.metrics import METRIC_CATALOG

    emitted: Set[str] = set()
    for text in _package_sources(os.path.join("obs", "metrics.py")):
        emitted |= set(_METRIC_EMIT.findall(text))
    return _catalog_problems("metric", METRIC_CATALOG, emitted,
                             "METRIC_CATALOG")


_SPAN_EMIT = re.compile(
    r"""trace\.(?:span|instant)\(\s*["'](nns\.[a-z0-9_.]+)["']"""
)


def span_self_check() -> List[str]:
    """The same three-way check for the program's spans: every ``nns.*``
    literal passed to ``trace.span`` / ``trace.instant`` is in
    trace.SPAN_CATALOG, every cataloged name has an emitter, and
    docs/observability.md documents every one. The names are what the
    benchmark's readers select events by, so a rename must show here."""
    from nnstreamer_tpu.trace import SPAN_CATALOG

    emitted: Set[str] = set()
    for text in _package_sources("trace.py"):
        emitted |= set(_SPAN_EMIT.findall(text))
    return _catalog_problems("span", SPAN_CATALOG, emitted, "SPAN_CATALOG")


# -- nns-xray self-check: chain codes wired emitters<->catalog<->docs -------

_XRAY_CODES = (
    "NNS-W120", "NNS-W121", "NNS-W122", "NNS-W123", "NNS-W124",
    "NNS-W125",
)


def xray_self_check() -> List[str]:
    """Validate the chain-analysis diagnostics both ways: every
    W120-W125 code is in the catalog, has an emitter in
    analysis/xray.py, and is documented in docs/chain-analysis.md AND
    docs/linting.md; conversely every NNS code docs/chain-analysis.md
    mentions exists in the catalog (no doc drift either direction)."""
    import importlib
    import os

    from nnstreamer_tpu.analysis.diagnostics import CATALOG

    problems: List[str] = []
    mod = importlib.import_module("nnstreamer_tpu.analysis.xray")
    emitted = set(_CODE_REF.findall(inspect.getsource(mod)))
    for code in _XRAY_CODES:
        if code not in CATALOG:
            problems.append(f"chain code {code} missing from the catalog")
        if code not in emitted:
            problems.append(
                f"chain code {code} has no emitter in analysis/xray.py"
            )
    for doc_name in ("chain-analysis.md", "linting.md"):
        doc = os.path.join(_repo_root(), "docs", doc_name)
        if not os.path.isfile(doc):  # repo checkouts only
            continue
        with open(doc, encoding="utf-8") as f:
            text = f.read()
        for code in _XRAY_CODES:
            if code not in text:
                problems.append(
                    f"{code} is not documented in docs/{doc_name}"
                )
        if doc_name == "chain-analysis.md":
            for code in sorted(set(_CODE_REF.findall(text))):
                if code not in CATALOG:
                    problems.append(
                        f"docs/chain-analysis.md mentions unknown code "
                        f"{code}"
                    )
    return problems


# -- nns-kscope self-check: kernel codes + registry wired both ways ---------

_KSCOPE_CODES = ("NNS-W127", "NNS-W128", "NNS-W129")


def kscope_self_check() -> List[str]:
    """Validate the kernel-analysis wiring both ways: every W127-W129
    code is in the catalog, has an emitter in analysis/kernels.py, and
    is documented in docs/kernel-analysis.md AND docs/linting.md;
    every NNS code docs/kernel-analysis.md mentions exists in the
    catalog; every public kernel entry point in ops/pallas has a
    registered KernelSpec of the same name (and vice versa); and the
    union of registered dispatch ops equals ops/dispatch.KNOWN_OPS (a
    dispatch site cannot appear without --engage coverage)."""
    import importlib
    import os

    from nnstreamer_tpu.analysis.diagnostics import CATALOG

    problems: List[str] = []
    mod = importlib.import_module("nnstreamer_tpu.analysis.kernels")
    emitted = set(_CODE_REF.findall(inspect.getsource(mod)))
    for code in _KSCOPE_CODES:
        if code not in CATALOG:
            problems.append(f"kernel code {code} missing from the catalog")
        if code not in emitted:
            problems.append(
                f"kernel code {code} has no emitter in analysis/kernels.py"
            )
    for doc_name in ("kernel-analysis.md", "linting.md"):
        doc = os.path.join(_repo_root(), "docs", doc_name)
        if not os.path.isfile(doc):  # repo checkouts only
            continue
        with open(doc, encoding="utf-8") as f:
            text = f.read()
        for code in _KSCOPE_CODES:
            if code not in text:
                problems.append(
                    f"{code} is not documented in docs/{doc_name}"
                )
        if doc_name == "kernel-analysis.md":
            for code in sorted(set(_CODE_REF.findall(text))):
                if code not in CATALOG:
                    problems.append(
                        f"docs/kernel-analysis.md mentions unknown code "
                        f"{code}"
                    )
    # registry completeness: public kernel entry points <-> KernelSpecs
    import nnstreamer_tpu.ops.pallas as pallas_pkg
    from nnstreamer_tpu.ops import dispatch
    from nnstreamer_tpu.ops.pallas import registry as kreg

    public = {
        name for name, obj in vars(pallas_pkg).items()
        # callable, not isfunction: the entry points are jax.jit-wrapped
        if not name.startswith("_") and callable(obj)
        and not inspect.ismodule(obj)
        and getattr(obj, "__module__", "").startswith(
            "nnstreamer_tpu.ops.pallas.")
        and not getattr(obj, "__name__", "").endswith("_ref")
    }
    registered = set(kreg.names())
    for name in sorted(public - registered):
        problems.append(
            f"ops/pallas exports kernel {name!r} with no registered "
            "KernelSpec (nns-kscope cannot analyze it)"
        )
    for name in sorted(registered - public):
        problems.append(
            f"KernelSpec {name!r} is registered but ops/pallas exports "
            "no kernel of that name"
        )
    covered = set()
    for spec in kreg.all_specs():
        covered |= set(spec.ops)
    for op in sorted(set(dispatch.KNOWN_OPS) - covered):
        problems.append(
            f"dispatch op {op!r} is in KNOWN_OPS but no KernelSpec "
            "covers it (--engage cannot prove it)"
        )
    for op in sorted(covered - set(dispatch.KNOWN_OPS)):
        problems.append(
            f"KernelSpec op {op!r} is not in ops/dispatch.KNOWN_OPS"
        )
    return problems


# -- nns-disagg self-check: disagg codes + metrics wired both ways ----------

_DISAGG_CODES = ("NNS-W130",)


def disagg_self_check() -> List[str]:
    """Validate the disaggregated-serving wiring both ways: every
    disagg lint code is in the catalog, has an emitter in
    analysis/lint.py, and is documented in docs/linting.md AND
    docs/llm-serving.md; and both disagg metrics
    (``nns_disagg_handoffs_total``, ``nns_route_prefix_hits_total``)
    are in the METRIC_CATALOG with a live emitter in the serving/edge
    code — a renamed counter cannot silently fall out of the docs."""
    import importlib
    import os

    from nnstreamer_tpu.analysis.diagnostics import CATALOG

    problems: List[str] = []
    mod = importlib.import_module("nnstreamer_tpu.analysis.lint")
    emitted = set(_CODE_REF.findall(inspect.getsource(mod)))
    for code in _DISAGG_CODES:
        if code not in CATALOG:
            problems.append(f"disagg code {code} missing from the catalog")
        if code not in emitted:
            problems.append(
                f"disagg code {code} has no emitter in analysis/lint.py"
            )
    for doc_name in ("linting.md", "llm-serving.md"):
        doc = os.path.join(_repo_root(), "docs", doc_name)
        if not os.path.isfile(doc):  # repo checkouts only
            continue
        with open(doc, encoding="utf-8") as f:
            text = f.read()
        for code in _DISAGG_CODES:
            if code not in text:
                problems.append(
                    f"{code} is not documented in docs/{doc_name}"
                )
    from nnstreamer_tpu.obs.metrics import METRIC_CATALOG

    wanted = {
        "nns_disagg_handoffs_total": "nnstreamer_tpu.serving_plane.disagg",
        "nns_route_prefix_hits_total": "nnstreamer_tpu.edge.query",
    }
    for metric, mod_name in wanted.items():
        if metric not in METRIC_CATALOG:
            problems.append(
                f"disagg metric {metric} missing from METRIC_CATALOG"
            )
        src = inspect.getsource(importlib.import_module(mod_name))
        if f'"{metric}"' not in src and f"'{metric}'" not in src:
            problems.append(
                f"disagg metric {metric} has no emitter in {mod_name}"
            )
    return problems


def main(argv=None) -> int:  # pragma: no cover - thin wrapper
    problems = self_check()
    for p in problems:
        print(p)
    if problems:
        print(f"{len(problems)} schema gap(s)")
        return 1
    print("all element PROPERTIES schemas cover their code")
    return 0
