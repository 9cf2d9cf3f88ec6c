#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the program's main paths once on the attached TPU,
through the entry points a user calls (``parse_pipeline(...).run()`` and
the element API — the code ``python -m nnstreamer_tpu.cli`` drives), and
checks every result against a reference:

- **vision**: the README quick-start pipeline at full width (MobileNet-v2
  1.0, 224x224, the BASELINE.json config), host source and
  ``videotestsrc device=true``; labels must equal ``SingleShot.invoke``
  on the same frames;
- **postproc**: SSD-MobileNet-v2 300x300 with ``tensor_decoder
  postproc=device`` (on-device NMS) and the ``zoo:face_composite``
  detect -> crop -> landmark program, so the Pallas kernels ``impl=auto``
  selects on a TPU are built by the real compiler on the path that
  selects them; results must match the jnp path on the same frames;
- **llm**: a ``tensor_query_serversrc ! tensor_llm_serversink`` /
  ``tensor_llm_serversrc ! tensor_query_serversink`` server and a
  ``tensor_query_client`` pipeline, paged KV, d_model 2048 / 16 heads of
  128 (depth cut to 4 layers, random weights from a seed), once with XLA
  and once with Pallas attention; greedy tokens must be equal.

``--chips 4`` (the script's only option) runs the paths that exist only
across chips instead — a ``mesh:dp2tp2`` sharded filter against one
device, and per-device stage placement — and no one-chip phase.

It fails (non-zero exit, no result line) when jax finds no TPU, when a
phase raises, when a check fails, or when any pipeline segment degraded
to the host path. The last stdout line is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

#: full-width sizes (module constants so a CPU rehearsal can shrink them)
VISION = dict(frames=64, size=224)
POSTPROC = dict(frames=4, face_size=128)
LLM = dict(
    custom="d_model:2048,n_heads:16,n_layers:4,vocab:32000",
    vocab=32000, prompt_lens=(48, 64, 56, 64), shared_prefix=32,
    new_tokens=32,
)
MESH = dict(mesh="dp2tp2", batch=8, size=224)


class Compiles:
    """Compile seconds and persistent-cache hits/misses, from jax's own
    monitoring events (what the compile cache saw, not a guess from
    wall time)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.secs, self.hits, self.misses)


@contextlib.contextmanager
def float32_contract_precision():
    """The TPU's default rounds float32 matmul and convolution inputs to
    bf16. Where a check is an IDENTITY between two programs — labels
    against single-shot, greedy tokens of two attention kernels — it is
    fair only when both sides are exact: at the default, 2 of 64
    quick-start labels flip between near-tied classes of the
    random-weight model (PERF.md, PR 21). Set process-wide, not as jax's
    thread-local context: the executor's service threads do the tracing."""
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", None)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def assert_healthy(ex, what: str) -> None:
    """No segment may have touched the device-fault ladder: a compile
    failure the circuit would serve from the eager host path is exactly
    the failure this script exists to surface."""
    for node, row in ex.stats().items():
        for key in ("device_degraded", "device_faults",
                    "device_circuit_opens", "device_eager_invokes",
                    "chain_fallback_windows", "oom_events"):
            check(not row.get(key), f"{what}: node {node} reports {key}="
                  f"{row.get(key)} — a segment left the device path")
    check(not ex.errors, f"{what}: executor errors {ex.errors}")


def run_pipeline(desc: str, what: str, timeout: float = 900.0):
    """parse + run + health check; returns (pipeline, executor, sink)."""
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.pipeline.parse import parse_pipeline

    p = parse_pipeline(desc)
    ex = p.run(timeout=timeout)
    assert_healthy(ex, what)
    (sink,) = [e for e in p.elements if isinstance(e, TensorSink)]
    return p, ex, sink


def on_accelerator(arrays, what: str) -> None:
    import jax

    for a in arrays:
        check(isinstance(a, jax.Array), f"{what}: {type(a)} is no jax.Array")
        plats = {d.platform for d in a.devices()}
        check(plats == {jax.default_backend()},
              f"{what}: output array lives on {plats}")


# -- phase: vision ----------------------------------------------------------


@float32_contract_precision()
def phase_vision(frames: int, size: int) -> dict:
    from nnstreamer_tpu.single import SingleShot

    opts = "" if size == 224 else f"size:{size}"  # only a rehearsal shrinks
    src = f"num-frames={frames} width={size} height={size}"
    tail = (
        "tensor_converter ! tensor_filter framework=jax "
        "model=zoo:mobilenet_v2" + (f" custom={opts}" if opts else "") + " ! "
        "tensor_decoder mode=image_labeling ! tensor_sink"
    )
    # the frames the source produced (it is deterministic), for the
    # single-shot reference
    _, _, raw = run_pipeline(
        f"videotestsrc {src} ! tensor_converter ! tensor_sink", "vision/frames"
    )
    check(len(raw.frames) == frames, "vision: frame capture short")
    with SingleShot(
        framework="jax", model="zoo:mobilenet_v2", custom=opts
    ) as single:
        want, ref_logits = [], []
        for f in raw.frames:
            out = single.invoke(f.tensors[0])
            on_accelerator(out, "vision/single-shot")
            logits = np.asarray(out[0]).reshape(-1)
            check(np.all(np.isfinite(logits)), "vision: non-finite logits")
            ref_logits.append(logits)
            want.append(int(np.argmax(logits)))
    out = {}
    for name, source in (
        ("host", f"videotestsrc {src}"),
        ("device", f"videotestsrc device=true {src}"),
    ):
        _, ex, sink = run_pipeline(f"{source} ! {tail}", f"vision/{name}")
        check(len(sink.frames) == frames,
              f"vision/{name}: {len(sink.frames)}/{frames} frames at the sink")
        got = [int(np.asarray(f.tensors[0]).reshape(-1)[0]) for f in sink.frames]
        wrong = [
            (i, g, w, float(ref_logits[i][w] - ref_logits[i][g]))
            for i, (g, w) in enumerate(zip(got, want)) if g != w
        ]
        check(not wrong, f"vision/{name}: labels differ from single-shot on "
              f"{len(wrong)}/{frames} frames (frame, got, want, reference "
              f"logit gap): {wrong}")
        # the labels were fetched from the device by the sink (D2H), not
        # computed on the host
        d2h = ex.totals()["transfer"]["d2h"]
        check(d2h > 0, f"vision/{name}: sink fetched nothing from the device")
        out[name] = {"frames": len(got), "labels_equal_single_shot": True}
    out["distinct_labels"] = len(set(want))
    return out


# -- phase: on-device post-processing -----------------------------------------


def _with_pallas_disabled(fn):
    """Run ``fn`` with every dual-path op forced onto its jnp/XLA
    expression (the registry's own kill switch), dropping jit caches on
    both sides so each run traces — and dispatches — afresh."""
    import jax

    from nnstreamer_tpu.ops.pallas._compat import DISABLE_ENV

    jax.clear_caches()
    os.environ[DISABLE_ENV] = "1"
    try:
        return fn()
    finally:
        del os.environ[DISABLE_ENV]
        jax.clear_caches()


def phase_postproc(frames: int, face_size: int) -> dict:
    import jax

    from nnstreamer_tpu.elements.decoder import TensorDecoder
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.sources import AppSrc
    from nnstreamer_tpu.models import ssd_mobilenet
    from nnstreamer_tpu.ops import dispatch
    from nnstreamer_tpu.pipeline.graph import Pipeline
    from nnstreamer_tpu.pipeline.parse import parse_pipeline

    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        priors = os.path.join(tmp, "box_priors.txt")
        ssd_mobilenet.write_box_priors(priors)
        # a low threshold so NMS has a crowd to suppress, not an empty
        # list; the tee keeps the model's raw outputs for the reference
        box_decoder = dict(mode="bounding_boxes", option1="mobilenet-ssd",
                           option3=f"{priors}:0.02")
        ssd = parse_pipeline(
            f"videotestsrc num-frames={frames} width=300 height=300 ! "
            "tensor_converter ! "
            "tensor_filter framework=jax model=zoo:ssd_mobilenet_v2 ! "
            "tee name=t "
            "t. ! queue ! tensor_decoder postproc=device "
            + " ".join(f"{k}={v}" for k, v in box_decoder.items())
            + " ! tensor_sink name=det "
            "t. ! queue ! tensor_sink name=raw"
        )
        face = (
            f"videotestsrc pattern=gradient num-frames={frames} "
            f"width={face_size} height={face_size} ! tensor_converter ! "
            "tensor_filter framework=jax model=zoo:face_composite "
            'custom="threshold:0.0" ! tensor_sink'
        )
        since = dispatch.tally.snapshot()
        assert_healthy(ssd.run(timeout=900), "postproc/ssd")
        _, _, face_sink = run_pipeline(face, "postproc/face")
        want_impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
        for op in ("nms", "crop_and_resize"):
            engaged = dispatch.engaged_impls(op, since)
            check(engaged == [want_impl], f"postproc: {op} dispatched to "
                  f"{engaged}, expected [{want_impl!r}]")
            out[f"tally_{op}"] = engaged

        # the references: the SAME raw model outputs through the host
        # decoder, and the same frames through the face cascade, with
        # every dual-path op on its jnp expression
        def references():
            ref_det = TensorSink()
            raw = ssd["raw"].frames
            Pipeline().chain(
                AppSrc(iterable=[f.tensors for f in raw], spec=raw[0].spec()),
                TensorDecoder(postproc="host", **box_decoder),
                ref_det,
            ).run(timeout=900)
            return ref_det, run_pipeline(face, "postproc/face/jnp")[2]

        ref_det, ref_face = _with_pallas_disabled(references)
    # NMS is pinned bit-comparable with its jnp reference (its registry
    # tolerance is 0.0): the SAME rows must be kept, in the same order.
    # The box decode around it is compiled in two fusion contexts (fused
    # segment here, its own program in the host decoder), so the
    # coordinates themselves may differ in the last float32 digits.
    kept = 0
    check(len(ssd["det"].frames) == frames == len(ref_det.frames),
          "postproc/ssd: frames short")
    for got, ref in zip(ssd["det"].frames, ref_det.frames):
        rows = np.asarray(got.tensors[0])
        check(rows.shape == (100, 6), f"postproc/ssd: shape {rows.shape}")
        check(np.all(np.isfinite(rows)), "postproc/ssd: non-finite")
        rows, want = rows[rows[:, 5] > 0], ref.meta["detections"]
        check(rows.shape == want.shape and np.array_equal(
            rows[:, 4], want[:, 4]), "postproc/ssd: NMS kept other rows "
            f"than the jnp path: classes {rows[:, 4]} vs {want[:, 4]}")
        np.testing.assert_allclose(rows, want, rtol=0, atol=1e-5)
        kept += len(rows)
    check(kept > 0, "postproc/ssd: NMS kept no detection — nothing checked")
    out["ssd_detections_kept"] = kept
    # face cascade: the detections come before the crop, the landmarks
    # after it — the crop kernel's registered tolerance carried through
    # the landmark net's default-precision convolutions
    check(len(face_sink.frames) == frames == len(ref_face.frames),
          "postproc/face: frames short")
    diff = 0.0
    for got, ref in zip(face_sink.frames, ref_face.frames):
        (lmk, det), (ref_lmk, ref_det_rows) = (
            [np.asarray(t) for t in f.tensors] for f in (got, ref)
        )
        check(np.all(np.isfinite(lmk)) and np.all(np.isfinite(det)),
              "postproc/face: non-finite")
        np.testing.assert_allclose(det, ref_det_rows, rtol=0, atol=1e-3)
        np.testing.assert_allclose(lmk, ref_lmk, rtol=0, atol=2e-2)
        diff = max(diff, float(np.abs(lmk - ref_lmk).max()))
    out["face_landmarks_max_abs_diff"] = diff
    return out


# -- phase: LLM serving ---------------------------------------------------------


def _serve(attn_impl: str, prompts, custom: str, new_tokens: int) -> list:
    """One server pipeline + one client pipeline in this process; returns
    the generated token arrays in request order."""
    from nnstreamer_tpu.edge.query import TensorQueryClient
    from nnstreamer_tpu.elements.sink import TensorSink
    from nnstreamer_tpu.elements.sources import AppSrc
    from nnstreamer_tpu.pipeline.graph import Pipeline
    from nnstreamer_tpu.pipeline.parse import parse_pipeline
    from nnstreamer_tpu.tensors.spec import TensorFormat, TensorsSpec

    sid = f"smoke-{attn_impl}"
    server = parse_pipeline(
        f"tensor_query_serversrc name=qsrc port=0 id={sid}q ! "
        f'tensor_llm_serversink id={sid} model=zoo:transformer_lm '
        f'custom="{custom}" kv-layout=paged attn-impl={attn_impl} pump=8 '
        f"n-slots=4 max-len=128 prompt-len=64 max-new-tokens={new_tokens} "
        f"tensor_llm_serversrc id={sid} ! tensor_query_serversink id={sid}q"
    )
    sex = server.start()
    try:
        port = server["qsrc"].bound_port  # port=0: the one the OS gave
        sink = TensorSink()
        client = Pipeline().chain(
            AppSrc(
                iterable=[(p[None, :],) for p in prompts],
                spec=TensorsSpec(format=TensorFormat.FLEXIBLE),
            ),
            TensorQueryClient(**{"dest-port": port, "timeout": 900}),
            sink,
        )
        cex = client.run(timeout=1500)
        assert_healthy(cex, f"llm/{attn_impl}/client")
        check(not sex.errors, f"llm/{attn_impl}: server errors {sex.errors}")
        replies = [np.asarray(f.tensors[0]).reshape(-1) for f in sink.frames]
    finally:
        sex.stop()
    return replies


@float32_contract_precision()
def phase_llm(custom: str, vocab: int, prompt_lens, shared_prefix: int,
              new_tokens: int) -> dict:
    from nnstreamer_tpu.obs import metrics
    from nnstreamer_tpu.ops import dispatch

    rng = np.random.default_rng(0)
    prefix = rng.integers(1, vocab, (shared_prefix,))
    prompts = []
    for i, n in enumerate(prompt_lens):
        p = rng.integers(1, vocab, (n,))
        if i in (1, 3):  # two requests open with the same system prompt
            p[:shared_prefix] = prefix
        prompts.append(p.astype(np.int32))
    reg = metrics.enable()
    out = {}
    tokens = {}
    for impl in ("xla", "pallas"):
        since = dispatch.tally.snapshot()
        hits0 = getattr(reg.find("nns_kv_prefix_hits_total"), "value", 0)
        replies = _serve(impl, prompts, custom, new_tokens)
        check(len(replies) == len(prompts),
              f"llm/{impl}: {len(replies)}/{len(prompts)} replies")
        for r in replies:
            check(r.shape == (new_tokens,) and r.min() >= 0
                  and r.max() < vocab, f"llm/{impl}: bad reply {r!r}")
        engaged = dispatch.engaged_impls("serving_attention", since)
        check(engaged == [impl],
              f"llm/{impl}: serving_attention tally {engaged}")
        hits = reg.find("nns_kv_prefix_hits_total").value - hits0
        check(hits > 0, f"llm/{impl}: nns_kv_prefix_hits_total stayed 0")
        tokens[impl] = replies
        out[impl] = {"replies": len(replies), "prefix_hits": int(hits),
                     "tally": engaged}
    for a, b in zip(tokens["xla"], tokens["pallas"]):
        check(np.array_equal(a, b),
              f"llm: greedy tokens differ, xla {a.tolist()} vs pallas "
              f"{b.tolist()}")
    out["greedy_tokens_equal"] = True
    return out


# -- four chips (--chips 4) -------------------------------------------------------


def phase_mesh(mesh: str, batch: int, size: int) -> dict:
    """``zoo:vit`` sharded over four devices (``mesh:dp2tp2``) against
    the same model on one device, same frames."""
    import jax

    from nnstreamer_tpu.single import SingleShot

    rng = np.random.default_rng(0)
    x = rng.integers(0, 255, (batch, size, size, 3), np.uint8)
    custom = f"batch:{batch}"
    if size != 224:  # only a rehearsal shrinks
        custom += f",size:{size},d_model:96,n_layers:2"
    with SingleShot(
        framework="jax", model="zoo:vit", custom=custom + ",device:0"
    ) as one:
        (want,) = one.invoke(x)
        want = np.asarray(want)
    with SingleShot(
        framework="jax", model="zoo:vit", custom=f"{custom},mesh:{mesh}"
    ) as sharded:
        (got,) = sharded.invoke(x)
        backend = sharded.backend
        spread = set()
        n_sharded = 0
        for leaf in jax.tree_util.tree_leaves(backend._placed_params):
            devs = {s.device.id for s in leaf.addressable_shards}
            spread |= devs
            shard_shapes = {s.data.shape for s in leaf.addressable_shards}
            n_sharded += int(shard_shapes != {leaf.shape})
        check(len(spread) == 4,
              f"mesh: weights live on devices {sorted(spread)}, not 4")
        check(n_sharded > 0, "mesh: no weight is split — all replicated")
        check(len({d.id for d in got.devices()}) == 4,
              "mesh: output not spread over 4 devices")
        got = np.asarray(got)
    check(np.all(np.isfinite(got)), "mesh: non-finite logits")
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-2)
    return {"weight_devices": sorted(spread), "weights_split": n_sharded,
            "max_abs_diff_vs_one_device": float(np.abs(got - want).max())}


#: BASELINE config #5 (tests/test_composite_face.py): the face detector
#: feeds bounding regions to tensor_crop, crops stream to the landmark
#: model — here with each model pinned to its own chip
COMPOSITE = (
    "videotestsrc pattern=gradient num-frames={n} width=128 height=128 ! "
    "tensor_converter ! tee name=t "
    "t. ! queue ! tensor_filter framework=jax model=zoo:face_detect "
    'custom="output:regions,threshold:0.0,frame_size:128:128{det_dev}" ! '
    "crop.sink_1 "
    "t. ! queue ! crop.sink_0 "
    "tensor_crop name=crop ! "
    "tensor_filter framework=jax model=zoo:face_landmark "
    'custom="{lmk_dev}" invoke-dynamic=true input-combination=0 ! '
    "tensor_sink name=out"
)


def phase_placement(frames: int) -> dict:
    """Detector on device 0, landmark model on device 1: the crops hop
    chips by device transfer. Placement is a scheduling choice, not a
    numeric one — the landmarks must equal the unpinned run's."""
    import jax

    from nnstreamer_tpu.elements.filter import TensorFilter
    from nnstreamer_tpu.pipeline.parse import parse_pipeline

    _, _, ref = run_pipeline(
        COMPOSITE.format(n=frames, det_dev="", lmk_dev=""), "placement/unpinned"
    )
    p = parse_pipeline(
        COMPOSITE.format(n=frames, det_dev=",device:0", lmk_dev="device:1")
    )
    ex = p.start()
    try:
        check(ex.wait(900), "placement: pipeline did not reach EOS")
        # where each stage's weights live, read while the backends are open
        homes = []
        for e in p.elements:
            if isinstance(e, TensorFilter):
                leaves = jax.tree_util.tree_leaves(e.backend._placed_params)
                homes.append(sorted({d.id for x in leaves for d in x.devices()}))
    finally:
        ex.stop()
    assert_healthy(ex, "placement/pinned")
    devs = jax.devices()
    check(homes == [[devs[0].id], [devs[1].id]],
          f"placement: stage weights live on devices {homes}")
    got = [np.asarray(f.tensors[0]) for f in p["out"].frames]
    want = [np.asarray(f.tensors[0]) for f in ref.frames]
    check(len(got) == frames == len(want), "placement: frames short")
    for a, b in zip(got, want):
        check(np.all(np.isfinite(a)), "placement: non-finite landmarks")
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
    return {"stage_weight_devices": homes, "frames": frames}


# -- driver -----------------------------------------------------------------------


def run_phases(phases, comp: Compiles) -> dict:
    import jax

    report = {}
    dev = jax.devices()[0]
    for name, fn, kw in phases:
        c0 = comp.snapshot()
        t0 = time.perf_counter()
        result = fn(**kw)
        wall = time.perf_counter() - t0
        c1 = comp.snapshot()
        stats = dev.memory_stats() or {}
        row = {
            "phase": name,
            "compile_seconds": round(c1[0] - c0[0], 2),
            "wall_seconds": round(wall, 2),
            "compile_cache_hits": c1[1] - c0[1],
            "compile_cache_misses": c1[2] - c0[2],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            **result,
        }
        print(json.dumps(row), flush=True)
        report[name] = row
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(4,), default=None,
                    help="run the four-chip paths (and no one-chip phase)")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if jax.default_backend() != "tpu" or dev.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (default backend "
              f"{jax.default_backend()!r}); nothing run", file=sys.stderr)
        return 1
    if args.chips and len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax has {len(devices)}", file=sys.stderr)
        return 1

    comp = Compiles()
    from nnstreamer_tpu.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jaxlib

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_version = "not importable"
    print(json.dumps({
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version, "device_kind": dev.device_kind,
        "devices": len(devices),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "JAX_COMPILATION_CACHE_DIR": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    }), flush=True)

    if args.chips:
        phases = [
            ("mesh", phase_mesh, MESH),
            ("placement", phase_placement, dict(frames=4)),
        ]
    else:
        phases = [
            ("vision", phase_vision, VISION),
            ("postproc", phase_postproc, POSTPROC),
            ("llm", phase_llm, LLM),
        ]
    t0 = time.perf_counter()
    run_phases(phases, comp)
    print(json.dumps({
        "total_wall_seconds": round(time.perf_counter() - t0, 2),
        "total_compile_seconds": round(comp.secs, 2),
        "compile_cache_hits": comp.hits,
        "compile_cache_misses": comp.misses,
    }), flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
