"""Built-in model zoo: named (fn, params, spec) bundles for framework=jax.

The analogue of the reference's tests/test_models/models/ fixture set
(add.tflite, mobilenet_v2_..., deeplabv3_...), but as constructively seeded
jax models: ``model=zoo:<name>`` always works offline with deterministic
params (seed via custom option ``seed:N``). Weight files can be layered in
via ``params:<path.npz>``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.tensors.spec import DType, TensorSpec, TensorsSpec


@dataclass
class ZooModel:
    name: str
    fn: Callable  # (*tensors) -> tensor | tuple, pure & traceable
    input_spec: Optional[TensorsSpec]
    params: Optional[Dict] = None
    # params-explicit form ``apply(params, *tensors)``: required for mesh-
    # sharded filters (custom="mesh:dp2tp4") — closed-over params would be
    # baked into the jaxpr as replicated constants, defeating TP sharding
    apply: Optional[Callable] = None
    # the block family tensor_llm_serversink's paged batcher serves the
    # model through (models/family.py); None is the dense transformer block
    family: Optional[object] = None


_FACTORIES: Dict[str, Callable[..., ZooModel]] = {}


def model_factory(name: str):
    def deco(fn):
        _FACTORIES[name] = fn
        return fn

    return deco


def get(name: str, **options: str) -> ZooModel:
    if name not in _FACTORIES:
        raise KeyError(f"unknown zoo model {name!r}; known: {sorted(_FACTORIES)}")
    return _FACTORIES[name](**options)


def available():
    return sorted(_FACTORIES)


def _load_params_overlay(params, options):
    path = options.get("params")
    if not path:
        return params
    blob = np.load(path, allow_pickle=True)
    flat = {k: jnp.asarray(v) for k, v in blob.items()}
    leaves, treedef = jax.tree_util.tree_flatten(params)
    new_leaves = [flat[f"p{i}"] if f"p{i}" in flat else l for i, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


@model_factory("add")
def _add(**options) -> ZooModel:
    """y = x + const (the reference's add.tflite test model)."""
    const = float(options.get("const", 2.0))
    dims = options.get("dims", "1")
    spec = TensorsSpec.of(TensorSpec.from_dim_string(dims, "float32"))

    def fn(x):
        return x + jnp.asarray(const, x.dtype)

    return ZooModel("add", fn, spec)


@model_factory("mobilenet_v2")
def _mobilenet_v2(**options) -> ZooModel:
    from nnstreamer_tpu.models import mobilenet_v2

    seed = int(options.get("seed", 0))
    num_classes = int(options.get("num_classes", 1001))
    width = float(options.get("width", 1.0))
    batch = int(options.get("batch", 1))
    size = int(options.get("size", 224))
    compute_dtype = _compute_dtype(options)
    params = mobilenet_v2.init_params(
        jax.random.PRNGKey(seed), num_classes=num_classes, width=width
    )
    params = _load_params_overlay(params, options)

    if options.get("quantize") == "int8w":
        # weight-only int8 with the fused on-device dequant epilogue
        # (models/quantize.py apply_int8w): int8 weights + per-channel
        # scales device-resident, dequantized at the matmul operand
        # inside the segment; no calibration pass, no per-activation
        # quant math — the winning int8 configuration
        # (docs/on-device-ops.md)
        from nnstreamer_tpu.models import quantize as qz

        qparams = qz.quantize_mobilenet_weights(qz.fold_mobilenet(params))

        def qw_apply(p, image):
            return qz.apply_int8w(p, image, compute_dtype=compute_dtype)

        def qw_fn(image):
            return qw_apply(qparams, image)

        spec = _image_spec(batch, size, options.get("input_dtype", "uint8"))
        return ZooModel("mobilenet_v2", qw_fn, spec, qparams, qw_apply)

    if options.get("quantize") == "int8":
        # the reference's *_quant.tflite slot, redesigned for the MXU's
        # s8×s8→s32 path (models/quantize.py): fold BN, calibrate
        # activation scales on seeded sample batches, serve int8
        from nnstreamer_tpu.models import quantize as qz

        folded = qz.fold_mobilenet(params)
        rng = np.random.default_rng(seed)
        calib = [
            jnp.asarray(rng.integers(0, 256, (batch, size, size, 3), np.uint8))
            for _ in range(int(options.get("calib_batches", 2)))
        ]
        qparams = qz.quantize_mobilenet(
            folded, qz.calibrate_mobilenet(folded, calib)
        )
        def q_apply(p, image):
            return qz.apply_int8(p, image, compute_dtype=compute_dtype)

        def q_fn(image):
            return q_apply(qparams, image)

        spec = _image_spec(batch, size, options.get("input_dtype", "uint8"))
        return ZooModel("mobilenet_v2", q_fn, spec, qparams, q_apply)

    def apply_fn(p, image):
        return mobilenet_v2.apply(p, image, compute_dtype=compute_dtype)

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(batch, size, options.get("input_dtype", "uint8"))
    return ZooModel("mobilenet_v2", fn, spec, params, apply_fn)


def _image_spec(batch: int, size: int, in_dtype: str) -> TensorsSpec:
    return TensorsSpec.of(
        TensorSpec((batch, size, size, 3), DType.from_any(in_dtype), name="image")
    )


def _compute_dtype(options) -> "jnp.dtype":
    compute = options.get("compute_dtype", "float32")
    return jnp.bfloat16 if compute == "bfloat16" else jnp.dtype(compute)


@model_factory("ssd_mobilenet_v2")
def _ssd_mobilenet_v2(**options) -> ZooModel:
    """Raw 2-tensor SSD (locations + class logits) for decoder
    mode=mobilenet-ssd; the analogue of ssd_mobilenet_v2_coco.tflite."""
    from nnstreamer_tpu.models import ssd_mobilenet

    seed = int(options.get("seed", 0))
    batch = int(options.get("batch", 1))
    num_classes = int(options.get("num_classes", ssd_mobilenet.NUM_CLASSES))
    dtype = _compute_dtype(options)
    params = _load_params_overlay(
        ssd_mobilenet.init_params(jax.random.PRNGKey(seed), num_classes), options
    )

    def apply_fn(p, image):
        return ssd_mobilenet.apply(
            p, image, compute_dtype=dtype, num_classes=num_classes
        )

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(batch, 300, options.get("input_dtype", "uint8"))
    return ZooModel("ssd_mobilenet_v2", fn, spec, params, apply_fn)


@model_factory("ssd_mobilenet_v2_pp")
def _ssd_mobilenet_v2_pp(**options) -> ZooModel:
    """SSD + on-device NMS → the TFLite detection-postprocess 4-tensor
    layout (decoder mode=mobilenet-ssd-postprocess). Batch-1."""
    from nnstreamer_tpu.models import ssd_mobilenet

    seed = int(options.get("seed", 0))
    max_out = int(options.get("max_out", 10))
    threshold = float(options.get("threshold", 0.001))
    dtype = _compute_dtype(options)
    params = _load_params_overlay(
        ssd_mobilenet.init_params(jax.random.PRNGKey(seed)), options
    )
    priors = jnp.asarray(ssd_mobilenet.generate_anchors())

    def apply_fn(p, image):
        return ssd_mobilenet.apply_postprocessed(
            p, image, priors, max_out=max_out, threshold=threshold,
            compute_dtype=dtype,
        )

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(1, 300, options.get("input_dtype", "uint8"))
    return ZooModel("ssd_mobilenet_v2_pp", fn, spec, params, apply_fn)


@model_factory("yolov5")
def _yolov5(**options) -> ZooModel:
    """YOLOv5-style detector (models/yolo.py): [B,S,S,3] → decoded
    [B, rows, 5+C] predictions for decoder mode=yolov5 — the native
    model behind the reference's yolov5 decoder fixtures
    (tensordec-boundingbox.c yolov5 mode; yolov5s tflite fixtures).
    Options: size (default 320), num_classes (80), width (32), batch,
    seed, compute_dtype."""
    from nnstreamer_tpu.models import yolo

    seed = int(options.get("seed", 0))
    batch = int(options.get("batch", 1))
    size = int(options.get("size", 320))
    num_classes = int(options.get("num_classes", 80))
    width = int(options.get("width", 32))
    dtype = _compute_dtype(options)
    if size % 32:
        raise ValueError(f"yolov5 size must be a multiple of 32, got {size}")
    params = _load_params_overlay(
        yolo.init_params(
            jax.random.PRNGKey(seed), num_classes=num_classes, width=width
        ),
        options,
    )

    def apply_fn(p, image):
        return yolo.apply(
            p, image, num_classes=num_classes, compute_dtype=dtype
        )

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(batch, size, options.get("input_dtype", "uint8"))
    return ZooModel("yolov5", fn, spec, params, apply_fn)


@model_factory("kws")
def _kws(**options) -> ZooModel:
    """Keyword-spotting raw-waveform classifier (models/audio.py, an
    M5-style conv net) — the zoo's audio model family, exercising the
    converter's audio path (gsttensor_converter.c media dispatch) with
    real inference. Input [samples, channels] S16LE (the converter's
    audio tensor) or batched [B, samples, C]. Options: samples (1024),
    channels (1), num_classes (12), width (32), batch, seed,
    compute_dtype."""
    from nnstreamer_tpu.models import audio

    seed = int(options.get("seed", 0))
    batch = int(options.get("batch", 1))
    samples = int(options.get("samples", 1024))
    channels = int(options.get("channels", 1))
    num_classes = int(options.get("num_classes", 12))
    width = int(options.get("width", 32))
    dtype = _compute_dtype(options)
    params = _load_params_overlay(
        audio.init_params(
            jax.random.PRNGKey(seed), num_classes=num_classes, width=width
        ),
        options,
    )

    def apply_fn(p, pcm):
        return audio.apply(p, pcm, compute_dtype=dtype)

    def fn(pcm):
        return apply_fn(params, pcm)

    shape = (
        (samples, channels) if batch == 1
        else (batch, samples, channels)
    )
    spec = TensorsSpec.of(TensorSpec(shape, DType.INT16, name="pcm"))
    return ZooModel("kws", fn, spec, params, apply_fn)


@model_factory("posenet")
def _posenet(**options) -> ZooModel:
    """PoseNet MobileNet-v1 257x257 multi-output (heatmap/offsets/
    displacements) — decoder mode=pose-estimation."""
    from nnstreamer_tpu.models import posenet

    seed = int(options.get("seed", 0))
    batch = int(options.get("batch", 1))
    dtype = _compute_dtype(options)
    params = _load_params_overlay(posenet.init_params(jax.random.PRNGKey(seed)), options)

    def apply_fn(p, image):
        return posenet.apply(p, image, compute_dtype=dtype)

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(batch, posenet.INPUT_SIZE, options.get("input_dtype", "uint8"))
    return ZooModel("posenet", fn, spec, params, apply_fn)


@model_factory("deeplab_v3")
def _deeplab_v3(**options) -> ZooModel:
    """DeepLab-v3 MobileNet-v2 257x257x21 — decoder mode=image-segment
    (tflite-deeplab)."""
    from nnstreamer_tpu.models import deeplab_v3

    seed = int(options.get("seed", 0))
    batch = int(options.get("batch", 1))
    dtype = _compute_dtype(options)
    params = _load_params_overlay(
        deeplab_v3.init_params(jax.random.PRNGKey(seed)), options
    )

    def apply_fn(p, image):
        return deeplab_v3.apply(p, image, compute_dtype=dtype)

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(batch, deeplab_v3.INPUT_SIZE, options.get("input_dtype", "uint8"))
    return ZooModel("deeplab_v3", fn, spec, params, apply_fn)


@model_factory("face_detect")
def _face_detect(**options) -> ZooModel:
    """Face detector. Default output: [max_faces,7] OV detection rows
    (decoder mode=ov-face-detection). ``output=regions`` emits int32
    [max_faces,4] pixel (x,y,w,h) for tensor_crop, scaled to
    ``frame_size=W:H`` (defaults to the model input size).
    ``output=regions+image`` emits (image, regions) so a downstream
    crop-resize transform fuses the whole cascade on device
    (docs/on-device-ops.md)."""
    from nnstreamer_tpu.models import face_pipeline as fp

    seed = int(options.get("seed", 0))
    max_faces = int(options.get("max_faces", fp.MAX_FACES))
    dtype = _compute_dtype(options)
    out_mode = options.get("output", "ov")
    threshold = float(options.get("threshold", 0.5))
    frame_size = options.get("frame_size", f"{fp.DETECT_SIZE}:{fp.DETECT_SIZE}")
    fw, fh = (int(v) for v in frame_size.split(":"))
    params = _load_params_overlay(
        fp.init_detect_params(jax.random.PRNGKey(seed)), options
    )

    def apply_fn(p, image):
        if out_mode in ("regions+image", "regions_image"):
            return fp.apply_detect_regions_with_image(
                p, image, fw, fh, max_faces=max_faces,
                threshold=threshold, compute_dtype=dtype,
            )
        det = fp.apply_detect(p, image, max_faces=max_faces, compute_dtype=dtype)
        if out_mode == "regions":
            return fp.detections_to_regions(det, fw, fh, threshold)
        return det

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(1, fp.DETECT_SIZE, options.get("input_dtype", "uint8"))
    return ZooModel("face_detect", fn, spec, params, apply_fn)


@model_factory("face_composite")
def _face_composite(**options) -> ZooModel:
    """Fused detect→crop+resize→landmark cascade as ONE XLA program
    (fp.apply_composite): fixed shapes, all max_faces crops batched on
    the MXU, zero host hops — the TPU-first form of the element-level
    tensor_crop composite. fn: uint8 [1,S,S,3] → (landmarks [max,136],
    detections [max,7])."""
    from nnstreamer_tpu.models import face_pipeline as fp

    seed = int(options.get("seed", 0))
    max_faces = int(options.get("max_faces", fp.MAX_FACES))
    threshold = float(options.get("threshold", 0.5))
    size = int(options.get("size", fp.DETECT_SIZE))
    dtype = _compute_dtype(options)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {
        "detect": _load_params_overlay(fp.init_detect_params(k1), options),
        "landmark": fp.init_landmark_params(k2),
    }

    def apply_fn(p, image):
        return fp.apply_composite(
            p["detect"], p["landmark"], image,
            max_faces=max_faces, threshold=threshold, compute_dtype=dtype,
        )

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(1, size, options.get("input_dtype", "uint8"))
    return ZooModel("face_composite", fn, spec, params, apply_fn)


@model_factory("transformer_lm")
def _transformer_lm(**options) -> ZooModel:
    """Decoder-only transformer LM (models/transformer.py) — the
    long-context flagship. fn: int32 tokens [B,T] → logits [B,T,V]."""
    from nnstreamer_tpu.models import transformer as tfm

    seed = int(options.get("seed", 0))
    vocab = int(options.get("vocab", 1024))
    d_model = int(options.get("d_model", 256))
    n_heads = int(options.get("n_heads", 8))
    n_layers = int(options.get("n_layers", 4))
    batch = int(options.get("batch", 1))
    seqlen = int(options.get("seqlen", 128))
    dtype = _compute_dtype(options)
    n_kv_heads = int(options.get("n_kv_heads", n_heads))
    params = _load_params_overlay(
        tfm.init_params(
            jax.random.PRNGKey(seed), vocab, d_model, n_heads, n_layers,
            n_kv_heads=n_kv_heads,
        ),
        options,
    )
    if options.get("quantize") == "int8w":
        # weight-only int8 (models/quantize.py): decode reads every
        # weight once per token, so fewer bytes/weight → more tok/s
        from nnstreamer_tpu.models import quantize as qz

        params = qz.quantize_lm_weights(params)
    attn_kind = options.get("attn", "dense")
    if attn_kind == "flash":
        from nnstreamer_tpu.ops.pallas.flash_attention import make_flash_attention

        attn_fn = make_flash_attention()
    elif attn_kind == "dense":
        attn_fn = None
    else:
        raise KeyError(f"transformer_lm: unknown attn {attn_kind!r}")

    gen_tokens = int(options.get("generate", 0))
    if gen_tokens > 0:
        # serving mode: prompt frames in, generated token frames out — the
        # whole KV-cache loop (models/decode.py) is one jitted program, so
        # a tensor_filter stage becomes an LLM generation server.
        # decode strategies: greedy/sampled (default), beam search, or
        # draft-free n-gram speculation
        from nnstreamer_tpu.models import decode as dec

        strategy = options.get("decode", "greedy")
        temperature = float(options.get("temperature", 0.0))
        gen_seed = int(options.get("gen_seed", 0))
        if strategy == "beam":
            beam_width = int(options.get("beam_width", 4))

            def fn(tokens):
                toks, _ = dec.beam_search(
                    params, tokens, n_heads, gen_tokens,
                    beam_width=beam_width, compute_dtype=dtype,
                )
                return toks
        elif strategy == "ngram":
            # the WHOLE speculative generation is one compiled program
            # (device while_loop: on-device n-gram mining + chunk
            # verify; speculative.ngram_generate_scanned) — the
            # host-looped ngram_speculative_generate pays a round trip
            # per round, the per-token poison the serving pumps remove
            from nnstreamer_tpu.models.speculative import (
                ngram_generate_scanned,
            )

            spec_k = int(options.get("spec_k", 4))
            spec_g = int(options.get("spec_ngram", 2))

            def fn(tokens):
                toks, _ = ngram_generate_scanned(
                    params, tokens, n_heads, gen_tokens, k=spec_k,
                    g=spec_g, compute_dtype=dtype,
                )
                return toks
        elif strategy == "greedy":
            def fn(tokens):
                return dec.generate(
                    params, tokens, n_heads, gen_tokens,
                    temperature=temperature,
                    rng=jax.random.PRNGKey(gen_seed),
                    compute_dtype=dtype,
                )
        else:
            raise KeyError(
                f"transformer_lm: unknown decode strategy {strategy!r} "
                "(greedy|beam|ngram)"
            )
        apply_fn = None
    else:
        def apply_fn(p, tokens):
            return tfm.apply(
                p, tokens, n_heads, attn_fn=attn_fn, compute_dtype=dtype
            )

        def fn(tokens):
            return apply_fn(params, tokens)

    spec = TensorsSpec.of(
        TensorSpec((batch, seqlen), DType.from_any("int32"), name="tokens")
    )
    return ZooModel("transformer_lm", fn, spec, params, apply_fn)


def _family_lm(name: str, mod, family_cls, options) -> ZooModel:
    """A language model served through its own block family (models/
    family.py): ``mod`` gives ``config_from_options``, ``init_params`` and
    ``apply``. ``custom=`` takes the widths by their short names (published
    by default), ``n_layers``, ``experts_held``, ``expert_offset``, ``vocab``,
    ``seed`` and the storage ``dtype`` (bfloat16 by default: weights drawn in
    float32 from the seed and rounded once).
    fn: int32 tokens [B,T] -> logits [B,T,V]."""
    cfg = mod.config_from_options(options)
    dtype = jnp.dtype(options.get("dtype", "bfloat16"))
    params = mod.init_params(cfg, int(options.get("seed", 0)), dtype)

    def apply_fn(p, tokens):
        return mod.apply(p, tokens, cfg)

    spec = TensorsSpec.of(TensorSpec(
        (int(options.get("batch", 1)), int(options.get("seqlen", 128))),
        DType.from_any("int32"), name="tokens"))
    return ZooModel(name, lambda tokens: apply_fn(params, tokens),
                    spec, params, apply_fn, family=family_cls(cfg, dtype))


@model_factory("longcat_flash_lm")
def _longcat_flash_lm(**options) -> ZooModel:
    """LongCat-Flash (models/longcat.py): latent attention, the
    shortcut-connected double layer, one chip's share of the routed experts
    with identity experts (``_family_lm`` has the options)."""
    from nnstreamer_tpu.models import longcat

    return _family_lm("longcat_flash_lm", longcat, longcat.LongcatFamily, options)


@model_factory("kimi_linear_lm")
def _kimi_linear_lm(**options) -> ZooModel:
    """Kimi-Linear (models/kimi_linear.py): Kimi Delta Attention layers with a
    per-slot recurrent state beside latent (MLA, unrotated) layers, a leading
    dense layer, sigmoid-routed experts with a shared expert, one chip's share
    of the routed experts (``_family_lm`` has the options)."""
    from nnstreamer_tpu.models import kimi_linear

    return _family_lm("kimi_linear_lm", kimi_linear, kimi_linear.KimiLinearFamily,
                      options)


@model_factory("granite_hybrid_lm")
def _granite_hybrid_lm(**options) -> ZooModel:
    """Granite-4.0-H (models/granite_hybrid.py): Mamba-2 state-space layers
    with a per-slot state beside grouped-query attention layers without
    positions (the dense family's K/V blocks), every layer followed by
    softmax-routed experts with a shared MLP, one chip's share of the routed
    experts, a tied head (``_family_lm`` has the options)."""
    from nnstreamer_tpu.models import granite_hybrid

    return _family_lm("granite_hybrid_lm", granite_hybrid,
                      granite_hybrid.GraniteHybridFamily, options)


@model_factory("vit")
def _vit(**options) -> ZooModel:
    """Vision Transformer classifier (models/vit.py): patch-embed +
    non-causal encoder stack, image-labeling compatible logits."""
    from nnstreamer_tpu.models import vit

    seed = int(options.get("seed", 0))
    num_classes = int(options.get("num_classes", 1001))
    d_model = int(options.get("d_model", 384))
    n_heads = int(options.get("n_heads", 6))
    n_layers = int(options.get("n_layers", 12))
    patch = int(options.get("patch", vit.PATCH))
    batch = int(options.get("batch", 1))
    size = int(options.get("size", vit.INPUT_SIZE))
    if size % patch:
        raise ValueError(f"vit: size {size} not divisible by patch {patch}")
    dtype = _compute_dtype(options)
    params = _load_params_overlay(
        vit.init_params(
            jax.random.PRNGKey(seed), num_classes, d_model, n_heads,
            n_layers, patch, size,
        ),
        options,
    )

    def apply_fn(p, image):
        return vit.apply(p, image, n_heads, compute_dtype=dtype)

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(batch, size, options.get("input_dtype", "uint8"))
    return ZooModel("vit", fn, spec, params, apply_fn)


@model_factory("face_landmark")
def _face_landmark(**options) -> ZooModel:
    """68-point landmark net on face crops (global-pooled trunk, so any
    crop size ≥16 works; spec advertises the canonical 112)."""
    from nnstreamer_tpu.models import face_pipeline as fp

    seed = int(options.get("seed", 0))
    batch = int(options.get("batch", 1))
    size = int(options.get("size", fp.LANDMARK_SIZE))
    dtype = _compute_dtype(options)
    params = _load_params_overlay(
        fp.init_landmark_params(jax.random.PRNGKey(seed)), options
    )

    def apply_fn(p, image):
        return fp.apply_landmark(p, image, compute_dtype=dtype)

    def fn(image):
        return apply_fn(params, image)

    spec = _image_spec(batch, size, options.get("input_dtype", "uint8"))
    return ZooModel("face_landmark", fn, spec, params, apply_fn)
