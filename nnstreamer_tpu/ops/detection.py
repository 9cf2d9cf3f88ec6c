"""Jittable detection post-processing primitives.

TPU-native redesign of the scalar C loops in the reference's bounding-box
decoder (ext/nnstreamer/tensor_decoder/tensordec-boundingbox.c): prior-box
decode (:349-361 scales), score thresholding, and NMS run as vectorized jax
ops so they can be jitted — and fused into the same XLA program as the model
when a Filter and Decoder stage are fused by the pipeline compiler. The
reference iterates detections one-by-one on the CPU; here everything is a
fixed-shape masked tensor program (no data-dependent shapes, so XLA compiles
once and the MXU/VPU stay busy).

Detections are represented as a fixed-size ``(max_out, 6)`` float32 tensor
of ``[x1, y1, x2, y2, class, score]`` rows (normalized [0,1] coords), with
``score == 0`` marking empty slots — the static-shape analogue of the
reference's GArray of detectedObject.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Reference defaults (tensordec-boundingbox.c:343-361, :125-127)
SSD_THRESHOLD = 0.5
SSD_Y_SCALE = 10.0
SSD_X_SCALE = 10.0
SSD_H_SCALE = 5.0
SSD_W_SCALE = 5.0
SSD_IOU_THRESHOLD = 0.5
YOLOV5_CONF_THRESHOLD = 0.3
YOLOV5_IOU_THRESHOLD = 0.6
OV_CONF_THRESHOLD = 0.8


def ssd_decode_boxes(
    locations: jax.Array,
    priors: jax.Array,
    y_scale: float = SSD_Y_SCALE,
    x_scale: float = SSD_X_SCALE,
    h_scale: float = SSD_H_SCALE,
    w_scale: float = SSD_W_SCALE,
) -> jax.Array:
    """Decode SSD location offsets against prior boxes → [N,4] x1,y1,x2,y2.

    locations: [N, 4] (ycenter, xcenter, h, w offsets); priors: [4, N]
    rows (ycenter, xcenter, h, w) as loaded from the reference's
    box-priors.txt (4 lines × N values).
    """
    loc = locations.astype(jnp.float32)
    pr = priors.astype(jnp.float32)
    ycenter = loc[:, 0] / y_scale * pr[2] + pr[0]
    xcenter = loc[:, 1] / x_scale * pr[3] + pr[1]
    h = jnp.exp(loc[:, 2] / h_scale) * pr[2]
    w = jnp.exp(loc[:, 3] / w_scale) * pr[3]
    x1 = xcenter - w / 2.0
    y1 = ycenter - h / 2.0
    return jnp.stack([x1, y1, x1 + w, y1 + h], axis=-1)


def iou_matrix(boxes: jax.Array) -> jax.Array:
    """Pairwise IoU of [N,4] x1,y1,x2,y2 boxes → [N,N]. O(N²) but fully
    vectorized — the TPU-friendly trade against the reference's sequential
    compare loop."""
    area = jnp.maximum(boxes[:, 2] - boxes[:, 0], 0.0) * jnp.maximum(
        boxes[:, 3] - boxes[:, 1], 0.0
    )
    lt = jnp.maximum(boxes[:, None, :2], boxes[None, :, :2])
    rb = jnp.minimum(boxes[:, None, 2:], boxes[None, :, 2:])
    wh = jnp.maximum(rb - lt, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    union = area[:, None] + area[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


def nms(
    boxes: jax.Array,
    scores: jax.Array,
    iou_threshold: float,
    max_out: int,
    impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Greedy class-agnostic NMS with static shapes.

    Returns (keep_idx[max_out] int32, keep_score[max_out]); empty slots have
    score 0 and index -1. Implemented as a lax.fori_loop over ranked
    candidates with a masked IoU matrix — equivalent semantics to the
    reference's sort + suppress loop, but compiled. ``impl="auto"``
    swaps in the Pallas suppression kernel (ops/pallas/nms.py — no N×N
    IoU matrix in HBM) on a real TPU backend; both implementations are
    bit-identical (tests/test_ops_device.py).
    """
    if impl not in ("auto", "jnp", "pallas"):
        raise ValueError(f"nms impl {impl!r} not auto/jnp/pallas")
    from nnstreamer_tpu.ops.dispatch import record as _record_dispatch
    from nnstreamer_tpu.ops.pallas._compat import interpret_default, pallas_ok

    use_pallas = impl == "pallas" or (
        impl == "auto" and jax.default_backend() == "tpu"
    )
    if use_pallas:
        # registry dtype gate: an unsupported score dtype degrades to
        # the (bit-identical) jnp path with a logged reason
        use_pallas, _ = pallas_ok("nms", scores.dtype)
    _record_dispatch("nms", "pallas" if use_pallas else "jnp")
    if use_pallas:
        from nnstreamer_tpu.ops.pallas.nms import nms as pallas_nms

        # explicit impl=pallas off-TPU runs the interpreter (parity
        # tests); auto never picks it there
        return pallas_nms(
            boxes, scores, iou_threshold, max_out,
            interpret=interpret_default(),
        )
    n = boxes.shape[0]
    k = min(max_out, n)
    order = jnp.argsort(-scores)
    sboxes = boxes[order]
    sscores = scores[order]
    ious = iou_matrix(sboxes)

    def body(i, alive):
        # i-th candidate survives iff still alive; then kill its overlaps.
        keep_i = alive[i]
        suppress = (ious[i] > iou_threshold) & (jnp.arange(n) > i) & keep_i
        return alive & ~suppress

    alive = jax.lax.fori_loop(0, n, body, sscores > 0)
    kept_scores = jnp.where(alive, sscores, 0.0)
    top = jnp.argsort(-kept_scores)[:k]
    sel_scores = kept_scores[top]
    sel_idx = jnp.where(sel_scores > 0, order[top], -1)
    if k < max_out:
        sel_idx = jnp.pad(sel_idx, (0, max_out - k), constant_values=-1)
        sel_scores = jnp.pad(sel_scores, (0, max_out - k))
    return sel_idx.astype(jnp.int32), sel_scores


def _pack_detections(
    boxes: jax.Array,
    classes: jax.Array,
    keep_idx: jax.Array,
    keep_scores: jax.Array,
) -> jax.Array:
    """Gather kept rows into the fixed [max_out, 6] detections tensor."""
    safe = jnp.maximum(keep_idx, 0)
    sel_boxes = boxes[safe]
    sel_cls = classes[safe].astype(jnp.float32)
    valid = (keep_idx >= 0)[:, None].astype(jnp.float32)
    rows = jnp.concatenate(
        [sel_boxes, sel_cls[:, None], keep_scores[:, None]], axis=-1
    )
    return rows * valid


@functools.partial(
    jax.jit, static_argnames=("threshold", "iou_threshold", "max_out")
)
def ssd_postprocess(
    locations: jax.Array,
    class_scores: jax.Array,
    priors: jax.Array,
    threshold: float = SSD_THRESHOLD,
    iou_threshold: float = SSD_IOU_THRESHOLD,
    max_out: int = 100,
    y_scale: float = SSD_Y_SCALE,
    x_scale: float = SSD_X_SCALE,
    h_scale: float = SSD_H_SCALE,
    w_scale: float = SSD_W_SCALE,
) -> jax.Array:
    """mobilenet-ssd mode: priors + raw logits → [max_out, 6] detections.

    class_scores: [N, num_classes] raw logits; class 0 is background
    (skipped, as in the reference's label loop starting at 1). The
    reference thresholds in logit space (sigmoid_threshold = logit(thr),
    tensordec-boundingbox.c:204,361) — same math, done as one masked
    sigmoid here.
    """
    boxes = ssd_decode_boxes(locations, priors, y_scale, x_scale, h_scale, w_scale)
    probs = jax.nn.sigmoid(class_scores.astype(jnp.float32))
    probs = probs.at[:, 0].set(0.0)  # background
    best = jnp.argmax(probs, axis=-1)
    best_score = jnp.max(probs, axis=-1)
    score = jnp.where(best_score >= threshold, best_score, 0.0)
    keep_idx, keep_scores = nms(boxes, score, iou_threshold, max_out)
    return _pack_detections(boxes, best, keep_idx, keep_scores)


@functools.partial(jax.jit, static_argnames=("threshold", "max_out"))
def ssd_pp_postprocess(
    locations: jax.Array,
    classes: jax.Array,
    scores: jax.Array,
    num: jax.Array,
    threshold: float = 0.5,
    max_out: int = 100,
) -> jax.Array:
    """mobilenet-ssd-postprocess mode: the model already ran NMS; just
    threshold + repack. locations [N,4] = (ymin, xmin, ymax, xmax)
    normalized (TFLite detection postprocess convention)."""
    loc = locations.astype(jnp.float32)
    boxes = jnp.stack([loc[:, 1], loc[:, 0], loc[:, 3], loc[:, 2]], axis=-1)
    n = loc.shape[0]
    valid = jnp.arange(n) < num.astype(jnp.int32).reshape(())
    s = jnp.where(valid & (scores.astype(jnp.float32) >= threshold),
                  scores.astype(jnp.float32), 0.0)
    top = jnp.argsort(-s)[:max_out]
    keep_idx = jnp.where(s[top] > 0, top, -1).astype(jnp.int32)
    return _pack_detections(boxes, classes.astype(jnp.float32), keep_idx, s[top])


@functools.partial(
    jax.jit, static_argnames=("conf_threshold", "iou_threshold", "max_out", "scaled")
)
def yolov5_postprocess(
    pred: jax.Array,
    conf_threshold: float = YOLOV5_CONF_THRESHOLD,
    iou_threshold: float = YOLOV5_IOU_THRESHOLD,
    max_out: int = 100,
    scaled: bool = True,
) -> jax.Array:
    """yolov5 mode: [N, 5+C] (cx,cy,w,h,objectness,C class scores) →
    [max_out, 6]. ``scaled=False`` applies sigmoid (raw head outputs);
    coords are expected normalized to [0,1] (the element divides by input
    size beforehand when the model emits pixels)."""
    p = pred.astype(jnp.float32)
    if not scaled:
        p = jax.nn.sigmoid(p)
    cx, cy, w, h = p[:, 0], p[:, 1], p[:, 2], p[:, 3]
    obj = p[:, 4]
    cls_scores = p[:, 5:] * obj[:, None]
    best = jnp.argmax(cls_scores, axis=-1)
    best_score = jnp.max(cls_scores, axis=-1)
    boxes = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], axis=-1)
    score = jnp.where(best_score >= conf_threshold, best_score, 0.0)
    keep_idx, keep_scores = nms(boxes, score, iou_threshold, max_out)
    return _pack_detections(boxes, best, keep_idx, keep_scores)


@functools.partial(jax.jit, static_argnames=("conf_threshold", "max_out"))
def ov_detection_postprocess(
    pred: jax.Array,
    conf_threshold: float = OV_CONF_THRESHOLD,
    max_out: int = 100,
) -> jax.Array:
    """ov-person/face-detection: [N, 7] rows (image_id, label, conf,
    x_min, y_min, x_max, y_max), already normalized — threshold + repack
    (reference tensordec-boundingbox.c:121-124)."""
    p = pred.astype(jnp.float32).reshape(-1, 7)
    boxes = p[:, 3:7]
    score = jnp.where(p[:, 2] >= conf_threshold, p[:, 2], 0.0)
    n = p.shape[0]
    k = min(max_out, n)
    top = jnp.argsort(-score)[:k]
    keep_idx = jnp.where(score[top] > 0, top, -1).astype(jnp.int32)
    det = _pack_detections(boxes, p[:, 1], keep_idx, score[top])
    if k < max_out:
        det = jnp.pad(det, ((0, max_out - k), (0, 0)))
    return det


def generate_mp_palm_anchors(
    num_layers: int = 4,
    min_scale: float = 1.0,
    max_scale: float = 1.0,
    x_offset: float = 0.5,
    y_offset: float = 0.5,
    strides: Sequence[int] = (8, 16, 16, 16),
    input_size: int = 192,
) -> np.ndarray:
    """SSD-style anchor generation for mp-palm-detection (reference
    tensordec-boundingbox.c option3 scheme :68-80; same recipe as
    mediapipe's SsdAnchorsCalculator). Returns [N, 4] (ycenter, xcenter,
    h, w) — host-side, computed once at negotiate time."""
    if len(strides) < num_layers:
        raise ValueError(
            f"mp-palm anchors: {num_layers} layers need {num_layers} strides, "
            f"got {len(strides)}"
        )
    anchors = []
    layer = 0
    while layer < num_layers:
        # merge consecutive layers with identical strides
        scales = []
        last = layer
        while last < num_layers and strides[last] == strides[layer]:
            if num_layers == 1:
                scale = (min_scale + max_scale) * 0.5
            else:
                scale = min_scale + (max_scale - min_scale) * last / (num_layers - 1.0)
            scales.extend([scale, scale])  # 2 anchors per cell
            last += 1
        stride = strides[layer]
        fm = int(np.ceil(input_size / stride))
        for y in range(fm):
            for x in range(fm):
                for _ in scales:
                    anchors.append(
                        ((y + y_offset) / fm, (x + x_offset) / fm, 1.0, 1.0)
                    )
        layer = last
    return np.asarray(anchors, np.float32)


@functools.partial(
    jax.jit, static_argnames=("score_threshold", "iou_threshold", "max_out", "input_size")
)
def mp_palm_postprocess(
    raw_boxes: jax.Array,
    raw_scores: jax.Array,
    anchors: jax.Array,
    score_threshold: float = 0.5,
    iou_threshold: float = 0.3,
    max_out: int = 20,
    input_size: int = 192,
) -> jax.Array:
    """mp-palm-detection: raw_boxes [N, 18] (dx,dy,w,h + 7 keypoint pairs,
    pixel units), raw_scores [N] logits, anchors [N,4] → [max_out, 6]."""
    b = raw_boxes.astype(jnp.float32)
    a = anchors.astype(jnp.float32)
    cx = b[:, 0] / input_size + a[:, 1]
    cy = b[:, 1] / input_size + a[:, 0]
    w = b[:, 2] / input_size
    h = b[:, 3] / input_size
    boxes = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], axis=-1)
    probs = jax.nn.sigmoid(raw_scores.astype(jnp.float32).reshape(-1))
    score = jnp.where(probs >= score_threshold, probs, 0.0)
    keep_idx, keep_scores = nms(boxes, score, iou_threshold, max_out)
    return _pack_detections(boxes, jnp.zeros_like(score), keep_idx, keep_scores)
