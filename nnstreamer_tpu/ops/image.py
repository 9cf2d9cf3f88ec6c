"""Jittable image ops: fixed-shape crop+resize for on-device cascades.

The reference composes detector→crop→second-model cascades through
tensor_crop (gsttensor_crop.c), whose outputs are *variable-size* host
buffers — every frame crosses the host and each crop size retriggers
downstream negotiation. The TPU-first alternative: crop and resample to a
canonical size inside the same XLA program (fixed shapes, MXU-friendly),
so a whole detect→crop→landmark cascade is ONE program with zero host
hops (see models/face_pipeline.apply_composite).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nnstreamer_tpu.ops.dispatch import record as _record_dispatch


def _use_pallas(impl: str, op: str = "", dtype=None) -> bool:
    """Implementation pick for the image ops: ``auto`` takes the Pallas
    kernel on a real TPU backend (MXU-blocked resampling,
    ops/pallas/image_kernels.py) and the jnp expression elsewhere (the
    interpreter would be a pessimization on the CPU hot path; interpret
    mode stays a parity-test tool). A pallas pick is re-checked against
    the kernel registry's dtype support (_compat.pallas_ok) — an
    unsupported dtype degrades to jnp with a logged reason instead of a
    trace-time error. A non-empty ``op`` records the resolved choice in
    the dispatch tally (ops/dispatch.py) so ``nns-xray --dispatch`` can
    prove which kernel engaged."""
    if impl == "pallas":
        use = True
    elif impl == "jnp":
        use = False
    elif impl != "auto":
        raise ValueError(f"image op impl {impl!r} not auto/jnp/pallas")
    else:
        use = jax.default_backend() == "tpu"
    if use:
        from nnstreamer_tpu.ops.pallas._compat import pallas_ok

        use, _ = pallas_ok(op or "image", dtype)
    if op:
        _record_dispatch(op, "pallas" if use else "jnp")
    return use


def crop_and_resize(image, boxes, out_h: int, out_w: int, impl: str = "auto"):
    """Bilinear crop+resize (TF crop_and_resize semantics, pixel boxes).

    image: [H, W, C] float; boxes: [N, 4] (x1, y1, x2, y2) in pixel
    coordinates (any float dtype; degenerate boxes clamp to edge pixels)
    → [N, out_h, out_w, C], image dtype.
    """
    if _use_pallas(impl, op="crop_and_resize", dtype=image.dtype):
        from nnstreamer_tpu.ops.pallas._compat import interpret_default
        from nnstreamer_tpu.ops.pallas.image_kernels import (
            crop_and_resize as pallas_crop,
        )

        # explicit impl=pallas off-TPU runs the interpreter (parity
        # tests); auto never picks it there
        return pallas_crop(
            image, boxes, out_h, out_w, interpret=interpret_default()
        )
    h, w, _ = image.shape
    boxes = boxes.astype(jnp.float32)

    def one(box):
        x1, y1, x2, y2 = box
        # sample at output-pixel centers mapped into the box
        ys = y1 + (y2 - y1) * (jnp.arange(out_h, dtype=jnp.float32) + 0.5) / out_h - 0.5
        xs = x1 + (x2 - x1) * (jnp.arange(out_w, dtype=jnp.float32) + 0.5) / out_w - 0.5
        y0 = jnp.floor(ys)
        x0 = jnp.floor(xs)
        wy = ys - y0
        wx = xs - x0
        y0i = jnp.clip(y0, 0, h - 1).astype(jnp.int32)
        y1i = jnp.clip(y0 + 1, 0, h - 1).astype(jnp.int32)
        x0i = jnp.clip(x0, 0, w - 1).astype(jnp.int32)
        x1i = jnp.clip(x0 + 1, 0, w - 1).astype(jnp.int32)
        top = image[y0i][:, x0i] * (1 - wx)[None, :, None] + \
            image[y0i][:, x1i] * wx[None, :, None]
        bot = image[y1i][:, x0i] * (1 - wx)[None, :, None] + \
            image[y1i][:, x1i] * wx[None, :, None]
        return top * (1 - wy)[:, None, None] + bot * wy[:, None, None]

    return _round_clip_cast(jax.vmap(one)(boxes), image.dtype)


def _round_clip_cast(x, dtype):
    """Cast crop/resize output to ``dtype`` with the tensor_crop
    convention for integers: round + clip to the dtype's own range (a
    truncating astype would make integer results backend-dependent,
    and 0..255 would wrap int8 / clamp valid uint16). The ONE home of
    this epilogue — the Pallas kernels' wrapper applies it too."""
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        x = jnp.clip(jnp.round(x), info.min, info.max)
    return x.astype(dtype)


def crop_regions(image, xyxy, out_h: int, out_w: int, valid=None,
                 out_dtype=None, impl: str = "auto"):
    """Crop+resize with the tensor_crop output conventions shared by
    ``tensor_crop out-size=`` and ``tensor_transform mode=crop-resize``
    (docs/on-device-ops.md): compute in float32, zero the rows where
    ``valid`` is False (zero-size regions, below-threshold detections),
    and round+clip integer outputs. image [H, W, C]; xyxy [N, 4] pixel
    corners; out_dtype defaults to the image dtype."""
    crops = crop_and_resize(
        image.astype(jnp.float32), xyxy, out_h, out_w, impl=impl
    )
    if valid is not None:
        crops = jnp.where(valid[:, None, None, None], crops, 0.0)
    return _round_clip_cast(
        crops, image.dtype if out_dtype is None else out_dtype
    )


def resize_bilinear(image, out_h: int, out_w: int, impl: str = "auto"):
    """Whole-image bilinear resize: [N, H, W, C] or [H, W, C] → same
    rank with the spatial dims replaced. Same sampling grid as
    crop_and_resize over the full-image box, so the element-level
    resize (tensor_transform mode=resize) and the crop path can't
    drift apart numerically."""
    squeeze = image.ndim == 3
    img = image[None] if squeeze else image
    if _use_pallas(impl, op="resize_bilinear", dtype=img.dtype):
        from nnstreamer_tpu.ops.pallas._compat import interpret_default
        from nnstreamer_tpu.ops.pallas.image_kernels import (
            resize_bilinear as pallas_resize,
        )

        out = pallas_resize(img, out_h, out_w, interpret=interpret_default())
    else:
        _, h, w, _ = img.shape
        box = jnp.asarray([[0.0, 0.0, float(w), float(h)]], jnp.float32)

        def one(im):
            return crop_and_resize(im, box, out_h, out_w, impl="jnp")[0]

        out = jax.vmap(one)(img)
    return out[0] if squeeze else out
