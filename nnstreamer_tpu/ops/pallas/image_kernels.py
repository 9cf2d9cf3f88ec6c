"""Tiled bilinear crop/resize/normalize as a Pallas TPU kernel.

The pre-processing half of the "beyond matmul" direction (PAPERS.md:
Pushing Tensor Accelerators Beyond MatMul; GPTPU): bilinear resampling is
two small matrix contractions — ``out = Wy · img · Wxᵀ`` per channel,
where ``Wy [out_h, H]`` / ``Wx [out_w, W]`` are interpolation-weight
matrices with two non-zeros per row — so the crop runs on the MXU instead
of the gather/scatter path XLA lowers ``image[y0i][:, x0i]`` to.

Layout is what the MXU and Mosaic's (8, 128) tiling want, not NHWC: the
wrapper hands the kernel channel PLANES ``[n_img, C, H, W]`` (one XLA
transpose; W on lanes, rows on sublanes — an interleaved C=3 minor dim
would pad every pixel to a 128-lane tile) and gets planes
``[N, C, out_h, out_w]`` float32 back, which it transposes to NHWC and
casts. The grid walks (box, channel, row chunk): each step contracts one
``[th, W]`` chunk of source rows against its slice of ``Wy`` into a
VMEM-resident ``[out_h, W]`` accumulator — so VMEM holds a row chunk,
never the whole frame (a 1080p plane is 8 MB) — and the last chunk
contracts the accumulator with ``Wxᵀ``. Box corners ride as
scalar-prefetch (SMEM) operands; the weight matrices are built in-kernel
from integer ``broadcasted_iota`` (Mosaic has no float iota). An optional
fused ``*scale + offset`` normalization epilogue makes a uint8→float
input transform cost zero extra HBM round trips.

Numerics match :func:`nnstreamer_tpu.ops.image.crop_and_resize` (the jnp
reference): sample centers at ``box_lo + extent·(i+0.5)/out - 0.5``,
edge clamping via clipping the sample coordinate — a clipped coordinate
puts weight 1 on the edge row, exactly what the reference's index
clamping computes. Both contractions ask for float32 contract precision
(one bf16 pass would cost a uint8 image its low bits). Parity is pinned
by tests/test_ops_device.py in interpret mode (the CPU fallback,
ops/pallas/_compat.py discipline).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.ops.pallas import registry as _registry
from nnstreamer_tpu.ops.pallas._compat import interpret_default

#: source rows contracted per grid step: a multiple of every dtype's
#: sublane tile (8 f32 / 16 bf16 / 32 int8), small enough that a
#: [ROW_CHUNK, 1920] f32 chunk double-buffers in ~2 MB of VMEM
ROW_CHUNK = 128


# BlockSpec index maps — module-level so the registered LaunchPlans and
# the live pallas_call share the SAME callables (grid (box, channel, row
# chunk), box corners prefetched)
def _crop_img_index_map(i, c, k, boxes_ref):
    # crop grid: every box reads the one shared image
    return (0, c, k, 0)


def _resize_img_index_map(i, c, k, boxes_ref):
    # resize grid: one batch element per "box"
    return (i, c, k, 0)


def _out_index_map(i, c, k, boxes_ref):
    return (i, c, 0, 0)


def _row_chunk(h: int):
    """(rows per grid step, number of steps) covering ``h`` source rows."""
    th = min(h, ROW_CHUNK)
    return th, -(-h // th)


def _weights(lo, hi, out_n: int, in_n: int, shape, out_axis: int,
             in_start=0):
    """Bilinear interpolation weights for sampling the interval
    [lo, hi) (pixel coords) at ``out_n`` output-pixel centers, over the
    ``shape``-d window of input pixels starting at ``in_start``:
    ``out_axis`` indexes outputs, the other axis inputs. Integer iota
    cast to float (TPU iota is integer-only, and 2-D)."""
    o = jax.lax.broadcasted_iota(jnp.int32, shape, out_axis)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - out_axis) + in_start
    ys = lo + (hi - lo) * (o.astype(jnp.float32) + 0.5) / float(out_n) - 0.5
    ys = jnp.clip(ys, 0.0, float(in_n - 1))
    return jnp.maximum(0.0, 1.0 - jnp.abs(ys - i.astype(jnp.float32)))


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _crop_kernel(
    boxes_ref, img_ref, out_ref, acc_ref, *,
    h: int, w: int, th: int, n_k: int, out_h: int, out_w: int,
    scale: Optional[float], offset: Optional[float],
):
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    img = img_ref[0, 0]                               # [th, w]
    if jnp.issubdtype(img.dtype, jnp.integer):
        img = img.astype(jnp.int32)                   # Mosaic: no u8→f32
    img = img.astype(jnp.float32)
    if h % th:
        # the tail chunk's pad rows hold arbitrary bytes, and a zero
        # weight does not neutralize a NaN
        row = k * th + jax.lax.broadcasted_iota(jnp.int32, img.shape, 0)
        img = jnp.where(row < h, img, 0.0)
    # y-interpolation: this chunk's rows against its slice of Wy
    wy = _weights(
        boxes_ref[4 * i + 1], boxes_ref[4 * i + 3], out_h, h,
        (out_h, th), 0, in_start=k * th,
    )
    acc_ref[:] += _dot(wy, img)                       # [out_h, w]

    @pl.when(k == n_k - 1)
    def _final():
        # x-interpolation: contract the W axis → [out_h, out_w]
        wxt = _weights(
            boxes_ref[4 * i], boxes_ref[4 * i + 2], out_w, w,
            (w, out_w), 1,
        )
        out = _dot(acc_ref[:], wxt)
        if scale is not None:
            out = out * scale
        if offset is not None:
            out = out + offset
        out_ref[0, 0] = out


def _launch(planes, boxes, img_index_map, out_h, out_w, scale, offset,
            out_dtype, interpret):
    """One home for the kernel launch: ``planes`` [n_img, C, H, W],
    ``boxes`` [N, 4] pixel corners → NHWC [N, out_h, out_w, C] in
    ``out_dtype`` (integer outputs round-and-clip like the device-crop
    element). The crop and resize entry points differ only in which
    image a box reads (``img_index_map``)."""
    n = boxes.shape[0]
    _, c, h, w = planes.shape
    th, n_k = _row_chunk(h)
    kernel = functools.partial(
        _crop_kernel,
        h=h, w=w, th=th, n_k=n_k, out_h=out_h, out_w=out_w,
        scale=scale, offset=offset,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, c, out_h, out_w), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, c, n_k),
            in_specs=[pl.BlockSpec((1, 1, th, w), img_index_map)],
            out_specs=pl.BlockSpec((1, 1, out_h, out_w), _out_index_map),
            scratch_shapes=[pltpu.VMEM((out_h, w), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(boxes.astype(jnp.float32).reshape(-1), planes)
    from nnstreamer_tpu.ops.image import _round_clip_cast

    return _round_clip_cast(out.transpose(0, 2, 3, 1), out_dtype)


def _out_dtype(image, scale, offset, out_dtype=None):
    if out_dtype is not None:
        return out_dtype
    normalized = scale is not None or offset is not None
    return jnp.float32 if normalized else image.dtype


@functools.partial(
    jax.jit,
    static_argnames=(
        "out_h", "out_w", "scale", "offset", "out_dtype", "interpret"
    ),
)
def crop_and_resize(
    image,
    boxes,
    out_h: int,
    out_w: int,
    scale: Optional[float] = None,
    offset: Optional[float] = None,
    out_dtype=None,
    interpret: bool = False,
):
    """Pallas crop+resize: image [H, W, C], boxes [N, 4] pixel
    (x1, y1, x2, y2) → [N, out_h, out_w, C].

    ``scale``/``offset`` fuse a normalization epilogue (out·scale +
    offset) into the kernel — the uint8→float preprocessing transform at
    zero extra memory traffic. ``out_dtype`` defaults to the image dtype
    (float outputs when a normalize epilogue is active); integer outputs
    round-and-clip like the device-crop element."""
    return _launch(
        image.transpose(2, 0, 1)[None], boxes, _crop_img_index_map,
        out_h, out_w, scale, offset,
        _out_dtype(image, scale, offset, out_dtype), interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("out_h", "out_w", "scale", "offset", "interpret"),
)
def resize_bilinear(
    image,
    out_h: int,
    out_w: int,
    scale: Optional[float] = None,
    offset: Optional[float] = None,
    interpret: bool = False,
):
    """Whole-image bilinear resize (+ optional normalize epilogue):
    [N, H, W, C] or [H, W, C] → same rank with H, W replaced. A resize
    IS a crop of the full image; the batch rides the box axis of the
    grid (one full-image box per batch element)."""
    squeeze = image.ndim == 3
    img = image[None] if squeeze else image
    n, h, w, _ = img.shape
    boxes = jnp.broadcast_to(
        jnp.asarray([[0.0, 0.0, float(w), float(h)]], jnp.float32), (n, 4)
    )
    out = _launch(
        img.transpose(0, 3, 1, 2), boxes, _resize_img_index_map,
        out_h, out_w, scale, offset, _out_dtype(img, scale, offset),
        interpret,
    )
    return out[0] if squeeze else out


# -- kernel registration (nns-kscope) ----------------------------------------


def _crop_flops(n, h, w, c, out_h, out_w):
    # two MXU contractions per box and channel plane: Wy·plane
    # ([out_h,h]·[h,w]) then ·Wxᵀ ([out_h,w]·[w,out_w]), 2·m·n·k each
    return n * 2 * out_h * w * c * (h + out_w)


def _plan(params, shared_image: bool, defaults):
    n = params.get("n", defaults["n"])
    h, w, c = (params.get(k, defaults[k]) for k in ("h", "w", "c"))
    out_h, out_w = params.get("out_h", 8), params.get("out_w", 8)
    dtype = params.get("dtype", "float32")
    th, n_k = _row_chunk(h)
    import numpy as np

    return _registry.LaunchPlan(
        grid=(n, c, n_k),
        blocks=(
            _registry.BlockDesc(
                "planes", "in", (1 if shared_image else n, c, h, w),
                (1, 1, th, w), dtype,
                _crop_img_index_map if shared_image
                else _resize_img_index_map,
            ),
            _registry.BlockDesc(
                "out", "out", (n, c, out_h, out_w), (1, 1, out_h, out_w),
                "float32", _out_index_map,
            ),
        ),
        scratch=(_registry.ScratchDesc("acc", (out_h, w)),),
        prefetch=(
            _registry.PrefetchDesc(
                "boxes", (4 * n,), "float32",
                make=lambda: np.tile(
                    np.asarray([0.0, 0.0, w, h], np.float32), n
                ),
            ),
        ),
        flops=_crop_flops(n, h, w, c, out_h, out_w),
        notes="row-chunked: VMEM holds one [th, W] chunk and the "
        "[out_h, W] accumulator, never the frame",
    )


def _crop_plan(params):
    return _plan(params, True, {"n": 4, "h": 32, "w": 48, "c": 3})


def _resize_plan(params):
    return _plan(params, False, {"n": 2, "h": 17, "w": 23, "c": 3})


def _interp_atol(dtype, h, w):
    """Parity tolerance for bilinear sampling: the kernel and the jnp
    reference round the float32 source coordinates differently, and at
    magnitude max(h, w) one coordinate ulp (≈ max(h,w)·2⁻²³) moves an
    O(1) interpolation weight by that much — 720p-scale cases need a
    looser bar than thumbnails, not a sloppier kernel."""
    if jnp.issubdtype(dtype, jnp.integer):
        return 1.0
    return max(1e-4, 8 * max(h, w) * 2.0 ** -23)


def _rand_boxes(rng, n, h, w):
    import numpy as np

    x1 = rng.uniform(0, w - 1, n)
    y1 = rng.uniform(0, h - 1, n)
    x2 = x1 + rng.uniform(1.0, np.maximum(1.5, w - x1))
    y2 = y1 + rng.uniform(1.0, np.maximum(1.5, h - y1))
    return jnp.asarray(np.stack([x1, y1, x2, y2], -1), jnp.float32)


def _crop_run_case(params):
    import numpy as np

    from nnstreamer_tpu.ops import image as image_ops

    rng = np.random.default_rng(5)
    n = params.get("n", 4)
    h, w, c = params.get("h", 32), params.get("w", 48), params.get("c", 3)
    out_h, out_w = params.get("out_h", 8), params.get("out_w", 8)
    dtype = jnp.dtype(params.get("dtype", "float32"))
    scale, offset = params.get("scale"), params.get("offset")
    if jnp.issubdtype(dtype, jnp.integer):
        img = jnp.asarray(rng.integers(0, 256, (h, w, c)), dtype)
    else:
        img = jnp.asarray(rng.standard_normal((h, w, c)), dtype)
    boxes = _rand_boxes(rng, n, h, w)
    got = crop_and_resize(
        img, boxes, out_h, out_w, scale=scale, offset=offset,
        interpret=interpret_default(),
    )
    want = image_ops.crop_and_resize(
        img.astype(jnp.float32), boxes, out_h, out_w, impl="jnp"
    )
    if scale is not None:
        want = want * scale
    if offset is not None:
        want = want + offset
    if scale is None and offset is None:
        want = image_ops._round_clip_cast(want, dtype)
    return got, want, _interp_atol(dtype, h, w)


def _resize_run_case(params):
    import numpy as np

    from nnstreamer_tpu.ops import image as image_ops

    rng = np.random.default_rng(6)
    n = params.get("n", 2)
    h, w, c = params.get("h", 17), params.get("w", 23), params.get("c", 3)
    out_h, out_w = params.get("out_h", 8), params.get("out_w", 8)
    dtype = jnp.dtype(params.get("dtype", "float32"))
    if jnp.issubdtype(dtype, jnp.integer):
        img = jnp.asarray(rng.integers(0, 256, (n, h, w, c)), dtype)
    else:
        img = jnp.asarray(rng.standard_normal((n, h, w, c)), dtype)
    got = resize_bilinear(img, out_h, out_w, interpret=interpret_default())
    want = image_ops.resize_bilinear(img, out_h, out_w, impl="jnp")
    return got, want, _interp_atol(dtype, h, w)


def _crop_probe():
    import numpy as np

    from nnstreamer_tpu.ops import image as image_ops

    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.standard_normal((8, 8, 3)), jnp.float32)
    boxes = jnp.asarray([[1.0, 1.0, 6.0, 6.0]], jnp.float32)
    np.asarray(image_ops.crop_and_resize(img, boxes, 4, 4, impl="pallas"))


def _resize_probe():
    import numpy as np

    from nnstreamer_tpu.ops import image as image_ops

    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.standard_normal((8, 8, 3)), jnp.float32)
    np.asarray(image_ops.resize_bilinear(img, 4, 4, impl="pallas"))


_registry.register(_registry.KernelSpec(
    name="crop_and_resize",
    module=__name__,
    ops=("crop_and_resize",),
    dtypes=("float32", "bfloat16", "uint8"),
    cases=(
        _registry.ShapeCase("f32", {}, tier1=True),
        _registry.ShapeCase("uint8", {"dtype": "uint8"}, tier1=True),
        _registry.ShapeCase(
            "normalize-epilogue",
            {"scale": 1.0 / 255.0, "offset": -0.5},
            tier1=True,
        ),
        _registry.ShapeCase(
            "cam-720p-face",
            {"n": 8, "h": 720, "w": 1280, "out_h": 112, "out_w": 112},
        ),
    ),
    plan=_crop_plan,
    run_case=_crop_run_case,
    probe=_crop_probe,
))

_registry.register(_registry.KernelSpec(
    name="resize_bilinear",
    module=__name__,
    ops=("resize_bilinear",),
    dtypes=("float32", "bfloat16", "uint8"),
    cases=(
        _registry.ShapeCase("down", {}, tier1=True),
        _registry.ShapeCase(
            "up",
            {"n": 1, "h": 8, "w": 8, "out_h": 16, "out_w": 16},
            tier1=True,
        ),
        _registry.ShapeCase("uint8", {"dtype": "uint8"}),
        _registry.ShapeCase(
            "cam-720p-to-300",
            {"n": 1, "h": 720, "w": 1280, "out_h": 300, "out_w": 300},
        ),
    ),
    plan=_resize_plan,
    run_case=_resize_run_case,
    probe=_resize_probe,
))
