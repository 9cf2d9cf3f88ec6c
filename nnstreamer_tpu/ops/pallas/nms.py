"""Greedy NMS suppression as a Pallas TPU kernel.

The sequential-suppression half of detection post-processing is the part
XLA handles poorly: the jnp reference (ops/detection.nms) materializes
the full N×N IoU matrix in HBM and walks it with a ``fori_loop``, so the
O(N²) pairwise work is paid in memory traffic before the loop even
starts. Here the kernel keeps the candidate list resident in VMEM as
four coordinate *rows* ([1, N] each — the block-masked layout) and, per
greedy step, computes ONE masked IoU row on the VPU against the live
mask, suppressing in place: no N×N buffer, no HBM round trips between
steps. The argsort ranking and the final top-k packing stay outside in
plain jnp (they're single XLA ops); only the data-dependent suppression
recurrence lives in the kernel.

Interpret-mode CPU fallback per ops/pallas/_compat.py discipline; bit
parity with ops/detection.nms is pinned by tests/test_ops_device.py
(identical ranking, identical suppression predicate, identical packing).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nnstreamer_tpu.ops.pallas import registry as _registry
from nnstreamer_tpu.ops.pallas._compat import interpret_default


# BlockSpec index map — module-level so the registered LaunchPlan and
# the live pallas_call share the SAME callable (grid (1,): the whole
# candidate list stays VMEM-resident across the greedy recurrence)
def _whole_index_map(i):
    return (0, 0)


def _pad_n(n: int) -> int:
    """Candidate count padded to whole 128-lane tiles."""
    return max(128, -(-n // 128) * 128)


def _nms_kernel(coords_ref, scores_ref, alive_ref, *, n: int, n_pad: int,
                thr: float):
    """coords [4, n_pad] rows (x1, y1, x2, y2) of score-ranked boxes,
    scores [1, n_pad] → alive [1, n_pad] float32 0/1 mask."""
    x1 = coords_ref[0:1, :]
    y1 = coords_ref[1:2, :]
    x2 = coords_ref[2:3, :]
    y2 = coords_ref[3:4, :]
    area = jnp.maximum(x2 - x1, 0.0) * jnp.maximum(y2 - y1, 0.0)  # [1, n_pad]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)
    alive_ref[:] = (scores_ref[:] > 0.0).astype(jnp.float32)

    def step(i, _):
        # the i-th ranked candidate's scalars by masked reduction: a
        # dynamic single-lane slice is something Mosaic cannot align,
        # and summing one selected lane with zeros is exact
        pivot = col == i

        def pick(row):
            return jnp.sum(jnp.where(pivot, row, 0.0), axis=1, keepdims=True)

        bx1, by1, bx2, by2 = pick(x1), pick(y1), pick(x2), pick(y2)
        barea = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
        iw = jnp.maximum(
            jnp.minimum(x2, bx2) - jnp.maximum(x1, bx1), 0.0
        )
        ih = jnp.maximum(
            jnp.minimum(y2, by2) - jnp.maximum(y1, by1), 0.0
        )
        inter = iw * ih
        union = area + barea - inter
        iou = jnp.where(union > 0.0, inter / union, 0.0)
        alive = alive_ref[:]
        keep_i = pick(alive)  # [1,1]: still live?
        suppress = (
            (iou > thr)
            & (col > i)
            & (keep_i > 0.0)
        )
        alive_ref[:] = jnp.where(suppress, 0.0, alive)
        return 0

    jax.lax.fori_loop(0, n, step, 0)


@functools.partial(
    jax.jit, static_argnames=("iou_threshold", "max_out", "interpret")
)
def nms(
    boxes: jax.Array,
    scores: jax.Array,
    iou_threshold: float,
    max_out: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for ops/detection.nms: boxes [N,4] x1,y1,x2,y2 + scores
    [N] → (keep_idx [max_out] int32, keep_score [max_out]); empty slots
    score 0 / index -1. Ranking and packing are the reference's exact
    jnp expressions, so the two implementations are bit-comparable."""
    n = boxes.shape[0]
    k = min(max_out, n)
    order = jnp.argsort(-scores)
    sboxes = boxes.astype(jnp.float32)[order]
    sscores = scores.astype(jnp.float32)[order]
    # lane-pad the candidate list; padded columns carry score 0 (never
    # alive, never selected) and zero-area boxes (suppress nothing)
    n_pad = _pad_n(n)
    coords = jnp.zeros((4, n_pad), jnp.float32)
    coords = coords.at[:, :n].set(sboxes.T)
    srow = jnp.zeros((1, n_pad), jnp.float32).at[0, :n].set(sscores)
    kernel = functools.partial(
        _nms_kernel, n=n, n_pad=n_pad, thr=float(iou_threshold)
    )
    alive_row = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        grid=(1,),
        in_specs=[
            pl.BlockSpec((4, n_pad), _whole_index_map),
            pl.BlockSpec((1, n_pad), _whole_index_map),
        ],
        out_specs=pl.BlockSpec((1, n_pad), _whole_index_map),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(coords, srow)
    alive = alive_row[0, :n] > 0.0
    # packing identical to the jnp reference (bit-comparable selection)
    kept_scores = jnp.where(alive, sscores, 0.0)
    top = jnp.argsort(-kept_scores)[:k]
    sel_scores = kept_scores[top]
    sel_idx = jnp.where(sel_scores > 0, order[top], -1)
    if k < max_out:
        sel_idx = jnp.pad(sel_idx, (0, max_out - k), constant_values=-1)
        sel_scores = jnp.pad(sel_scores, (0, max_out - k))
    # the jnp reference preserves the caller's score dtype (it never
    # casts); match it so impl="auto" traces the same output spec on
    # every backend
    return sel_idx.astype(jnp.int32), sel_scores.astype(scores.dtype)


# -- kernel registration (nns-kscope) ----------------------------------------


def _plan(params):
    n = params.get("n", 32)
    n_pad = _pad_n(n)
    return _registry.LaunchPlan(
        grid=(1,),
        blocks=(
            _registry.BlockDesc(
                "coords", "in", (4, n_pad), (4, n_pad), "float32",
                _whole_index_map,
            ),
            _registry.BlockDesc(
                "scores", "in", (1, n_pad), (1, n_pad), "float32",
                _whole_index_map,
            ),
            _registry.BlockDesc(
                "alive", "out", (1, n_pad), (1, n_pad), "float32",
                _whole_index_map,
            ),
        ),
        # one masked IoU row (~12 VPU ops/column) per greedy step
        flops=12 * n * n_pad,
        notes="sequential greedy recurrence; VPU-only (no MXU work)",
    )


def _boxes_scores(params):
    import numpy as np

    rng = np.random.default_rng(9)
    n = params.get("n", 32)
    xy = rng.uniform(0, 60, (n, 2))
    wh = rng.uniform(2, 30, (n, 2))
    boxes = jnp.asarray(np.concatenate([xy, xy + wh], -1), jnp.float32)
    scores = jnp.asarray(rng.uniform(0.05, 1.0, n), jnp.float32)
    return boxes, scores


def _run_case(params):
    from nnstreamer_tpu.ops import detection

    boxes, scores = _boxes_scores(params)
    thr = params.get("thr", 0.5)
    max_out = params.get("max_out", 8)
    got = nms(boxes, scores, thr, max_out, interpret=interpret_default())
    want = detection.nms(boxes, scores, thr, max_out, impl="jnp")
    # the two implementations are pinned bit-comparable (same ranking,
    # same suppression predicate, same packing)
    return got, want, 0.0


def _probe():
    import numpy as np

    from nnstreamer_tpu.ops import detection

    boxes = jnp.asarray(
        [[0, 0, 10, 10], [1, 1, 11, 11], [30, 30, 40, 40], [2, 2, 9, 9]],
        jnp.float32,
    )
    scores = jnp.asarray([0.9, 0.8, 0.7, 0.6], jnp.float32)
    idx, sc = detection.nms(boxes, scores, 0.5, 2, impl="pallas")
    np.asarray(idx), np.asarray(sc)


_registry.register(_registry.KernelSpec(
    name="nms",
    module=__name__,
    ops=("nms",),
    dtypes=("float32", "bfloat16"),
    cases=(
        _registry.ShapeCase("n32", {"n": 32}, tier1=True),
        _registry.ShapeCase("n100-pad128", {"n": 100, "max_out": 16}, tier1=True),
        _registry.ShapeCase("n200-pad256", {"n": 200, "max_out": 32}),
        _registry.ShapeCase("ssd-1917", {"n": 1917, "max_out": 100}),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
