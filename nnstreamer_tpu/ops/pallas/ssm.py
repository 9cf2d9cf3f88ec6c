"""One decode step of Mamba-2's selective state space over the per-slot state,
as a Pallas TPU kernel: each head's state is read once and written once, in
place, at close to what the memory allows.

The Granite-4.0-H family (models/granite_hybrid.py) keeps, per slot, SSM layer
and head, a float32 matrix ``S`` of ``P`` channels by ``N`` states. A decode
step is::

    S' = a S + (dt x~) B^T        a, dt scalars a head; B, C shared by all heads
    y  = S' C + D x~

Written as XLA elementwise ops that is several passes over ``S`` (4 MB a
layer and slot at the published widths, a third of what a step must stream);
here one.

The leaf's layout is chosen for this kernel: ``[SSM layers, n_slots + 1, H / k,
N, k P]`` (the last row is scratch), ``N`` on the sublanes and ``k`` heads side
by side on the lanes (:func:`heads_per_row`: 2 at the published ``P = 64``, so
a tile is 128 x 128 and pads nothing). What multiplies ``S`` along the
channels (the decay, ``dt x~``) is then a ROW that broadcasts over sublanes,
``B`` and ``C`` are two ``[N, 1]`` columns broadcast over the lanes ONCE a
grid step for every head in it, ``S' C`` is a sum over sublanes (elementwise
adds of whole vregs and one fold), and ``y`` leaves as a full-lane row. With
``N`` on the lanes (a leaf ``[H, P, N]``) every head pays two column
broadcasts, eight 128-lane reductions and a one-lane-wide store, and the
vector and cross-lane units, not the DMA, bound the kernel: 503 GB/s against
649 on a v5e, which is what XLA's own in-place pass over the same rows reads
(PERF.md section 6, PR 38). :func:`state_to_leaf` / :func:`leaf_to_state`
are the layout's two directions (the prompt's scan keeps ``[H, P, N]``).

Mechanics: grid ``(lanes, head blocks)``. The lanes' rows in the leaf and
their live flags ride as scalar-prefetch operands: a live lane's blocks are
``S[layer, row, block]``, a dead lane's are the scratch row's first block
(consecutive dead steps fetch nothing again) and its body is skipped, so its
own row is never touched. The state leaf is passed WHOLE and aliased to the
output (``layer`` is static). The decay and ``dt x~`` arrive as rows ``[lanes,
blocks, 2, Gb, k P]`` and ``y`` leaves as ``[lanes, blocks, Gb, k P]``: both
reshapes of ``[lanes, H, P]``, no transpose. All arithmetic is float32 on the
vector unit.
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

HEADS_PER_BLOCK = 32   # 1 MiB of state a block at P = 64, N = 128
LANES = 128            # a vreg's width: what a row of the leaf fills


def _largest_divisor(x: int, most: int) -> int:
    """The largest divisor of ``x`` that is at most ``most`` (and at least 1)."""
    return next(d for d in range(max(1, min(x, most)), 0, -1) if x % d == 0)


def heads_per_row(h: int, p: int) -> int:
    """How many heads lie side by side on the lanes of the leaf: as many as
    fill a vreg's width (``128 // p``) where that divides ``h``, else the
    largest divisor of ``h`` under it; 1 from ``p`` = 128 up."""
    return _largest_divisor(h, LANES // p)


def _rows_per_block(g: int, k: int, heads: Optional[int]) -> int:
    """Rows of ``k`` heads a grid step takes: ``heads`` (``HEADS_PER_BLOCK``)
    heads' worth, cut to a divisor of the leaf's ``g`` rows."""
    return _largest_divisor(g, (heads or HEADS_PER_BLOCK) // k)


def state_to_leaf(s, k: int):
    """[..., H, P, N] -> the leaf's [..., H / k, N, k P]."""
    *lead, h, p, n = s.shape
    s = s.reshape(*lead, h // k, k, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // k, n, k * p)


def leaf_to_state(leaf, k: int):
    """The leaf's [..., H / k, N, k P] -> [..., H, P, N]."""
    *lead, g, n, kp = leaf.shape
    s = leaf.reshape(*lead, g, n, k, kp // k)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, g * k, kp // k, n)


def _kernel(row_ref, live_ref, rows_ref, bc_ref, s_ref, so_ref, y_ref, *, gb: int):
    live = live_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        tile = s_ref.shape[1:]
        bm = jnp.broadcast_to(bc_ref[:, 0:1], tile)              # [N, k P]
        cm = jnp.broadcast_to(bc_ref[:, 1:2], tile)
        for t in range(gb):
            decay, dx = rows_ref[0, t:t + 1], rows_ref[1, t:t + 1]   # [1, k P]
            s = decay * s_ref[t] + bm * dx
            so_ref[t] = s
            y_ref[t:t + 1] = jnp.sum(s * cm, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _lane_map(b, g, *_):
    return (b, g, 0, 0)


def _rows_map(b, g, *_):
    return (b, g, 0, 0, 0)


def _bc_map(b, g, *_):
    return (b, 0, 0)


def _state_map(layer: int):
    def index(b, g, row_ref, live_ref):
        return (layer, row_ref[b], jnp.where(live_ref[b] != 0, g, 0), 0, 0)

    return index


def _operands(x, bm, cm, dt, decay, gb: int, kp: int):
    """The kernel's small operands from the step's vectors: ``rows`` [B, G /
    Gb, 2, Gb, k P] (the decay over its head's channels, then dt x~: each a
    reshape of [B, H, P]) and ``bc`` [B, N, 2] (columns)."""
    b = x.shape[0]
    rows = jnp.stack([jnp.broadcast_to(decay[..., None], x.shape).reshape(b, -1, gb, kp),
                      (dt[..., None] * x).reshape(b, -1, gb, kp)], axis=2)
    return rows, jnp.stack([bm, cm], axis=-1)


@functools.partial(jax.jit, static_argnames=("layer", "heads", "interpret"))
def ssm_decode_step(state, x, bm, cm, dt, decay, d_skip, active, *, layer: int,
                    heads: Optional[int] = None, interpret: Optional[bool] = None):
    """state [Ls, R + 1, H / k, N, k P] float32 (row R is scratch; lane b is
    row b; ``k`` is read off the shapes); x [B, H, P], bm, cm [B, N], dt,
    decay [B, H], d_skip [H] float32; active [B] bool -> (state with layer
    ``layer``'s rows of the live lanes advanced one token, y [B, H, P]
    float32, zero on dead lanes)."""
    b, h, p = x.shape
    g, n, kp = state.shape[2:]
    gb = _rows_per_block(g, h // g, heads)
    if interpret is None:
        interpret = interpret_default()
    rows, bc = _operands(x, bm, cm, dt, decay, gb, kp)
    live = active.astype(jnp.int32)
    lane_rows = jnp.where(active, jnp.arange(b, dtype=jnp.int32), state.shape[1] - 1)
    state_spec = pl.BlockSpec((None, None, gb, n, kp), _state_map(layer))
    state, y = pl.pallas_call(
        functools.partial(_kernel, gb=gb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, g // gb),
            in_specs=[
                pl.BlockSpec((None, None, 2, gb, kp), _rows_map),
                pl.BlockSpec((None, n, 2), _bc_map),
                state_spec,
            ],
            out_specs=[state_spec, pl.BlockSpec((None, None, gb, kp), _lane_map)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, g // gb, gb, kp), jnp.float32)],
        # operand 4 of the call (after the two prefetched vectors) is the state
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_decode_step",
    )(lane_rows, live, rows, bc, state)
    y = y.reshape(b, h, p)
    return state, y + jnp.where(active[:, None, None], d_skip[:, None] * x, 0.0)


def ssm_decode_step_ref(state, x, bm, cm, dt, decay, d_skip, active, *, layer: int):
    """The plain recurrence the kernel is pinned against, and the off-TPU
    path: same arguments (the same leaf) and results as
    :func:`ssm_decode_step`."""
    b, h, p = x.shape
    g, _, kp = state.shape[2:]
    row = lambda m: m.reshape(b, g, 1, kp)  # noqa: E731  [B, H, P] -> over N
    s0 = state[layer, :b]
    s1 = (row(jnp.broadcast_to(decay[..., None], x.shape)) * s0
          + bm[:, None, :, None] * row(dt[..., None] * x))
    y = jnp.sum(s1 * cm[:, None, :, None], axis=2).reshape(b, h, p) + d_skip[:, None] * x
    keep = active[:, None, None]
    state = state.at[layer, :b].set(jnp.where(keep[..., None], s1, s0))
    return state, jnp.where(keep, y, 0.0)


# -- registry (nns-kscope) ---------------------------------------------------


def _case_geometry(params):
    """(b, h, p, n, slots, layers, heads, the leaf's shape)."""
    b, h, p, n = (params.get("b", 4), params.get("h", 4), params.get("p", 8),
                  params.get("n", 16))
    slots, layers = params.get("slots", b), params.get("layers", 2)
    k = heads_per_row(h, p)
    return (b, h, p, n, slots, layers, params.get("heads"),
            (layers, slots + 1, h // k, n, k * p))


def _plan(params):
    import numpy as np

    b, h, p, n, slots, layers, heads, shape = _case_geometry(params)
    g, kp = shape[2], shape[4]
    gb = _rows_per_block(g, h // g, heads)
    live = np.asarray(params.get("live", [1] * b), np.int32)
    state_index = _state_map(layers - 1)
    blocks = (
        _registry.BlockDesc("rows", "in", (b, g // gb, 2, gb, kp),
                            (1, 1, 2, gb, kp), "float32", _rows_map),
        _registry.BlockDesc("bc", "in", (b, n, 2), (1, n, 2), "float32", _bc_map),
        _registry.BlockDesc("state", "in", shape, (1, 1, gb, n, kp), "float32",
                            state_index),
        _registry.BlockDesc("state_out", "out", shape, (1, 1, gb, n, kp), "float32",
                            state_index),
        _registry.BlockDesc("y", "out", (b, g // gb, gb, kp), (1, 1, gb, kp),
                            "float32", _lane_map),
    )
    return _registry.LaunchPlan(
        grid=(b, g // gb),
        blocks=blocks,
        prefetch=(
            _registry.PrefetchDesc(
                "rows", (b,),
                make=lambda: np.where(live > 0, np.arange(b), slots).astype(np.int32)),
            _registry.PrefetchDesc("live", (b,), make=lambda: live),
        ),
        # per state element: the decay, the rank-1 update, S C (1 + 2 + 2)
        flops=5 * p * n * h * int(live.sum()),
        notes="the state leaf is aliased to the output; a dead lane maps to "
              "the scratch row and its body is skipped",
    )


def _run_case(params):
    import numpy as np

    rng = np.random.default_rng(12)
    b, h, p, n, slots, layers, heads, shape = _case_geometry(params)
    live = np.asarray(params.get("live", [1] * b), bool)
    state = rng.standard_normal(shape).astype(np.float32)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    args = (f32(rng.standard_normal((b, h, p))), f32(rng.standard_normal((b, n))),
            f32(rng.standard_normal((b, n))), f32(rng.uniform(1e-3, 0.1, (b, h))),
            f32(np.exp(-rng.uniform(0.0, 1.6, (b, h)))), f32(np.ones((h,))),
            jnp.asarray(live))
    want = ssm_decode_step_ref(jnp.asarray(state), *args, layer=layers - 1)
    got = ssm_decode_step(jnp.asarray(state), *args, layer=layers - 1, heads=heads,
                          interpret=interpret_default())
    # everything but the scratch row, which a dead lane may leave anything in
    pack = lambda s, y: jnp.concatenate(  # noqa: E731
        [s[:, :slots].reshape(-1), y.reshape(-1)])
    return pack(*got), pack(*want), 2e-5


def _probe():
    from nnstreamer_tpu.ops.dispatch import record

    record("ssm_recurrence", "pallas")
    _run_case({"b": 2, "h": 2})


_registry.register(_registry.KernelSpec(
    name="ssm_decode_step",
    module=__name__,
    ops=("ssm_recurrence",),
    dtypes=("float32",),
    cases=(
        # live and dead lanes mixed, fewer lanes than rows, two head blocks of
        # two rows of four heads each
        _registry.ShapeCase(
            "dead-lanes-two-groups",
            {"b": 5, "h": 16, "p": 32, "n": 16, "slots": 6, "layers": 3,
             "heads": 8, "live": [1, 0, 0, 1, 1]},
            tier1=True,
        ),
        # all four heads on one row of 32 lanes
        _registry.ShapeCase(
            "one-group", {"b": 3, "h": 4, "p": 8, "n": 16, "live": [0, 1, 1]},
            tier1=True,
        ),
        # a head as wide as a vreg lies alone on its row
        _registry.ShapeCase(
            "one-head-a-row",
            {"b": 2, "h": 3, "p": 128, "n": 8, "heads": 1, "live": [1, 1]},
            tier1=True,
        ),
        # the benchmark's cell's widths: 128 heads of 64 x 128, two a row, in
        # four blocks of 32 (its 64 lanes and 9 SSM layers are 2.4 GB of
        # state: 8 and 2 here)
        _registry.ShapeCase(
            "cell-widths-granite-4.0-h-small",
            {"b": 8, "h": 128, "p": 64, "n": 128, "slots": 8, "layers": 2,
             "live": [1, 1, 0, 1, 1, 1, 0, 1]},
        ),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
