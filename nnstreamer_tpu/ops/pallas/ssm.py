"""One decode step of Mamba-2's selective state space over the per-slot state,
as a Pallas TPU kernel: each head's ``[P, N]`` state is read once and written
once, in place.

The Granite-4.0-H family (models/granite_hybrid.py) keeps, per slot, SSM layer
and head, a float32 matrix ``S`` (a slot leaf ``[SSM layers, n_slots + 1, H,
P, N]``; the last row is scratch). A decode step is::

    S' = a S + (dt x~) B^T        a, dt scalars a head; B, C shared by all heads
    y  = S' C + D x~

Written as XLA elementwise ops that is several passes over ``S`` (4 MB a
layer and slot at the published widths, a third of what a step must stream);
here one.

Mechanics: grid ``(lanes, head groups)``. The lanes' rows in the leaf and
their live flags ride as scalar-prefetch operands: a live lane's blocks are
``S[layer, row, group]``, a dead lane's are the scratch row's first group
(consecutive dead steps fetch nothing again) and its body is skipped, so its
own row is never touched. The state leaf is passed WHOLE and aliased to the
output (``layer`` is static). ``N`` lies on the lanes: ``B`` and ``C`` arrive
as rows ``[lanes, 2, N]``; what multiplies ``S`` along ``P`` (the decay and
``dt x~``) arrives with ``P`` on the sublanes, a head to two lanes (``[lanes,
groups, P, 2 Hb]``: one compact tile a lane and group), and ``y`` leaves the
same way (``[lanes, groups, P, Hb]``), so no transpose runs in the kernel. All
arithmetic is float32 on the vector unit.
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
from nnstreamer_tpu.ops.pallas.kda import heads_per_block

HEADS_PER_BLOCK = 32   # 1 MiB of state a block at P = 64, N = 128
_COLS = 2              # the decay, dt x~


def _kernel(row_ref, live_ref, cols_ref, bc_ref, s_ref, so_ref, y_ref, *, hb: int):
    live = live_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        bm, cm = bc_ref[0:1], bc_ref[1:2]                        # [1, N]
        for h in range(hb):
            decay = cols_ref[:, _COLS * h:_COLS * h + 1]         # [P, 1]
            dx = cols_ref[:, _COLS * h + 1:_COLS * h + 2]
            s = decay * s_ref[h] + dx * bm                       # [P, N]
            so_ref[h] = s
            y_ref[:, h:h + 1] = jnp.sum(s * cm, axis=1, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _lane_map(b, g, *_):
    return (b, g, 0, 0)


def _bc_map(b, g, *_):
    return (b, 0, 0)


def _state_map(layer: int):
    def index(b, g, row_ref, live_ref):
        return (layer, row_ref[b], jnp.where(live_ref[b] != 0, g, 0), 0, 0)

    return index


def _operands(x, bm, cm, dt, decay, hb: int):
    """The kernel's small operands from the step's vectors: ``cols`` [B, G,
    P, 2 Hb] (P on the sublanes) and ``bc`` [B, 2, N] (rows)."""
    b, h, p = x.shape
    cols = jnp.stack([jnp.broadcast_to(decay[..., None], x.shape),
                      dt[..., None] * x], axis=-1)                # [B, H, P, 2]
    cols = cols.reshape(b, h // hb, hb, p, _COLS).transpose(0, 1, 3, 2, 4)
    return cols.reshape(b, h // hb, p, hb * _COLS), jnp.stack([bm, cm], axis=1)


@functools.partial(jax.jit, static_argnames=("layer", "heads", "interpret"))
def ssm_decode_step(state, x, bm, cm, dt, decay, d_skip, active, *, layer: int,
                    heads: Optional[int] = None, interpret: Optional[bool] = None):
    """state [Ls, R + 1, H, P, N] float32 (row R is scratch; lane b is row
    b); x [B, H, P], bm, cm [B, N], dt, decay [B, H], d_skip [H] float32;
    active [B] bool -> (state with layer ``layer``'s rows of the live lanes
    advanced one token, y [B, H, P] float32, zero on dead lanes)."""
    b, h, p = x.shape
    n = bm.shape[-1]
    hb = heads_per_block(h, heads or HEADS_PER_BLOCK)
    if interpret is None:
        interpret = interpret_default()
    cols, bc = _operands(x, bm, cm, dt, decay, hb)
    live = active.astype(jnp.int32)
    rows = jnp.where(active, jnp.arange(b, dtype=jnp.int32), state.shape[1] - 1)
    state_spec = pl.BlockSpec((None, None, hb, p, n), _state_map(layer))
    state, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // hb),
            in_specs=[
                pl.BlockSpec((None, None, p, hb * _COLS), _lane_map),
                pl.BlockSpec((None, 2, n), _bc_map),
                state_spec,
            ],
            out_specs=[state_spec, pl.BlockSpec((None, None, p, hb), _lane_map)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, h // hb, p, hb), jnp.float32)],
        # operand 4 of the call (after the two prefetched vectors) is the state
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_decode_step",
    )(rows, live, cols, bc, state)
    y = y.transpose(0, 1, 3, 2).reshape(b, h, p)
    return state, y + jnp.where(active[:, None, None], d_skip[:, None] * x, 0.0)


def ssm_decode_step_ref(state, x, bm, cm, dt, decay, d_skip, active, *, layer: int):
    """The plain recurrence the kernel is pinned against, and the off-TPU
    path: same arguments and results as :func:`ssm_decode_step`."""
    b = x.shape[0]
    s0 = state[layer, :b]
    s1 = (decay[..., None, None] * s0
          + (dt[..., None] * x)[..., None] * bm[:, None, None, :])
    y = jnp.sum(s1 * cm[:, None, None, :], axis=-1) + d_skip[:, None] * x
    keep = active[:, None, None]
    state = state.at[layer, :b].set(jnp.where(keep[..., None], s1, s0))
    return state, jnp.where(keep, y, 0.0)


# -- registry (nns-kscope) ---------------------------------------------------


def _case_geometry(params):
    return (params.get("b", 4), params.get("h", 4), params.get("p", 8),
            params.get("n", 16), params.get("slots", params.get("b", 4)),
            params.get("layers", 2), params.get("heads"))


def _plan(params):
    import numpy as np

    b, h, p, n, slots, layers, heads = _case_geometry(params)
    hb = heads_per_block(h, heads or HEADS_PER_BLOCK)
    live = np.asarray(params.get("live", [1] * b), np.int32)
    layer = layers - 1
    state_index = _state_map(layer)
    shape = (layers, slots + 1, h, p, n)
    blocks = (
        _registry.BlockDesc("cols", "in", (b, h // hb, p, hb * _COLS),
                            (1, 1, p, hb * _COLS), "float32", _lane_map),
        _registry.BlockDesc("bc", "in", (b, 2, n), (1, 2, n), "float32", _bc_map),
        _registry.BlockDesc("state", "in", shape, (1, 1, hb, p, n), "float32",
                            state_index),
        _registry.BlockDesc("state_out", "out", shape, (1, 1, hb, p, n), "float32",
                            state_index),
        _registry.BlockDesc("y", "out", (b, h // hb, p, hb), (1, 1, p, hb),
                            "float32", _lane_map),
    )
    return _registry.LaunchPlan(
        grid=(b, h // hb),
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
    b, h, p, n, slots, layers, heads = _case_geometry(params)
    live = np.asarray(params.get("live", [1] * b), bool)
    state = rng.standard_normal((layers, slots + 1, h, p, n)).astype(np.float32)
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
        # live and dead lanes mixed, fewer lanes than rows, two head groups
        _registry.ShapeCase(
            "dead-lanes-two-groups",
            {"b": 5, "h": 16, "p": 8, "n": 16, "slots": 6, "layers": 3,
             "heads": 8, "live": [1, 0, 0, 1, 1]},
            tier1=True,
        ),
        _registry.ShapeCase(
            "one-group", {"b": 3, "h": 4, "p": 8, "n": 16, "live": [0, 1, 1]},
            tier1=True,
        ),
        # the benchmark's cell's widths: 128 heads of 64 x 128 in four groups
        # of 32 (its 64 lanes and 9 SSM layers are 2.4 GB of state: 8 and 2
        # here)
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
