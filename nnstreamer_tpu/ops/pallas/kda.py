"""One decode step of Kimi Delta Attention's recurrence over the per-slot state,
as a Pallas TPU kernel: each head's ``[d_k, d_v]`` state is read once and
written once, in place.

The Kimi-Linear family (models/kimi_linear.py) keeps, per slot, KDA layer and
head, a float32 matrix ``S`` (a slot leaf ``[KDA layers, n_slots + 1, H, d_k,
d_v]``; the last row is scratch). A decode step is::

    S' = Diag(alpha) S + k (beta (v - (k * alpha)^T S))^T
    o  = S'^T q = (q * alpha)^T S + (q . k) beta (v - (k * alpha)^T S)

Written as XLA elementwise ops that is several passes over ``S`` (14.7 MB a
slot at the published widths, a third of what a step must stream); here one.

Mechanics: grid ``(lanes, head groups)``. The lanes' rows in the leaf and
their live flags ride as scalar-prefetch operands: a live lane's blocks are
``S[layer, row, group]``, a dead lane's are the scratch row's first group
(consecutive dead steps fetch nothing again) and its body is skipped, so its
own row is never touched. The state leaf is passed WHOLE and aliased to the
output (``layer`` is static). What multiplies ``S`` along ``d_k`` (alpha, k,
k alpha, q alpha) arrives with ``d_k`` on the sublanes, a head to four lanes
(``[lanes, groups, d_k, 4 Hb]``: one compact tile a lane and group), so no
transpose runs in the kernel; what lies along ``d_v`` (v, and beta and q.k
broadcast) arrives as rows. All arithmetic is float32 on the vector unit.
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

HEADS_PER_BLOCK = 16   # 1 MiB of state a block at d_k = d_v = 128
_COLS = 4              # alpha, k, k alpha, q alpha


def _kernel(row_ref, live_ref, cols_ref, vec_ref, s_ref, so_ref, o_ref, *, hb: int):
    live = live_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        for h in range(hb):
            s = s_ref[h]                                     # [dk, dv]
            col = lambda j: cols_ref[:, _COLS * h + j:_COLS * h + j + 1]  # noqa: E731
            v, beta, qk = vec_ref[0, h:h + 1], vec_ref[1, h:h + 1], vec_ref[2, h:h + 1]
            delta = beta * (v - jnp.sum(col(2) * s, axis=0, keepdims=True))
            o_ref[h:h + 1] = jnp.sum(col(3) * s, axis=0, keepdims=True) + qk * delta
            so_ref[h] = col(0) * s + col(1) * delta

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _lane_map(b, g, *_):
    return (b, g, 0, 0)


def _vec_map(b, g, *_):
    return (b, 0, g, 0)


def _out_map(b, g, *_):
    return (b, g, 0)


def _state_map(layer: int):
    def index(b, g, row_ref, live_ref):
        return (layer, row_ref[b], jnp.where(live_ref[b] != 0, g, 0), 0, 0)

    return index


def heads_per_block(h: int, want: Optional[int] = None) -> int:
    """The largest divisor of ``h`` that is at most ``want`` and a whole
    number of sublane tiles (or all of ``h``)."""
    want = min(h, want or HEADS_PER_BLOCK)
    for hb in range(want, 0, -1):
        if h % hb == 0 and (hb % 8 == 0 or hb == h):
            return hb
    return h


def _operands(q, k, v, alpha, beta, hb: int):
    """The kernel's small operands from the step's vectors: ``cols``
    [B, G, dk, 4 Hb] (d_k on the sublanes) and ``vec`` [B, 3, H, dv] (rows)."""
    b, h, dk = q.shape
    cols = jnp.stack([alpha, k, k * alpha, q * alpha], axis=-1)   # [B, H, dk, 4]
    cols = cols.reshape(b, h // hb, hb, dk, _COLS).transpose(0, 1, 3, 2, 4)
    cols = cols.reshape(b, h // hb, dk, hb * _COLS)
    qk = jnp.sum(q * k, axis=-1, keepdims=True)
    vec = jnp.stack([v, jnp.broadcast_to(beta[..., None], v.shape),
                     jnp.broadcast_to(qk, v.shape)], axis=1)
    return cols, vec


@functools.partial(jax.jit, static_argnames=("layer", "heads", "interpret"))
def kda_decode_step(state, q, k, v, alpha, beta, active, *, layer: int,
                    heads: Optional[int] = None, interpret: Optional[bool] = None):
    """state [Lk, N + 1, H, dk, dv] float32 (row N is scratch; lane b is row
    b); q, k, alpha [B, H, dk], v [B, H, dv], beta [B, H] float32; active [B]
    bool -> (state with layer ``layer``'s rows of the live lanes advanced one
    token, o [B, H, dv] float32, zero on dead lanes)."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    hb = heads_per_block(h, heads)
    if interpret is None:
        interpret = interpret_default()
    cols, vec = _operands(q, k, v, alpha, beta, hb)
    live = active.astype(jnp.int32)
    rows = jnp.where(active, jnp.arange(b, dtype=jnp.int32), state.shape[1] - 1)
    state_spec = pl.BlockSpec((None, None, hb, dk, dv), _state_map(layer))
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // hb),
            in_specs=[
                pl.BlockSpec((None, None, dk, hb * _COLS), _lane_map),
                pl.BlockSpec((None, 3, hb, dv), _vec_map),
                state_spec,
            ],
            out_specs=[state_spec, pl.BlockSpec((None, hb, dv), _out_map)],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, h, dv), jnp.float32)],
        # operand 4 of the call (after the two prefetched vectors) is the state
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_step",
    )(rows, live, cols, vec, state)


def kda_decode_step_ref(state, q, k, v, alpha, beta, active, *, layer: int):
    """The plain recurrence the kernel is pinned against, and the off-TPU
    path: same arguments and results as :func:`kda_decode_step`."""
    b = q.shape[0]
    s0 = state[layer, :b]
    s1 = alpha[..., None] * s0
    u = jnp.sum(k[..., None] * s1, axis=-2)
    s1 = s1 + (beta[..., None] * k)[..., None] * (v - u)[:, :, None, :]
    o = jnp.sum(q[..., None] * s1, axis=-2)
    keep = active[:, None, None]
    state = state.at[layer, :b].set(jnp.where(keep[..., None], s1, s0))
    return state, jnp.where(keep, o, 0.0)


# -- registry (nns-kscope) ---------------------------------------------------


def _case_geometry(params):
    return (params.get("b", 4), params.get("h", 4), params.get("dk", 16),
            params.get("dv", 16), params.get("slots", params.get("b", 4)),
            params.get("layers", 2), params.get("heads"))


def _plan(params):
    import numpy as np

    b, h, dk, dv, slots, layers, heads = _case_geometry(params)
    hb = heads_per_block(h, heads)
    live = np.asarray(params.get("live", [1] * b), np.int32)
    layer = layers - 1
    state_index = _state_map(layer)
    shape = (layers, slots + 1, h, dk, dv)
    blocks = (
        _registry.BlockDesc("cols", "in", (b, h // hb, dk, hb * _COLS),
                            (1, 1, dk, hb * _COLS), "float32", _lane_map),
        _registry.BlockDesc("vec", "in", (b, 3, h, dv), (1, 3, hb, dv), "float32",
                            _vec_map),
        _registry.BlockDesc("state", "in", shape, (1, 1, hb, dk, dv), "float32",
                            state_index),
        _registry.BlockDesc("state_out", "out", shape, (1, 1, hb, dk, dv), "float32",
                            state_index),
        _registry.BlockDesc("o", "out", (b, h, dv), (1, hb, dv), "float32", _out_map),
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
        # per state element: the decay, k^T S, the rank-1 update, S^T q (1+2+2+2)
        flops=7 * dk * dv * h * int(live.sum()),
        notes="the state leaf is aliased to the output; a dead lane maps to "
              "the scratch row and its body is skipped",
    )


def _run_case(params):
    import numpy as np

    rng = np.random.default_rng(11)
    b, h, dk, dv, slots, layers, heads = _case_geometry(params)
    live = np.asarray(params.get("live", [1] * b), bool)
    state = rng.standard_normal((layers, slots + 1, h, dk, dv)).astype(np.float32)
    q, k, alpha = (rng.standard_normal((b, h, dk)).astype(np.float32) for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)   # as the layer makes them:
    q /= dk                                          # unit k, q of norm d_k^-1/2
    args = (jnp.asarray(q), jnp.asarray(k),
            jnp.asarray(rng.standard_normal((b, h, dv)), jnp.float32),
            jnp.asarray(np.exp(-np.abs(alpha))),
            jnp.asarray(rng.uniform(size=(b, h)), jnp.float32), jnp.asarray(live))
    want = kda_decode_step_ref(jnp.asarray(state), *args, layer=layers - 1)
    got = kda_decode_step(jnp.asarray(state), *args, layer=layers - 1, heads=heads,
                          interpret=interpret_default())
    # everything but the scratch row, which a dead lane may leave anything in
    pack = lambda s, o: jnp.concatenate(  # noqa: E731
        [s[:, :slots].reshape(-1), o.reshape(-1)])
    return pack(*got), pack(*want), 2e-5


def _probe():
    from nnstreamer_tpu.ops.dispatch import record

    record("kda_recurrence", "pallas")
    _run_case({"b": 2, "h": 2})


_registry.register(_registry.KernelSpec(
    name="kda_decode_step",
    module=__name__,
    ops=("kda_recurrence",),
    dtypes=("float32",),
    cases=(
        # live and dead lanes mixed, fewer lanes than rows, two head groups
        _registry.ShapeCase(
            "dead-lanes-two-groups",
            {"b": 5, "h": 16, "dk": 16, "dv": 16, "slots": 6, "layers": 3,
             "heads": 8, "live": [1, 0, 0, 1, 1]},
            tier1=True,
        ),
        _registry.ShapeCase(
            "one-group", {"b": 3, "h": 4, "dk": 8, "dv": 16, "live": [0, 1, 1]},
            tier1=True,
        ),
        # the benchmark's cell's widths: 32 heads of 128 x 128 in two groups
        # of 16 (its 128 lanes and 7 KDA layers are 14.8 GB of state: 8 and 2
        # here)
        _registry.ShapeCase(
            "cell-widths-kimi-linear",
            {"b": 8, "h": 32, "dk": 128, "dv": 128, "slots": 8, "layers": 2,
             "live": [1, 1, 0, 1, 1, 1, 0, 1]},
        ),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
