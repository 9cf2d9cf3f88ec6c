"""A decode step's routed experts as one Pallas TPU kernel: the weights of
every held expert that some token picked stream once each, and no others.

An expert layer holds ``n`` experts (stacked ``[n, d, f]`` gate and up
projections, ``[n, f, d]`` down projections). A decode step's few tokens
(``T`` <= 128, the slots) pick a few of them each; together they touch most
of the held experts but rarely all (LongCat-Flash's 16 held: about 10 a
step; Kimi-Linear's 64: about 45). Per expert ``e`` picked by some token::

    y += gates[:, e] * ((silu(x Wg[e]) * (x Wu[e])) -> weights' dtype) Wd[e]

``gates [T, n]`` is a token's pick weight, 0 where it did not pick ``e``.
That is the arithmetic of ``models/kimi_linear.experts_dense`` (operands in
the weights' dtype, float32 accumulation, the gate applied after the down
projection), over the hit experts alone, and with no expert's output
written to HBM: the float32 accumulator ``[T, d]`` is the output block,
resident in VMEM for the whole grid and written once at the end.

Mechanics: grid ``(hit slot, f-tile)``. The compacted list of hit experts
and its length ride as scalar-prefetch operands; the weights' index maps
read the expert id from the list. Slots past its length repeat the last
real (expert, tile) block index, so Pallas fetches nothing new for them,
and their compute is skipped. ``f`` is cut into tiles so that the three
weight blocks of a step, double-buffered, fit the VMEM budget beside the
resident ``x`` and accumulator (:func:`f_tile`).
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

LANES = 128
# what the pipeline keeps resident a step: three weight blocks double-buffered,
# x, the accumulator and a gate column (the analyzer's default bound is 16 MiB)
VMEM_BUDGET = 16 << 20


def f_tile(t: int, d: int, f: int, itemsize: int, want: Optional[int] = None) -> int:
    """The widest tile of the expert width ``f`` (a multiple of 128 lanes
    that divides it, or ``f`` itself) at most ``want`` whose step fits
    ``VMEM_BUDGET``: 2 x 3 weight blocks of ``d x tile`` beside ``x`` [T, d]
    and the float32 accumulator [T, d]."""
    fixed = t * d * (itemsize + 4) + 2 * t * LANES * 4
    fits = [tf for tf in range(f, 0, -1) if f % tf == 0 and (tf % LANES == 0 or tf == f)
            and (want is None or tf <= want)]
    for tf in fits:
        if fixed + 6 * d * tf * itemsize <= VMEM_BUDGET:
            return tf
    return fits[-1]


def _kernel(ids_ref, count_ref, x_ref, g_ref, wg_ref, wu_ref, wd_ref, o_ref):
    slot, tile = pl.program_id(0), pl.program_id(1)

    @pl.when((slot == 0) & (tile == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(slot < count_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(wd_ref.dtype)
        o_ref[...] += g_ref[...] * jnp.dot(h, wd_ref[...],
                                           preferred_element_type=jnp.float32)


def _x_map(slot, tile, ids_ref, count_ref):
    return (0, 0)


def _weight_maps(tiles: int):
    """Index maps of the gate column and the three weight blocks: a slot
    past the list's length keeps the last real step's (expert, tile)."""
    def last(slot, tile, count_ref):
        return jnp.where(slot < count_ref[0], tile, tiles - 1)

    def gate(slot, tile, ids_ref, count_ref):
        return (ids_ref[slot], 0, 0)

    def inward(slot, tile, ids_ref, count_ref):        # Wg, Wu [n, d, f]
        return (ids_ref[slot], 0, last(slot, tile, count_ref))

    def down(slot, tile, ids_ref, count_ref):          # Wd [n, f, d]
        return (ids_ref[slot], last(slot, tile, count_ref), 0)

    return gate, inward, down


def hit_list(gates):
    """gates [T, n] -> (ids [n] int32: the experts some token picked, in
    order, then the last of them repeated (all 0 where none was picked),
    count [1] int32, sizes [n] int32 tokens with a nonzero gate a held
    expert)."""
    n = gates.shape[1]
    sizes = jnp.sum(gates != 0, axis=0).astype(jnp.int32)
    order = jnp.argsort(sizes == 0, stable=True).astype(jnp.int32)
    count = jnp.sum(sizes > 0).astype(jnp.int32)
    ids = jnp.where(jnp.arange(n) < count, order, order[jnp.maximum(count - 1, 0)])
    return ids, count[None], sizes


def gates_of(w, group, n: int):
    """A token's pick weights by held expert: w [T, k] the picks' weights,
    group [T, k] their held expert's index (``n`` where not held here) ->
    [T, n] float32, 0 where the token did not pick the expert."""
    return jnp.sum(jnp.where(group[..., None] == jnp.arange(n), w[..., None], 0.0),
                   axis=1)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def held_experts_ffn(x, gates, w_gate, w_up, w_down, *, tile: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """x [T, d] in the weights' dtype; gates [T, n] float32; w_gate, w_up
    [n, d, f], w_down [n, f, d] -> (y [T, d] float32, the gated sum of the
    experts some token picked; sizes [n] int32, tokens with a nonzero gate
    a held expert)."""
    t, d = x.shape
    n, _, f = w_gate.shape
    tf = f_tile(t, d, f, jnp.dtype(w_gate.dtype).itemsize, tile)
    if interpret is None:
        interpret = interpret_default()
    ids, count, sizes = hit_list(gates)
    gate_map, inward_map, down_map = _weight_maps(f // tf)
    item = jnp.dtype(w_gate.dtype).itemsize
    # the blocks double-buffered, and the step's temporaries: gate, up and h
    # [T, tile], the down projection's product [T, d] float32
    vmem = 2 * (3 * d * tf * item + t * d * (item + 4) + t * LANES * 4) \
        + 3 * t * tf * 4 + t * d * 4
    y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, f // tf),
            in_specs=[
                pl.BlockSpec((t, d), _x_map),
                pl.BlockSpec((None, t, 1), gate_map),
                pl.BlockSpec((None, d, tf), inward_map),
                pl.BlockSpec((None, d, tf), inward_map),
                pl.BlockSpec((None, tf, d), down_map),
            ],
            out_specs=pl.BlockSpec((t, d), _x_map),
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem + (4 << 20), 32 << 20)),
        interpret=interpret,
        name="held_experts_ffn",
    )(ids, count, x, gates.T[:, :, None], w_gate, w_up, w_down)
    return y, sizes


def held_experts_ffn_ref(x, gates, w_gate, w_up, w_down):
    """The plain form the kernel is pinned against, and the path where
    Pallas is switched off: every held expert over every token, weighted
    by its gate. Same arguments and results as :func:`held_experts_ffn`."""
    dt = w_gate.dtype
    gate = jax.nn.silu(jnp.einsum("td,edf->etf", x, w_gate,
                                  preferred_element_type=jnp.float32))
    up = jnp.einsum("td,edf->etf", x, w_up, preferred_element_type=jnp.float32)
    ys = jnp.einsum("etf,efd->etd", (gate * up).astype(dt), w_down,
                    preferred_element_type=jnp.float32)
    y = jnp.einsum("te,etd->td", gates, ys, precision=jax.lax.Precision.HIGHEST)
    return y, jnp.sum(gates != 0, axis=0).astype(jnp.int32)


# -- registry (nns-kscope) ---------------------------------------------------


def _case_geometry(params):
    """(t, d, f, n, the hit experts, dtype, tile)."""
    n = params.get("n", 4)
    return (params.get("t", 8), params.get("d", 128), params.get("f", 256), n,
            tuple(params.get("hit", range(n))), params.get("dtype", "float32"),
            params.get("tile"))


def _gates(t, n, hit, seed=13):
    """Gates [T, n] in which exactly the experts ``hit`` have a token (one
    or two each)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    g = np.zeros((t, n), np.float32)
    for j, e in enumerate(hit):
        g[j % t, e] = rng.uniform(0.1, 1.0)
        g[rng.integers(0, t), e] = rng.uniform(0.1, 1.0)
    return g


def _plan(params):
    import numpy as np

    t, d, f, n, hit, dtype, tile = _case_geometry(params)
    item = np.dtype(dtype).itemsize
    tf = f_tile(t, d, f, item, tile)
    gate_map, inward_map, down_map = _weight_maps(f // tf)
    blocks = (
        _registry.BlockDesc("x", "in", (t, d), (t, d), dtype, _x_map),
        _registry.BlockDesc("gates", "in", (n, t, 1), (1, t, 1), "float32", gate_map),
        _registry.BlockDesc("w_gate", "in", (n, d, f), (1, d, tf), dtype, inward_map),
        _registry.BlockDesc("w_up", "in", (n, d, f), (1, d, tf), dtype, inward_map),
        _registry.BlockDesc("w_down", "in", (n, f, d), (1, tf, d), dtype, down_map),
        _registry.BlockDesc("y", "out", (t, d), (t, d), "float32", _x_map),
    )
    hits = np.asarray(sorted(hit), np.int32)
    ids = np.concatenate([hits, np.full(n - len(hits), hits[-1] if len(hits) else 0,
                                        np.int32)])
    return _registry.LaunchPlan(
        grid=(n, f // tf),
        blocks=blocks,
        prefetch=(
            _registry.PrefetchDesc("ids", (n,), make=lambda: ids),
            _registry.PrefetchDesc("count", (1,),
                                   make=lambda: np.asarray([len(hits)], np.int32)),
        ),
        # x Wg, x Wu and h Wd over every token, for each hit expert
        flops=6 * t * d * f * len(hit),
        notes="slots past the hit list's length keep the last real block "
              "index (nothing fetched) and skip their body",
    )


def _run_case(params):
    import numpy as np

    t, d, f, n, hit, dtype, tile = _case_geometry(params)
    rng = np.random.default_rng(14)
    dt = jnp.dtype(dtype)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) / np.sqrt(shape[-2]), jnp.float32).astype(dt)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32).astype(dt)
    args = (x, jnp.asarray(_gates(t, n, hit)), draw(n, d, f), draw(n, d, f),
            draw(n, f, d))
    want = held_experts_ffn_ref(*args)
    got = held_experts_ffn(*args, tile=tile, interpret=interpret_default())
    pack = lambda y, sizes: jnp.concatenate(  # noqa: E731
        [y.reshape(-1), sizes.astype(jnp.float32)])
    return pack(*got), pack(*want), 2e-5 if dtype == "float32" else 2e-2


def _probe():
    from nnstreamer_tpu.ops.dispatch import record

    record("moe_experts", "pallas")
    _run_case({"t": 4, "n": 2, "hit": [1]})


_registry.register(_registry.KernelSpec(
    name="held_experts_ffn",
    module=__name__,
    ops=("moe_experts",),
    dtypes=("float32", "bfloat16"),
    cases=(
        # three of five experts hit, two f-tiles each: slots 3 and 4 are padding
        _registry.ShapeCase(
            "some-hit-two-tiles",
            {"t": 8, "d": 128, "f": 256, "n": 5, "hit": [4, 1, 2], "tile": 128},
            tier1=True,
        ),
        # every expert hit, a token count off the sublane tile
        _registry.ShapeCase(
            "all-hit", {"t": 5, "d": 128, "f": 128, "n": 3, "hit": [0, 1, 2]},
            tier1=True,
        ),
        # no expert hit: every slot skipped, y is zero
        _registry.ShapeCase(
            "none-hit", {"t": 4, "d": 128, "f": 128, "n": 3, "hit": []},
            tier1=True,
        ),
        # the benchmark cells' widths with fewer experts: LongCat-Flash's
        # 64 slots of 6144 over experts of 2048 (16 held, 10 of them hit a
        # step), Kimi-Linear's 128 slots of 2304 over experts of 1024
        _registry.ShapeCase(
            "cell-widths-longcat-flash-chat",
            {"t": 64, "d": 6144, "f": 2048, "n": 4, "hit": [3, 0], "dtype": "bfloat16"},
        ),
        _registry.ShapeCase(
            "cell-widths-kimi-linear-48b-a3b",
            {"t": 128, "d": 2304, "f": 1024, "n": 4, "hit": [2, 1, 3],
             "dtype": "bfloat16"},
        ),
    ),
    plan=_plan,
    run_case=_run_case,
    probe=_probe,
))
