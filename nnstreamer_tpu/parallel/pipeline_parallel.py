"""Pipeline parallelism: GPipe-style microbatch rotation over a mesh axis.

The reference's only pipeline parallelism is streaming elements on threads
(SURVEY.md §2.6 item 1); on TPU the analogue for *model* pipelining is
stage-sharded layers with activations hopping stage→stage over ICI. Layers
live in a stacked pytree (leaves [L, ...], models/transformer.py layout);
sharding the leading dim over the ``pp`` axis gives every device a
contiguous stage slice. The schedule is the classic (M + S − 1)-tick loop:
each tick every stage runs one microbatch and ``ppermute`` hands its output
to the next stage — a bubble of (S−1)/(M+S−1), amortized by more
microbatches. The tick loop is a ``lax.scan``, so the same code path
differentiates for training.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward_local(
    stage_params,
    x,
    axis_name: str,
    stage_fn: Callable,
    n_microbatches: int,
):
    """Per-shard schedule (call inside shard_map).

    stage_params: this stage's layer slice (leaves [L/S, ...]).
    x: full input [N, ...] (replicated; stage 0 feeds it in), N = M * mb.
    stage_fn(x_mb, stage_params) → y_mb, same shape (homogeneous stages).
    Returns the full output [N, ...] (replicated via final psum).
    """
    s = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = n_microbatches
    n = x.shape[0]
    if n % m:
        raise ValueError(f"pipeline: batch {n} not divisible by {m} microbatches")
    x_mbs = x.reshape((m, n // m) + x.shape[1:])
    ticks = m + s - 1
    perm = [(i, i + 1) for i in range(s - 1)]

    def tick(recv, t):
        feed = x_mbs[jnp.clip(t, 0, m - 1)]
        inp = jnp.where(idx == 0, feed, recv)
        out = stage_fn(inp, stage_params)
        return jax.lax.ppermute(out, axis_name, perm), out

    init = jnp.zeros_like(x_mbs[0])
    _, outs = jax.lax.scan(tick, init, jnp.arange(ticks))
    # outs [ticks, mb, ...]; the last stage's microbatch j completes at
    # tick j + s - 1 → its valid stream is outs[s-1:]
    y = outs[s - 1 :]
    y = jnp.where(idx == s - 1, y, 0)
    y = jax.lax.psum(y, axis_name)  # only the last stage contributes
    return y.reshape((n,) + y.shape[2:])


def make_pipeline_forward(
    mesh: Mesh,
    stage_fn: Callable,
    n_microbatches: int,
    axis: str = "pp",
):
    """Jitted full-array entry: (stacked_params, x) → y.

    stacked_params leaves are [L, ...], sharded over ``axis`` on the
    leading dim; L must divide by the axis size. x and y are replicated.
    """
    fn = jax.shard_map(
        functools.partial(
            pipeline_forward_local,
            axis_name=axis,
            stage_fn=stage_fn,
            n_microbatches=n_microbatches,
        ),
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)
