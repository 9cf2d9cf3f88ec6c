"""Ulysses sequence parallelism: all-to-all head↔sequence re-sharding.

The second long-context strategy (DeepSpeed-Ulysses pattern, arXiv:2309.14509
— implementation original): instead of rotating K/V around a ring, one
``all_to_all`` re-shards [B, T/n, H, D] → [B, T, H/n, D], every device runs
*dense* attention over the full sequence for its heads, and a second
all_to_all restores sequence sharding. Two collectives total (vs n-1 ring
hops) at the cost of holding full-sequence K/V per head group — the right
trade when heads ≥ mesh axis and the sequence fits HBM; ring_attention is
the choice when it doesn't. Both share the same [B, T, H, D] layout, so the
transformer picks per config.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from nnstreamer_tpu.parallel.ring_attention import dense_attention


def ulysses_attention_local(
    q, k, v, axis_name: str, causal: bool = True,
    attn_fn: Optional[Callable] = None,
):
    """Per-shard computation: q/k/v [B, T_local, H, D] sequence-sharded →
    output with the same sharding. Requires H % axis_size == 0."""
    attn = attn_fn or dense_attention
    n = jax.lax.psum(1, axis_name)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"ulysses: heads {h} not divisible by axis size {n}")

    def seq_to_head(x):  # [B, T/n, H, D] → [B, T, H/n, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def head_to_seq(x):  # [B, T, H/n, D] → [B, T/n, H, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    o = attn(seq_to_head(q), seq_to_head(k), seq_to_head(v), causal=causal)
    return head_to_seq(o.astype(q.dtype)).astype(jnp.float32)


def make_ulysses_attention(mesh: Mesh, axis: str = "sp", causal: bool = True):
    """Jitted full-array entry matching make_ring_attention's signature."""
    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention_local, axis_name=axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)
