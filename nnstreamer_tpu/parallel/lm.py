"""Sharded long-context LM training: dp × sp × ep over one mesh.

The composition layer: transformer LM (models/transformer.py) trained with
- **dp**: batch sharded over the data axis,
- **sp**: sequence sharded over the sequence axis; attention runs as an
  *inner shard_map* (ring_attention or ulysses) while everything else stays
  in the outer jit — XLA propagates shardings and inserts the grad
  collectives itself (the scaling-book recipe: annotate, don't hand-write
  collectives),
- **ep** (optional): MoE expert dim sharded via sharding constraints on the
  expert weights; the expert-combine einsum partitions over ``ep`` and XLA
  emits the psum.

This is the "full training step" the driver's dryrun compiles over a
virtual mesh; on hardware the same code lays dp/sp/ep onto ICI.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.parallel import moe as moe_mod
from nnstreamer_tpu.parallel.ring_attention import ring_attention_local
from nnstreamer_tpu.parallel.ulysses import ulysses_attention_local


def init_lm_params(
    key,
    vocab: int = 1024,
    d_model: int = 256,
    n_heads: int = 8,
    n_layers: int = 4,
    d_ff: Optional[int] = None,
    n_experts: int = 0,
    moe_d_ff: Optional[int] = None,
) -> Dict:
    """Transformer params; with n_experts > 0 the MoE leaves are merged
    into the stacked block pytree (moe_gate [L,D,E], moe_w_in [L,E,D,F],
    moe_w_out [L,E,F,D]) so one lax.scan drives both."""
    k1, k2 = jax.random.split(key)
    params = tfm.init_params(k1, vocab, d_model, n_heads, n_layers, d_ff)
    if n_experts > 0:
        mo = moe_mod.init_moe_params(
            k2, d_model, moe_d_ff or (d_ff or 4 * d_model) // 2, n_experts, n_layers
        )
        blocks = params["blocks"]
        # the dense MLP is replaced; drop its weights from the pytree
        for name in ("w_gate", "w_up", "w_down"):
            del blocks[name]
        blocks["moe_gate"] = mo["gate"]
        blocks["moe_w_in"] = mo["w_in"]
        blocks["moe_w_out"] = mo["w_out"]
    return params


def _make_attn_fn(mesh: Mesh, kind: str, dp_axis: str, sp_axis: str,
                  kv_chunk=None):
    local = {
        "ring": ring_attention_local,
        "ulysses": ulysses_attention_local,
    }[kind]
    if kv_chunk is not None and kind != "ring":
        raise ValueError(
            f"kv_chunk applies to attn='ring' only (got attn={kind!r}); "
            "ulysses gathers full sequences per head and has no chunked path"
        )
    extra = {"kv_chunk": kv_chunk} if kind == "ring" else {}
    spec = P(dp_axis, sp_axis, None, None)

    def attn(q, k, v, causal=True):
        return jax.shard_map(
            functools.partial(local, axis_name=sp_axis, causal=causal, **extra),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return attn


def _make_moe_ffn(mesh: Mesh, ep_axis: Optional[str], top_k: int):
    ep = P(ep_axis) if ep_axis else P()

    def ffn(y, blk):
        p = {
            "gate": blk["moe_gate"],
            "w_in": jax.lax.with_sharding_constraint(
                blk["moe_w_in"], NamedSharding(mesh, ep)
            ),
            "w_out": jax.lax.with_sharding_constraint(
                blk["moe_w_out"], NamedSharding(mesh, ep)
            ),
        }
        return moe_mod.moe_ffn_dense(y, p, top_k=top_k)

    return ffn


def loss_fn(params, tokens, n_heads, attn_fn=None, ffn_fn=None, compute_dtype=jnp.float32):
    """Next-token cross-entropy over tokens [B, T+1] (inputs = [:, :-1])."""
    logits = tfm.apply(
        params, tokens[:, :-1], n_heads, attn_fn, ffn_fn, compute_dtype
    )
    return jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(logits, tokens[:, 1:])
    )


def param_shardings(mesh: Mesh, params, ep_axis: Optional[str]) -> Dict:
    """Replicated everywhere except MoE expert weights (leading-L stacked,
    expert dim sharded over ep)."""
    repl = NamedSharding(mesh, P())

    def assign(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        if ep_axis and keys and str(keys[-1]).startswith("moe_w"):
            return NamedSharding(mesh, P(None, ep_axis))
        return repl

    return jax.tree_util.tree_map_with_path(assign, params)


def make_lm_train_step(
    mesh: Mesh,
    params: Dict,
    n_heads: int,
    attn: str = "ring",
    dp_axis: str = "dp",
    sp_axis: str = "sp",
    ep_axis: Optional[str] = None,
    top_k: int = 2,
    learning_rate: float = 0.1,
    compute_dtype=jnp.float32,
    kv_chunk=None,
) -> Tuple:
    """Returns (jitted_step, sharded_params). step(params, tokens) →
    (params, loss); tokens [B, T+1] sharded (dp, sp). ``kv_chunk`` bounds
    the in-shard attention score tensor for long contexts (ring only)."""
    attn_fn = _make_attn_fn(mesh, attn, dp_axis, sp_axis, kv_chunk=kv_chunk)
    is_moe = "moe_gate" in params["blocks"]
    ffn_fn = _make_moe_ffn(mesh, ep_axis, top_k) if is_moe else None
    p_shard = param_shardings(mesh, params, ep_axis)
    params = jax.device_put(params, p_shard)
    # tokens shard on batch only: [B, T+1] has a ragged +1 on the sequence
    # dim, so sequence sharding starts at the attention boundary (the inner
    # shard_map's in_specs make XLA reshard q/k/v to (dp, sp) there and
    # propagate outward)
    tok_shard = NamedSharding(mesh, P(dp_axis))
    repl = NamedSharding(mesh, P())

    @functools.partial(
        jax.jit,
        in_shardings=(p_shard, tok_shard),
        out_shardings=(p_shard, repl),
        donate_argnums=(0,),
    )
    def step(params, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, n_heads, attn_fn, ffn_fn, compute_dtype
        )
        params = jax.tree_util.tree_map(
            lambda p, g: p - learning_rate * g.astype(p.dtype), params, grads
        )
        return params, loss

    return step, params
