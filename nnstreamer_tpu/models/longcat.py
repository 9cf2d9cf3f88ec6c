"""LongCat-Flash block family: latent (MLA) attention, the shortcut-connected
double layer, and one chip's share of the routed experts with identity
("zero-compute") experts.

One layer, ``h`` the float32 residual, every norm an RMSNorm::

    for i in (0, 1):
        a = norm_in[i](h);    h = h + MLA[i](a)
        b = norm_post[i](h)
        if i == 0:  s = MoE(b)         # the shortcut, added after the second FFN
        h = h + FFN[i](b)
    h = h + s

What is cached per token and attention sublayer is the latent: the normed,
scaled ``c`` (``kv_rank`` values) and the rotated shared key ``k_r`` (``rope``
values) — two arena leaves ``[2L, N, bs, kv_rank]`` and ``[2L, N, bs, rope
padded to full lanes]``, no V, no heads. Prefill expands K and V from the
latents; decode and chunked prefill read the cache in the absorbed form
(``q' = q_nope W_kvb,k^T``, score ``q'.c + q_rope.k_r``,
``o = (sum p c) W_kvb,v W_o``).

The expert layer is told which experts it holds (``n_held`` from
``expert_offset``): it routes over every router output (softmax in float32 at
highest precision, top-k of ``p + bias``, weights ``scale * p``), sorts the
pairs that fall on its own experts by expert, runs one grouped matmul over the
experts held (no capacity, no token dropped; pairs of absent experts and of
dead tokens form the tail no group covers) and adds the identity experts'
``(sum w) b``. What the absent experts would have added is left out. A
decode step's few tokens instead stream each held expert that some token
picked once, through ``ops/pallas/held_experts.held_experts_ffn``.

Storage dtype is stated by the caller: weights and latent cache are held in
``dtype`` (bfloat16 as served, float32 in the CPU tests); residual, norms,
softmax and router are float32; every other contraction takes operands in
``dtype`` and accumulates in float32. Weights follow the recipe of
``benchmark/reference/longcat_flash.py`` (normal draws in float32, one key per
tensor, rounded once to ``dtype``); the two modules share no code.

``LongcatFamily`` is what ``ContinuousBatcher`` serves it through
(models/family.py).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from nnstreamer_tpu.ops.dispatch import record
from nnstreamer_tpu.ops.pallas._compat import pallas_ok
from nnstreamer_tpu.ops.pallas.held_experts import (
    gates_of,
    held_experts_ffn,
    held_experts_ffn_ref,
)

NEG_INF = -1e30
LANES = 128
# the counters one decode step adds up on the device (nns.moe.routing)
MOE_STATS = ("tokens", "local_pairs", "experts_hit", "zero_picks", "picks")

# key schedule and scales of ``seed:<n>`` (stated in the configuration's file)
_WQA, _WQB, _WKVA, _WKVB, _WO, _FFN_GATE, _FFN_UP, _FFN_DOWN = range(1, 9)
_ROUTER, _ROUTER_BIAS, _EXP_GATE, _EXP_UP, _EXP_DOWN = 20, 21, 30, 31, 32
_EMBED, _HEAD, _LAYERS = 1, 2, 3
_EMBED_STD, _ROUTER_GAIN, _ROUTER_BIAS_STD = 0.02, 2.0, 1e-3


@dataclass(frozen=True)
class LongcatConfig:
    """Published widths by default; ``n_layers``, ``n_held`` and ``vocab``
    are the chip's share."""

    d_model: int = 6144
    n_heads: int = 64
    q_rank: int = 1536
    kv_rank: int = 512
    nope: int = 128
    rope: int = 64
    v_dim: int = 128
    d_ff: int = 12288
    d_expert: int = 2048
    n_routed: int = 512
    n_zero: int = 256
    topk: int = 12
    scale: float = 6.0
    theta: float = 1e7
    eps: float = 1e-5
    n_layers: int = 28
    vocab: int = 131072
    n_held: int = 512
    expert_offset: int = 0

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.n_routed - self.n_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + self.n_held})"
                f" are not among the {self.n_routed} routed experts"
            )
        if self.n_held < 1:
            raise ValueError("experts_held must be at least 1")
        if self.rope % 2:
            raise ValueError("rope head dim must be even")

    @property
    def kr_width(self) -> int:
        """Lanes of the k_r arena leaf: ``rope`` padded to whole lanes (the
        TPU tiles the minor dim to 128 anyway; the kernel DMAs whole lanes)."""
        return -(-self.rope // LANES) * LANES


def config_from_options(options: Dict[str, str]) -> LongcatConfig:
    """``custom=`` of ``zoo:longcat_flash_lm``: every width by its short name,
    ``n_layers``, ``experts_held``, ``expert_offset``, ``vocab``."""
    names = {
        "d_model": int, "n_heads": int, "q_rank": int, "kv_rank": int,
        "nope": int, "rope": int, "v_dim": int, "d_ff": int, "d_expert": int,
        "n_routed": int, "n_zero": int, "topk": int, "scale": float,
        "theta": float, "eps": float, "n_layers": int, "vocab": int,
        "expert_offset": int,
    }
    kw = {k: conv(options[k]) for k, conv in names.items() if k in options}
    if "experts_held" in options:
        kw["n_held"] = int(options["experts_held"])
    elif "n_routed" in kw:
        kw["n_held"] = kw["n_routed"]
    return LongcatConfig(**kw)


# -- weights -----------------------------------------------------------------


def _stds(c: LongcatConfig) -> Dict[int, float]:
    d = c.d_model
    return {
        _WQA: d ** -0.5,
        _WQB: c.q_rank ** -0.5 / math.sqrt(d / c.q_rank),
        _WKVA: d ** -0.5,
        _WKVB: c.kv_rank ** -0.5 / math.sqrt(d / c.kv_rank),
        _WO: (c.n_heads * c.v_dim) ** -0.5,
        _FFN_GATE: d ** -0.5, _FFN_UP: d ** -0.5, _FFN_DOWN: c.d_ff ** -0.5,
        _ROUTER: _ROUTER_GAIN * d ** -0.5, _ROUTER_BIAS: _ROUTER_BIAS_STD,
        _EXP_GATE: d ** -0.5, _EXP_UP: d ** -0.5, _EXP_DOWN: c.d_expert ** -0.5,
    }


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, std, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("n", "shape", "dtype"))
def _draw_experts(key, first, std, *, n, shape, dtype):
    def one(e):
        return jax.random.normal(jax.random.fold_in(key, first + e), shape,
                                 jnp.float32) * std

    return jax.vmap(one)(jnp.arange(n)).astype(dtype)


def init_params(c: LongcatConfig, seed: int, dtype=jnp.bfloat16) -> Dict:
    """Draw the weights of ``seed`` tensor by tensor in float32 and round each
    once to ``dtype`` (no float32 copy of a layer is ever held). Layers are a
    list: the step programs unroll them, so no stacked leaf is sliced."""
    root = jax.random.PRNGKey(seed)
    sd = _stds(c)
    h = c.n_heads

    def layer(index: int) -> Dict:
        lk = jax.random.fold_in(jax.random.fold_in(root, _LAYERS), index)

        def t(tensor, sub, shape, dt=dtype):
            return _draw(jax.random.fold_in(lk, tensor + 100 * sub), sd[tensor],
                         shape=shape, dtype=dt)

        def sub(i):
            wkvb = t(_WKVB, i, (c.kv_rank, h * (c.nope + c.v_dim)))
            wkvb = wkvb.reshape(c.kv_rank, h, c.nope + c.v_dim)
            return {
                "norm_in": jnp.ones((c.d_model,), jnp.float32),
                "norm_post": jnp.ones((c.d_model,), jnp.float32),
                "q_norm": jnp.ones((c.q_rank,), jnp.float32),
                "kv_norm": jnp.ones((c.kv_rank,), jnp.float32),
                "wqa": t(_WQA, i, (c.d_model, c.q_rank)),
                "wqb": t(_WQB, i, (c.q_rank, h * (c.nope + c.rope))),
                "wkva": t(_WKVA, i, (c.d_model, c.kv_rank + c.rope)),
                # W_kvb split once into its key and value halves, heads second
                "wkv_k": wkvb[..., :c.nope],
                "wkv_v": wkvb[..., c.nope:],
                "wo": t(_WO, i, (h * c.v_dim, c.d_model)),
                "w_gate": t(_FFN_GATE, i, (c.d_model, c.d_ff)),
                "w_up": t(_FFN_UP, i, (c.d_model, c.d_ff)),
                "w_down": t(_FFN_DOWN, i, (c.d_ff, c.d_model)),
            }

        def experts(tensor, shape):
            return _draw_experts(
                jax.random.fold_in(lk, tensor), c.expert_offset, sd[tensor],
                n=c.n_held, shape=shape, dtype=dtype)

        n_out = c.n_routed + c.n_zero
        return {
            "sub": (sub(0), sub(1)),
            # the router stays float32: it runs at highest precision
            "router": t(_ROUTER, 0, (c.d_model, n_out), jnp.float32),
            "router_bias": t(_ROUTER_BIAS, 0, (n_out,), jnp.float32),
            "e_gate": experts(_EXP_GATE, (c.d_model, c.d_expert)),
            "e_up": experts(_EXP_UP, (c.d_model, c.d_expert)),
            "e_down": experts(_EXP_DOWN, (c.d_expert, c.d_model)),
        }

    return {
        "embed": _draw(jax.random.fold_in(root, _EMBED), _EMBED_STD,
                       shape=(c.vocab, c.d_model), dtype=dtype),
        "layers": [layer(i) for i in range(c.n_layers)],
        "ln_f": jnp.ones((c.d_model,), jnp.float32),
        "head": _draw(jax.random.fold_in(root, _HEAD), c.d_model ** -0.5,
                      shape=(c.d_model, c.vocab), dtype=dtype),
    }


# -- the pieces of a layer ---------------------------------------------------


def _mm(spec: str, a, w):
    """A contraction on operands of the storage dtype, float32 accumulation."""
    return jnp.einsum(spec, a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, w, eps: float):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta: float):
    """x [B, T, ..., D] float32 rotated in half-split pairs; positions [B, T]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla_project(a, sp: Dict, c: LongcatConfig, positions):
    """a [B, T, d] float32 -> q_nope [B,T,H,nope], q_rope [B,T,H,rope] and the
    cache entry of each position: latent [B,T,kv_rank], k_r [B,T,rope]."""
    b, t, d = a.shape
    cq = rmsnorm(_mm("btd,dr->btr", a, sp["wqa"]), sp["q_norm"], c.eps)
    cq = cq * math.sqrt(d / c.q_rank)
    q = _mm("btr,re->bte", cq, sp["wqb"]).reshape(b, t, c.n_heads, c.nope + c.rope)
    ckr = _mm("btd,de->bte", a, sp["wkva"])
    lat = rmsnorm(ckr[..., :c.kv_rank], sp["kv_norm"], c.eps)
    lat = lat * math.sqrt(d / c.kv_rank)
    k_r = _rope(ckr[..., c.kv_rank:], positions, c.theta)
    return q[..., :c.nope], _rope(q[..., c.nope:], positions, c.theta), lat, k_r


def mla_attend_expanded(q_nope, q_rope, lat, k_r, sp: Dict, c: LongcatConfig, mask):
    """Un-absorbed attention: K and V expanded from the latents of every
    position (prefill). lat [B,S,kv_rank], k_r [B,S,rope] in the cache's
    dtype; mask [B,T,S] -> [B,T,H*v_dim] float32."""
    k_nope = _mm("bsr,rhd->bshd", lat, sp["wkv_k"])
    v = _mm("bsr,rhd->bshd", lat, sp["wkv_v"])
    dt = sp["wkv_k"].dtype
    s = (_mm("bthd,bshd->bhts", q_nope, k_nope.astype(dt))
         + _mm("bthd,bsd->bhts", q_rope, k_r.astype(dt)))
    s = jnp.where(mask[:, None], s / math.sqrt(c.nope + c.rope), NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhts,bshd->bthd", p, v.astype(dt))
    return o.reshape(o.shape[:2] + (-1,))


def mla_absorb_q(q_nope, sp: Dict):
    """q' = q_nope W_kvb,k^T: the query in the latent's space. [B,T,H,kv_rank]."""
    return _mm("bthd,rhd->bthr", q_nope, sp["wkv_k"])


def mla_attend_absorbed(q_lat, q_rope, lat, k_r, sp: Dict, c: LongcatConfig, mask):
    """Absorbed attention over cached latents (decode, chunked prefill):
    lat [B,S,kv_rank], k_r [B,S,>=rope] as cached; mask [B,T,S]. The XLA
    formulation, and the oracle of the block-table kernel."""
    dt = lat.dtype
    qr = jnp.pad(q_rope, ((0, 0),) * 3 + ((0, k_r.shape[-1] - q_rope.shape[-1]),))
    s = (jnp.einsum("bthr,bsr->bhts", q_lat.astype(dt), lat,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthd,bsd->bhts", qr.astype(dt), k_r,
                      preferred_element_type=jnp.float32))
    s = jnp.where(mask[:, None], s / math.sqrt(c.nope + c.rope), NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhts,bsr->bthr", p.astype(dt), lat,
                       preferred_element_type=jnp.float32)
    return mla_unabsorb(o_lat, sp)


def mla_unabsorb(o_lat, sp: Dict):
    """(sum p c) W_kvb,v per head. [B,T,H,kv_rank] -> [B,T,H*v_dim] float32."""
    o = _mm("bthr,rhd->bthd", o_lat, sp["wkv_v"])
    return o.reshape(o.shape[:2] + (-1,))


def ffn(b, sp: Dict):
    gate = jax.nn.silu(_mm("btd,df->btf", b, sp["w_gate"]))
    return _mm("btf,fd->btd", gate * _mm("btd,df->btf", b, sp["w_up"]), sp["w_down"])


def route(b, lp: Dict, c: LongcatConfig):
    """b [T, d] float32 -> (idx [T, topk] router outputs chosen, w [T, topk]
    their weights ``scale * p``). The bias moves the choice, not the weight."""
    logits = jnp.einsum("td,dr->tr", b, lp["router"],
                        precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(p + lp["router_bias"], c.topk)
    return idx, jnp.take_along_axis(p, idx, axis=-1) * c.scale


def grouped_ffn_ragged(xs, sizes, lp: Dict):
    """The XLA formulation of the grouped expert FFN: rows of ``xs`` sorted by
    expert, ``sizes`` [n_held] rows each; rows past their sum belong to no one."""
    dt = lp["e_gate"].dtype
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                            preferred_element_type=jnp.float32)
    gate = jax.nn.silu(dot(xs, lp["e_gate"]))
    return dot((gate * dot(xs, lp["e_up"])).astype(dt), lp["e_down"])


# a bucket of more (token, pick) pairs than this tries a grouped matmul over
# ``few`` of them first: here 16 of 768 router outputs are local, so a prompt's
# 6144 pairs hold about 128 local ones, an eighth covers them with room, and
# the worst case stays exact
MOE_FEW_PAIRS = 1024
# up to this many tokens (a decode step's lanes) each held expert that some
# token picked streams once, every token through it (``held_experts_ffn``);
# a prompt's bucket takes the grouped matmul
MOE_DENSE_TOKENS = 128


def dispatch_held(b, w, local, group, lp: Dict, n: int, few: float = 1 / 8):
    """The held experts' weighted outputs summed back onto their tokens.
    b [T, d] float32; w [T, k] the picks' weights; local [T, k] the picks
    that fall on an expert held here; group [T, k] their expert's index
    among the ``n`` held (``n`` elsewhere) -> (s [T, d] float32, sizes [n]
    int32 pairs per held expert). Pairs are sorted by held expert;
    everything else is group n, the tail no group covers: the local pairs
    are the first sum(sizes) sorted rows. ``few`` is the share of the pairs
    a bucket of more than ``MOE_FEW_PAIRS`` tries first."""
    t, k = w.shape
    group = group.reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(
        group[:, None] == jnp.arange(n, dtype=group.dtype)[None, :], axis=0
    ).astype(jnp.int32)
    dt = lp["e_gate"].dtype
    b_op = b.astype(dt)

    def experts(rows: int):
        """The grouped FFN over the first ``rows`` sorted pairs, weighted and
        summed back onto their tokens by one exact one-hot contraction."""
        sel = order[:rows]
        tok = sel // k
        ys = grouped_ffn_ragged(jnp.take(b_op, tok, axis=0), sizes, lp)   # [rows, d] f32
        mine = jnp.take(local.reshape(-1), sel)
        ys = jnp.where(mine[:, None], ys, 0.0)   # rows of no group hold anything
        onto = jnp.where(
            mine[None, :] & (tok[None, :] == jnp.arange(t)[:, None]),
            jnp.take(w.reshape(-1), sel)[None, :], 0.0)
        return jnp.dot(onto, ys, precision=jax.lax.Precision.HIGHEST)

    pairs = t * k
    if pairs > MOE_FEW_PAIRS:
        rows = max(int(pairs * few), n)
        return jax.lax.cond(jnp.sum(sizes) <= rows,
                            lambda: experts(rows), lambda: experts(pairs)), sizes
    return experts(pairs), sizes


def moe(b, live, lp: Dict, c: LongcatConfig):
    """The expert layer's share. b [T, d] float32 (normed), live [T] bool
    (tokens that exist: dead slots and padding route nowhere) ->
    (s [T, d] float32, stats [5] int32 in ``MOE_STATS`` order)."""
    k, e0, n = c.topk, c.expert_offset, c.n_held
    idx, w = route(b, lp, c)
    pick = live[:, None]
    zero = pick & (idx >= c.n_routed)
    local = pick & (idx >= e0) & (idx < e0 + n)
    ident = jnp.sum(jnp.where(zero, w, 0.0), axis=-1)
    group = jnp.where(local, idx - e0, n)
    if b.shape[0] <= MOE_DENSE_TOKENS:
        ok, _ = pallas_ok("held_experts_ffn", lp["e_gate"].dtype)
        record("moe_experts", "pallas" if ok else "jnp")
        s, sizes = (held_experts_ffn if ok else held_experts_ffn_ref)(
            b.astype(lp["e_gate"].dtype), gates_of(w, group, n),
            lp["e_gate"], lp["e_up"], lp["e_down"])
    else:
        s, sizes = dispatch_held(b, w, local, group, lp, n)
    s = s + ident[:, None] * b
    stats = jnp.stack([
        jnp.sum(live), jnp.sum(local), jnp.sum(sizes > 0), jnp.sum(zero),
        jnp.sum(live) * k,
    ]).astype(jnp.int32)
    return s, stats


# -- whole forwards ----------------------------------------------------------


def _embed(params, tokens):
    return params["embed"][jnp.maximum(tokens, 0)].astype(jnp.float32)


def _logits(params, x, c: LongcatConfig):
    return _mm("...d,dv->...v", rmsnorm(x, params["ln_f"], c.eps), params["head"])


def _layer(x, lp, c: LongcatConfig, live, attend):
    """One double layer over x [B, T, d]. ``attend(i, a, sp)`` -> the
    attention's [B, T, H*v_dim]; returns (x, MoE stats)."""
    b_, t_, d = x.shape
    s = stats = None
    for i in (0, 1):
        sp = lp["sub"][i]
        with jax.named_scope("nns.mla"):
            a = rmsnorm(x, sp["norm_in"], c.eps)
            x = x + _mm("bte,ed->btd", attend(i, a, sp), sp["wo"])
        b = rmsnorm(x, sp["norm_post"], c.eps)
        if i == 0:
            with jax.named_scope("nns.moe"):
                s, stats = moe(b.reshape(-1, d), live.reshape(-1), lp, c)
                s = s.reshape(b_, t_, d)
        with jax.named_scope("nns.ffn"):
            x = x + ffn(b, sp)
    return x + s, stats


def prefill(params, tokens, c: LongcatConfig, cache_dtype):
    """tokens [B, T] (ids < 0 are padding) -> (logits [B, T, V] float32, the
    latents (lat [2L, B, T, kv_rank], k_r [2L, B, T, kr_width]) in the
    cache's dtype). K and V are expanded; the attention is causal."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    mask = jnp.broadcast_to(
        (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])[None], (b, t, t))
    live = tokens >= 0
    x = _embed(params, tokens)
    lats, krs = [], []

    def attend(i, a, sp):
        q_nope, q_rope, lat, k_r = mla_project(a, sp, c, positions)
        lat, k_r = lat.astype(cache_dtype), k_r.astype(cache_dtype)
        lats.append(lat)
        krs.append(jnp.pad(k_r, ((0, 0), (0, 0), (0, c.kr_width - c.rope))))
        return mla_attend_expanded(q_nope, q_rope, lat, k_r, sp, c, mask)

    for lp in params["layers"]:
        x, _ = _layer(x, lp, c, live, attend)
    return _logits(params, x, c), (jnp.stack(lats), jnp.stack(krs))


def apply(params, tokens, c: LongcatConfig, cache_dtype=None):
    """tokens [B, T] -> logits [B, T, V] float32 (the full forward)."""
    cache_dtype = cache_dtype or params["embed"].dtype
    return prefill(params, tokens, c, cache_dtype)[0]


def chunk(params, tokens, cpos, stage, c: LongcatConfig,
          return_logits: bool = True):
    """One bucket of chunked prefill at absolute position ``cpos`` against a
    contiguous stage (lat [2L, 1, S, kv_rank], k_r [2L, 1, S, kr_width]):
    the chunk's latents are written at ``cpos``, then its queries attend the
    stage up to their own positions in the absorbed form. Padding columns
    (ids < 0) are written too and overwritten or masked before any read, as
    in the dense family's chunked prefill. -> (logits or None, stage)."""
    b, t = tokens.shape
    s_len = stage[0].shape[2]
    positions = cpos + jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    mask = jnp.arange(s_len)[None, None, :] <= positions[:, :, None]
    live = tokens >= 0
    x = _embed(params, tokens)
    lat_st, kr_st = stage
    li = [0]

    def attend(i, a, sp):
        nonlocal lat_st, kr_st
        q_nope, q_rope, lat, k_r = mla_project(a, sp, c, positions)
        k_r = jnp.pad(k_r, ((0, 0), (0, 0), (0, c.kr_width - c.rope)))
        lat_st = jax.lax.dynamic_update_slice(
            lat_st, lat.astype(lat_st.dtype)[None], (li[0], 0, cpos, 0))
        kr_st = jax.lax.dynamic_update_slice(
            kr_st, k_r.astype(kr_st.dtype)[None], (li[0], 0, cpos, 0))
        o = mla_attend_absorbed(
            mla_absorb_q(q_nope, sp), q_rope, lat_st[li[0]], kr_st[li[0]], sp, c, mask)
        li[0] += 1
        return o

    for lp in params["layers"]:
        x, _ = _layer(x, lp, c, live, attend)
    logits = _logits(params, x, c) if return_logits else None
    return logits, (lat_st, kr_st)


def decode_step(params, tok, pos, active, arena, tables, c: LongcatConfig,
                attn_fn: Optional[Callable] = None):
    """One decode step of a slot batch straight off the latent arena (leaves
    ``[2L, N, bs, ...]``) through the block tables [B, nb]: the sibling of
    ``kv.block_attn.batched_decode_step_block``. The pending token's latent is
    folded last and lands in its owning block with one ``write_fresh_window``
    after the layers. ``attn_fn(q_lat, q_rope, lat_arena, kr_arena, tables,
    fill, layer=, scale=)`` -> (o_lat [B,H,kv_rank] float32 normalised over
    the history, m, l [B,H] its running max and denominator) is the
    block-table kernel (ops/pallas/mla_attention.py); without it each
    sublayer takes its view through the tables (the XLA oracle there).
    -> (logits [B, V], arena, pos', MoE stats [5] int32 summed over layers)."""
    from nnstreamer_tpu.kv.block_attn import write_fresh_window
    from nnstreamer_tpu.ops.pallas.mla_attention import mla_paged_attention_ref

    record("mla_attention", "xla" if attn_fn is None else "pallas")
    lat_arena, kr_arena = arena
    fill = jnp.where(active, pos, 0)
    positions = pos[:, None]
    x = _embed(params, tok)[:, None, :]
    fresh_lat, fresh_kr = [], []
    sm_scale = 1.0 / math.sqrt(c.nope + c.rope)

    def attend(i, a, sp):
        li = len(fresh_lat)
        q_nope, q_rope, lat, k_r = mla_project(a, sp, c, positions)
        lat = lat.astype(lat_arena.dtype)                      # [B, 1, kv_rank]
        k_r = jnp.pad(k_r, ((0, 0), (0, 0), (0, c.kr_width - c.rope))
                      ).astype(kr_arena.dtype)
        fresh_lat.append(lat)
        fresh_kr.append(k_r)
        q_lat = mla_absorb_q(q_nope, sp)                       # [B, 1, H, kv_rank]
        dt = lat.dtype
        qr = jnp.pad(q_rope, ((0, 0),) * 3 + ((0, c.kr_width - c.rope),))
        # the pending token's own column: always live, folded last
        s1 = (jnp.einsum("bthr,btr->bh", q_lat.astype(dt), lat,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthd,btd->bh", qr.astype(dt), k_r,
                           preferred_element_type=jnp.float32)) * sm_scale
        o_h, m_h, l_h = (attn_fn or mla_paged_attention_ref)(
            q_lat[:, 0].astype(dt), qr[:, 0].astype(dt), lat_arena, kr_arena,
            tables, fill, layer=li, scale=sm_scale)
        # merge the history with the pending column (online-softmax step)
        m = jnp.maximum(m_h, s1)
        alpha = jnp.where(m_h <= NEG_INF, 0.0, jnp.exp(m_h - m)) * l_h
        p1 = jnp.exp(s1 - m)
        o_lat = (o_h * alpha[..., None]
                 + p1[..., None] * lat[:, 0, None, :].astype(jnp.float32)
                 ) / (alpha + p1)[..., None]
        return mla_unabsorb(o_lat[:, None], sp)

    stats = jnp.zeros((len(MOE_STATS),), jnp.int32)
    live = active[:, None]
    for lp in params["layers"]:
        x, st = _layer(x, lp, c, live, attend)
        stats = stats + st
    fresh = (jnp.stack(fresh_lat), jnp.stack(fresh_kr))        # [2L, B, 1, ...]
    arena = write_fresh_window(arena, tables, fresh, pos, 1, active, False,
                               per_layer=True)
    logits = _logits(params, x, c)[:, 0]
    return logits, arena, pos + active.astype(jnp.int32), stats


# -- the family the batcher serves -------------------------------------------


class LongcatFamily:
    """What ``ContinuousBatcher``'s paged path asks of a block family
    (models/family.py), for the LongCat-Flash layer."""

    name = "longcat_flash"
    pad_id = -1                       # padding routes nowhere (``moe``'s live mask)
    slot_leaves = 0
    aux_names: Tuple[str, ...] = MOE_STATS
    aux_prefix = "moe_"               # stats() keys: moe_tokens, moe_local_pairs, ...
    decode_kernel = "mla_paged_decode_attention"
    # what the paged path offers and this family does not carry yet
    unsupported = ("speculate", "cache-dtype=int8", "kv-layout=slot", "windowed",
                   "mesh", "draft model", "migration",
                   "snapshot")

    def __init__(self, config: LongcatConfig, dtype):
        self.config = config
        self.dtype = jnp.dtype(dtype)

    def arena(self, n_blocks: int, block_size: int, quantized: bool = False,
              n_slots: int = 0):
        c = self.config
        lead = (2 * c.n_layers, n_blocks + 1, block_size)
        return (jnp.zeros(lead + (c.kv_rank,), self.dtype),
                jnp.zeros(lead + (c.kr_width,), self.dtype))

    def stage(self, length: int):
        c = self.config
        lead = (2 * c.n_layers, 1, length)
        return (jnp.zeros(lead + (c.kv_rank,), self.dtype),
                jnp.zeros(lead + (c.kr_width,), self.dtype))

    def prefill(self, params, tokens):
        logits, stage = prefill(params, tokens, self.config, self.dtype)
        return logits, stage, jnp.asarray(tokens.shape[1], jnp.int32)

    def chunk(self, params, tokens, cpos, stage, return_logits: bool = True):
        logits, stage = chunk(params, tokens, cpos, stage, self.config,
                              return_logits=return_logits)
        return logits, stage, cpos + tokens.shape[1]

    def decode_step(self, params, tok, pos, active, arena, tables, attn_fn=None):
        return decode_step(params, tok, pos, active, arena, tables, self.config,
                           attn_fn=attn_fn)

    def make_attention(self):
        from nnstreamer_tpu.ops.pallas.mla_attention import mla_paged_decode_attention

        return mla_paged_decode_attention

    @staticmethod
    def note_aux(counts: Dict[str, int], registry) -> None:
        """One harvested pump's routing counters (``MOE_STATS``, summed on the
        device over the pump's steps and expert layers): one
        ``nns.moe.routing`` instant and the ``nns_moe_*_total`` counters."""
        from nnstreamer_tpu import trace as _trace

        _trace.instant("nns.moe.routing", **counts)
        if registry is None:
            return
        registry.counter("nns_moe_tokens_total").inc(counts["tokens"])
        registry.counter("nns_moe_local_pairs_total").inc(counts["local_pairs"])
        registry.counter("nns_moe_experts_hit_total").inc(counts["experts_hit"])
        registry.counter("nns_moe_zero_picks_total").inc(counts["zero_picks"])
        registry.counter("nns_moe_picks_total").inc(counts["picks"])
