"""Granite-4.0-H block family: Mamba-2 state-space layers beside grouped-query
attention layers without positions, every layer followed by softmax-routed
experts with a shared MLP, for one chip's share of the routed experts.

``x_0 = embedding_multiplier * E[token]``; layer l of 0..n_layers-1, ``x`` the
float32 residual, every norm an RMSNorm, ``r`` the residual multiplier::

    x = x + r * Mix_l(norm(x));  h = norm(x);  x = x + r * (Experts(h) + SharedMLP(h))

``Mix_l`` is attention where l is in ``attn_layers`` and Mamba-2 elsewhere;
``logits = norm(x) E^T / logits_scaling`` (the head is the embedding).

Mamba-2, H heads of P channels, a state of N a channel, one group::

    [z, u, dt] = a W_in;  u = SiLU(conv4(u) + b_conv);  [x~, B, C] = u
    D_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) D_t)       a head
    S_t = a_t S_{t-1} + D_t x~_t B_t^T,  S in R^{P x N} a head
    y_t = S_t C_t + D x~_t;  out = W_out [ RMSNorm( y * SiLU(z) ) ]

The model keeps TWO kinds of cache. An attention layer leaves, per token, its
keys and values: the dense family's block leaves ``[attention layers, N, bs,
KV, Dh]`` (kv/gather.init_arena), read by the same block-table kernel with the
published ``attention_multiplier`` as its scale. A Mamba-2 layer leaves
nothing per token: per SLOT it keeps the float32 state ``S`` (H heads of P x
N) and the last ``conv - 1`` inputs of its convolution: the slot leaves ``[SSM
layers, n_slots + 1, ...]`` (lane b is row b, the last row is scratch), carried
and donated with the block leaves. The state's leaf is laid for the decode
kernel, ``[..., H / k, N, k P]``: ``N`` on the sublanes and ``k`` heads side by
side on the lanes (``ssm.heads_per_row``: 2 at the published P = 64, a 128 x
128 tile), so that a step's per-head work is row broadcasts, whole-vreg
arithmetic and a sum over sublanes; over ``[H, P, N]`` the same kernel is
bound by column broadcasts and 128-lane reductions, not by the memory
(PERF.md section 6, PR 38). The same bytes; ``k`` is read off the shapes.
Prefill and ``chunk`` compute the scan chunk by chunk on ``[B, H, P,
N]`` (``ssm_chunked``: inside a chunk as ``(C B^T * L) x~`` with ``L_ij =
exp(l_i - l_j)`` from cumulative sums of ``log a``, between chunks through
``S``; float32, matmuls at highest precision) and convert where the stage is
read and written (``ssm.leaf_to_state`` / ``state_to_leaf``); decode is one
kernel that reads each head's state once and writes it in place
(ops/pallas/ssm.py), or the plain recurrence over the same leaf off a TPU.
Padding (ids < 0) leaves the state as it was: ``D = 0`` there, and the
convolution tail is the last three REAL inputs.

The expert layer routes over every router output (float32 at highest
precision, the ``topk`` largest logits, weights a softmax over those), runs
the pairs that fall on the experts held here through LongCat-Flash's
sort-by-held-expert dispatch (a prompt's bucket) or every held expert over
every token weighted by its gate (a decode step's few tokens), and adds the
shared MLP for every token. What the absent experts would have added is left
out.

Storage dtype is stated by the caller, as in models/longcat.py: weights, K/V
and convolution inputs in ``dtype``; residual, norms, softmax, router, ``dt``,
decay and the state float32. Weights follow the recipe of
``benchmark/reference/granite_hybrid.py``; the two modules share no code.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models.kimi_linear import (
    AUX_NAMES,
    MOE_STATS,
    _real_tail,
    _stored,
    experts_dense,
)
from nnstreamer_tpu.models.longcat import (
    NEG_INF,
    _draw,
    _draw_experts,
    _mm,
    dispatch_held,
    ffn,
    rmsnorm,
)

# key schedule and draws of ``seed:<n>`` (stated in the configuration's file)
_S_WZ, _S_WXBC, _S_WDT, _S_CONV, _S_CONV_B, _S_A_LOG, _S_DT_BIAS, _S_WOUT = range(1, 9)
_A_WQ, _A_WK, _A_WV, _A_WO = 20, 21, 22, 23
_ROUTER, _EXP_GATE, _EXP_UP, _EXP_DOWN = 40, 50, 51, 52
_SH_GATE, _SH_UP, _SH_DOWN = 60, 61, 62
_EMBED, _LAYERS = 1, 3
# the head is the embedding: at the usual 0.02 the input token's own logit
# (embed_mult * d * std^2 / rms(x)) stands 15 sigmas of the other logits above
# them and every step repeats its input, whatever the layers compute
_EMBED_STD = 1e-3
_A_RANGE, _DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)

# half of the picks are local when half of the experts are held (5 of 10 a
# token): a bucket of more than longcat.MOE_FEW_PAIRS pairs tries 9/16 of them
# first (a prompt's 5120 pairs hold 2560 +- 36 local ones)
MOE_FEW = 9 / 16
# up to this many tokens (a decode step's lanes) every held expert runs over
# every token, as models/kimi_linear.py's decode step does and for its reason:
# a step's 64 tokens give a held expert 9 pairs and hit all 36, whose weights
# then stream at 93 % of the bandwidth: 26.1 ms a step against 32.5 through
# the grouped matmul (PERF.md section 5, PR 36)
MOE_DENSE_TOKENS = 128
_HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class GraniteHybridConfig:
    """Published widths by default; ``n_layers``, ``n_held`` and ``vocab``
    are the chip's share."""

    d_model: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    conv: int = 4
    ssm_chunk: int = 256
    attn_layers: Tuple[int, ...] = (5, 15, 25, 35)    # 0-based, as layer_types
    d_expert: int = 768
    d_shared: int = 1536
    n_routed: int = 72
    topk: int = 10
    eps: float = 1e-5
    embed_mult: float = 12.0
    resid_mult: float = 0.22
    attn_scale: float = 0.0078125
    logit_scale: float = 16.0
    n_layers: int = 40
    vocab: int = 100352
    n_held: int = 72
    expert_offset: int = 0

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.n_routed - self.n_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + self.n_held})"
                f" are not among the {self.n_routed} routed experts"
            )
        if self.n_held < 1:
            raise ValueError("experts_held must be at least 1")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def attn_here(self) -> Tuple[int, ...]:
        """The 0-based layers that are attention, of those this model has."""
        return tuple(l for l in self.attn_layers if l < self.n_layers)

    @property
    def n_attn(self) -> int:
        return len(self.attn_here)

    @property
    def n_ssm(self) -> int:
        return self.n_layers - self.n_attn

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: x~, B and C side by side."""
        return self.d_inner + 2 * self.ssm_state


def config_from_options(options: Dict[str, str]) -> GraniteHybridConfig:
    """``custom=`` of ``zoo:granite_hybrid_lm``: every width by its short
    name, the multipliers, ``attn_layers`` (0-based, ``/``-separated),
    ``n_layers``, ``experts_held``, ``expert_offset``, ``vocab``."""
    names = {
        "d_model": int, "n_heads": int, "n_kv_heads": int, "head_dim": int,
        "ssm_heads": int, "ssm_head_dim": int, "ssm_state": int, "conv": int,
        "ssm_chunk": int, "d_expert": int, "d_shared": int, "n_routed": int,
        "topk": int, "eps": float, "embed_mult": float, "resid_mult": float,
        "attn_scale": float, "logit_scale": float, "n_layers": int,
        "vocab": int, "expert_offset": int,
    }
    kw = {k: conv(options[k]) for k, conv in names.items() if k in options}
    if "attn_layers" in options:
        kw["attn_layers"] = tuple(
            int(l) for l in str(options["attn_layers"]).split("/") if l != "")
    if "experts_held" in options:
        kw["n_held"] = int(options["experts_held"])
    elif "n_routed" in kw:
        kw["n_held"] = kw["n_routed"]
    return GraniteHybridConfig(**kw)


# -- weights -----------------------------------------------------------------


def init_params(c: GraniteHybridConfig, seed: int, dtype=jnp.bfloat16) -> Dict:
    """Draw the weights of ``seed`` tensor by tensor in float32 (every matrix
    and the convolution's bias normal(0, 1/fan_in)) and round each once to
    ``dtype``. Layers are a list: they differ in kind, and the step programs
    unroll them. The head is the embedding."""
    root = jax.random.PRNGKey(seed)
    d, hd = c.d_model, c.head_dim

    def layer(index: int) -> Dict:
        lk = jax.random.fold_in(jax.random.fold_in(root, _LAYERS), index)

        def t(tensor, shape, dt=dtype, fan_in=None):
            return _draw(jax.random.fold_in(lk, tensor),
                         (fan_in or shape[0]) ** -0.5, shape=shape, dtype=dt)

        def uniform(tensor, n, lo, hi):
            return jax.random.uniform(jax.random.fold_in(lk, tensor), (n,),
                                      jnp.float32, lo, hi)

        def ssm():
            dt_ = jnp.exp(uniform(_S_DT_BIAS, c.ssm_heads, math.log(_DT_RANGE[0]),
                                  math.log(_DT_RANGE[1])))
            return {
                # z, xBC and dt side by side: one projection
                "w_in": jnp.concatenate(
                    [t(_S_WZ, (d, c.d_inner)), t(_S_WXBC, (d, c.conv_width)),
                     t(_S_WDT, (d, c.ssm_heads))], axis=1),
                "conv": t(_S_CONV, (c.conv, c.conv_width)),
                "conv_b": t(_S_CONV_B, (c.conv_width,), fan_in=c.conv),
                "a_log": jnp.log(uniform(_S_A_LOG, c.ssm_heads, *_A_RANGE)),
                "dt_bias": dt_ + jnp.log(-jnp.expm1(-dt_)),
                "d_skip": jnp.ones((c.ssm_heads,), jnp.float32),
                "o_norm": jnp.ones((c.d_inner,), jnp.float32),
                "w_out": t(_S_WOUT, (c.d_inner, d)),
            }

        def attn():
            return {
                "wq": t(_A_WQ, (d, c.n_heads * hd)),
                "wk": t(_A_WK, (d, c.n_kv_heads * hd)),
                "wv": t(_A_WV, (d, c.n_kv_heads * hd)),
                "wo": t(_A_WO, (c.n_heads * hd, d)),
            }

        def experts(tensor, shape):
            return _draw_experts(
                jax.random.fold_in(lk, tensor), c.expert_offset, shape[0] ** -0.5,
                n=c.n_held, shape=shape, dtype=dtype)

        return {
            "norm_in": jnp.ones((d,), jnp.float32),
            "norm_post": jnp.ones((d,), jnp.float32),
            "mix": attn() if index in c.attn_here else ssm(),
            # the router stays float32: it runs at highest precision
            "router": t(_ROUTER, (d, c.n_routed), jnp.float32),
            "e_gate": experts(_EXP_GATE, (d, c.d_expert)),
            "e_up": experts(_EXP_UP, (d, c.d_expert)),
            "e_down": experts(_EXP_DOWN, (c.d_expert, d)),
            "shared": {"w_gate": t(_SH_GATE, (d, c.d_shared)),
                       "w_up": t(_SH_UP, (d, c.d_shared)),
                       "w_down": t(_SH_DOWN, (c.d_shared, d))},
        }

    return {
        "embed": _draw(jax.random.fold_in(root, _EMBED), _EMBED_STD,
                       shape=(c.vocab, d), dtype=dtype),
        "layers": [layer(i) for i in range(c.n_layers)],
        "ln_f": jnp.ones((d,), jnp.float32),
    }


# -- Mamba-2 -----------------------------------------------------------------


def ssm_project(a, live, tail, sp: Dict, c: GraniteHybridConfig):
    """a [B, T, d] float32 (normed), live [B, T] bool, tail [B, conv - 1, W]
    the convolution's inputs at the positions before these -> z [B, T,
    d_inner], x~ [B, T, H, P], B, C [B, T, N], dt and ``log a`` [B, T, H]
    float32, window [B, conv - 1 + T, W] float32 (the tail then these
    positions' inputs, rounded as cached). Where ``live`` is false ``dt`` is
    zero and so is ``log a``: the token leaves the state as it was."""
    b, t, _ = a.shape
    zxd = _mm("btd,dc->btc", a, sp["w_in"])
    z = zxd[..., :c.d_inner]
    u = _stored(zxd[..., c.d_inner:c.d_inner + c.conv_width], tail.dtype)
    window = jnp.concatenate([tail.astype(jnp.float32), u], axis=1)
    w32 = sp["conv"].astype(jnp.float32)
    y = sum(window[:, i:i + t] * w32[i] for i in range(c.conv))
    y = jax.nn.silu(y + sp["conv_b"].astype(jnp.float32))
    x = y[..., :c.d_inner].reshape(b, t, c.ssm_heads, c.ssm_head_dim)
    bm = y[..., c.d_inner:c.d_inner + c.ssm_state]
    cm = y[..., c.d_inner + c.ssm_state:]
    dt = jax.nn.softplus(zxd[..., c.d_inner + c.conv_width:] + sp["dt_bias"])
    dt = jnp.where(live[..., None], dt, 0.0)
    return z, x, bm, cm, dt, -jnp.exp(sp["a_log"]) * dt, window


def ssm_output(y, z, sp: Dict, c: GraniteHybridConfig):
    """y [B, T, H, P] float32 (S C + D x~) -> the layer's [B, T, d]: the
    gate, ONE norm over all of d_inner, W_out."""
    b, t = y.shape[:2]
    y = y.reshape(b, t, -1) * jax.nn.silu(z)
    return _mm("btc,cd->btd", rmsnorm(y, sp["o_norm"], c.eps), sp["w_out"])


def ssm_chunked(x, bm, cm, dt, la, state, chunk: int):
    """The scan over T positions, chunk by chunk. x [B, T, H, P], bm, cm
    [B, T, N], dt, la = log a [B, T, H] float32; state [B, H, P, N] -> (y
    [B, T, H, P] = S_t C_t, state after T). With l the running sum of ``la``
    inside a chunk and S the state at its start: ``y_i = e^(l_i) S C_i +
    sum_{j <= i} e^(l_i - l_j) (C_i . B_j) dt_j x~_j`` and the state moves to
    ``e^(l_Q) S + sum_j e^(l_Q - l_j) dt_j x~_j B_j^T``. Every exponent is a
    difference of cumulative sums for i >= j, never positive."""
    b, t, h, p = x.shape
    cs = min(chunk, t)
    pad = -t % cs
    if pad:  # whole chunks: the padding decays nothing and writes nothing
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bm, cm = (jnp.pad(m, ((0, 0), (0, pad), (0, 0))) for m in (bm, cm))
        dt, la = (jnp.pad(m, ((0, 0), (0, pad), (0, 0))) for m in (dt, la))
    n = (t + pad) // cs

    def chunks(m):  # [B, T, ...] -> [N, B, C, ...]
        return jnp.moveaxis(m.reshape((b, n, cs) + m.shape[2:]), 1, 0)

    seen = jnp.arange(cs)[:, None] >= jnp.arange(cs)[None, :]
    mm = lambda spec, *ops: jnp.einsum(spec, *ops, precision=_HI)  # noqa: E731

    def one(state, xs):
        xc, bc, cc, dc, lc = xs            # [B,C,H,P] [B,C,N] [B,C,N] [B,C,H] [B,C,H]
        l = jnp.cumsum(lc, axis=1)                               # [B, C, H]
        diff = l[:, :, None, :] - l[:, None, :, :]               # [B, i, j, H]
        lmat = jnp.exp(jnp.where(seen[None, :, :, None], diff, -jnp.inf))
        xd = xc * dc[..., None]                                  # dt_j x~_j
        m = mm("bin,bjn->bij", cc, bc)[..., None] * lmat         # [B, i, j, H]
        y = (mm("bijh,bjhp->bihp", m, xd)
             + jnp.exp(l)[..., None] * mm("bin,bhpn->bihp", cc, state))
        last = l[:, -1:, :]
        state = (jnp.exp(last[:, 0])[..., None, None] * state
                 + mm("bjhp,bjn->bhpn", xd * jnp.exp(last - l)[..., None], bc))
        return state, y

    state, y = jax.lax.scan(one, state, tuple(chunks(m) for m in (x, bm, cm, dt, la)))
    return jnp.moveaxis(y, 0, 1).reshape(b, n * cs, h, p)[:, :t], state


def ssm_recurrent(x, bm, cm, dt, la, state):
    """The same scan token by token (the oracle of ``ssm_chunked``)."""
    def step(s, xs):
        x_t, b_t, c_t, d_t, l_t = xs
        s = (jnp.exp(l_t)[..., None, None] * s
             + (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return s, jnp.sum(s * c_t[:, None, None, :], axis=-1)

    state, y = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(m, 1, 0) for m in (x, bm, cm, dt, la)))
    return jnp.moveaxis(y, 0, 1), state


# -- grouped-query attention, no positions -----------------------------------


def attn_project(a, ap: Dict, c: GraniteHybridConfig, cache_dtype):
    """a [B, T, d] float32 -> q [B, T, H, Dh] float32 rounded as an operand,
    and each position's cache entry k, v [B, T, KV, Dh] in the cache's dtype."""
    b, t, _ = a.shape
    q = _mm("btd,de->bte", a, ap["wq"]).reshape(b, t, c.n_heads, c.head_dim)
    k = _mm("btd,de->bte", a, ap["wk"]).reshape(b, t, c.n_kv_heads, c.head_dim)
    v = _mm("btd,de->bte", a, ap["wv"]).reshape(b, t, c.n_kv_heads, c.head_dim)
    return _stored(q, cache_dtype), k.astype(cache_dtype), v.astype(cache_dtype)


def attn_causal(q, k, v, mask, ap: Dict, c: GraniteHybridConfig):
    """q [B, T, H, Dh] against k, v [B, S, KV, Dh] under mask [B, T, S] ->
    the layer's [B, T, d]: softmax of ``q.k * attention_multiplier`` in
    float32, query heads grouped over the compact K/V heads."""
    b, t = q.shape[:2]
    dt = k.dtype
    q5 = q.reshape(b, t, c.n_kv_heads, c.n_heads // c.n_kv_heads, c.head_dim)
    s = jnp.einsum("btkgd,bskd->bkgts", q5.astype(dt), k,
                   preferred_element_type=jnp.float32) * c.attn_scale
    p = jax.nn.softmax(jnp.where(mask[:, None, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p.astype(dt), v,
                   preferred_element_type=jnp.float32)
    return _mm("bte,ed->btd", o.reshape(b, t, -1), ap["wo"])


# -- the expert layer --------------------------------------------------------


def route(b, lp: Dict, c: GraniteHybridConfig):
    """b [T, d] float32 -> (idx [T, topk] router outputs chosen, w [T, topk]
    their weights: a softmax over the chosen logits alone)."""
    logits = jnp.einsum("td,dr->tr", b, lp["router"], precision=_HI)
    picked, idx = jax.lax.top_k(logits, c.topk)
    return idx, jax.nn.softmax(picked, axis=-1)


def moe(b, live, lp: Dict, c: GraniteHybridConfig, shared: bool = True):
    """The expert layer's share. b [T, d] float32 (normed), live [T] bool ->
    (y [T, d] float32: the held experts' weighted outputs and, where
    ``shared``, the shared MLP's; stats [4] int32 in ``MOE_STATS`` order).
    Up to ``MOE_DENSE_TOKENS`` tokens (a decode step) every held expert runs
    over every token; a prompt's bucket takes the sort-by-held-expert dispatch."""
    e0, n = c.expert_offset, c.n_held
    idx, w = route(b, lp, c)
    local = live[:, None] & (idx >= e0) & (idx < e0 + n)
    run = experts_dense if b.shape[0] <= MOE_DENSE_TOKENS else functools.partial(
        dispatch_held, few=MOE_FEW)
    y, sizes = run(b, w, local, jnp.where(local, idx - e0, n), lp, n)
    if shared:
        y = y + ffn(b[None], lp["shared"])[0]
    stats = jnp.stack([
        jnp.sum(live), jnp.sum(local), jnp.sum(sizes > 0), jnp.sum(live) * c.topk,
    ]).astype(jnp.int32)
    return y, stats


# -- whole forwards ----------------------------------------------------------


def _embed(params, tokens, c: GraniteHybridConfig):
    return params["embed"][jnp.maximum(tokens, 0)].astype(jnp.float32) * c.embed_mult


def _logits(params, x, c: GraniteHybridConfig):
    return _mm("...d,vd->...v", rmsnorm(x, params["ln_f"], c.eps),
               params["embed"]) / c.logit_scale


def _layers(params, x, c: GraniteHybridConfig, live, ssm_mix, attn_mix):
    """Every layer over x [B, T, d]. ``ssm_mix(j, a, sp)`` / ``attn_mix(i, a,
    ap)`` -> the mixer's [B, T, d] for SSM layer j / attention layer i (each
    kind counted on its own: the index into its cache). Returns (x, MoE stats
    summed over the layers)."""
    b_, t_, d = x.shape
    stats = jnp.zeros((len(MOE_STATS),), jnp.int32)
    seen = {"ssm": 0, "attn": 0}
    for index, lp in enumerate(params["layers"]):
        kind = "attn" if index in c.attn_here else "ssm"
        with jax.named_scope("nns." + kind):
            a = rmsnorm(x, lp["norm_in"], c.eps)
            mix = attn_mix if kind == "attn" else ssm_mix
            x = x + c.resid_mult * mix(seen[kind], a, lp["mix"])
            seen[kind] += 1
        with jax.named_scope("nns.moe"):
            h = rmsnorm(x, lp["norm_post"], c.eps)
            y, st = moe(h.reshape(-1, d), live.reshape(-1), lp, c)
            x = x + c.resid_mult * y.reshape(b_, t_, d)
            stats = stats + st
    return x, stats


def _run_bucket(params, tokens, c: GraniteHybridConfig, states, tails, attn_mix):
    """What prefill and chunk share: a bucket's layers with the scan in the
    chunked form from ``states`` [Ls, B, H / k, N, k P] (the slot leaf's
    layout; the scan itself works on [B, H, P, N]) and ``tails`` [Ls, B,
    conv - 1, W] -> (x, states, tails after the bucket's real tokens)."""
    from nnstreamer_tpu.ops.pallas.ssm import leaf_to_state, state_to_leaf

    live = tokens >= 0
    k = c.ssm_heads // states.shape[2]
    new_states, new_tails = [], []

    def ssm_mix(j, a, sp):
        z, x, bm, cm, dt, la, window = ssm_project(a, live, tails[j], sp, c)
        y, s = ssm_chunked(x, bm, cm, dt, la, leaf_to_state(states[j], k),
                           c.ssm_chunk)
        new_states.append(state_to_leaf(s, k))
        new_tails.append(_real_tail(window, live, c.conv))
        return ssm_output(y + sp["d_skip"][:, None] * x, z, sp, c)

    x, _ = _layers(params, _embed(params, tokens, c), c, live, ssm_mix, attn_mix)
    return x, jnp.stack(new_states), jnp.stack(new_tails).astype(tails.dtype)


def empty_slot_stage(c: GraniteHybridConfig, batch: int, dtype):
    """Zero state and convolution tails of ``batch`` sequences: a prompt's
    start. The state in the slot leaf's layout (ops/pallas/ssm.py): ``N`` on
    the sublanes, ``k`` heads side by side on the lanes."""
    from nnstreamer_tpu.ops.pallas.ssm import heads_per_row

    k = heads_per_row(c.ssm_heads, c.ssm_head_dim)
    return (jnp.zeros((c.n_ssm, batch, c.ssm_heads // k, c.ssm_state,
                       k * c.ssm_head_dim), jnp.float32),
            jnp.zeros((c.n_ssm, batch, c.conv - 1, c.conv_width), dtype))


def prefill(params, tokens, c: GraniteHybridConfig, cache_dtype):
    """tokens [B, T] (ids < 0 are padding, at the end) -> (logits [B, T, V]
    float32, stage: the keys and values (k, v [La, B, T, KV, Dh]) in the
    cache's dtype, then the state [Ls, B, H / k, N, k P] float32 (the slot
    leaf's layout) and the convolution tails [Ls, B, conv - 1, W] after the
    real tokens)."""
    b, t = tokens.shape
    mask = jnp.broadcast_to(
        (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])[None], (b, t, t))
    ks, vs = [], []

    def attn_mix(i, a, ap):
        q, k, v = attn_project(a, ap, c, cache_dtype)
        ks.append(k)
        vs.append(v)
        return attn_causal(q, k, v, mask, ap, c)

    x, states, tails = _run_bucket(
        params, tokens, c, *empty_slot_stage(c, b, cache_dtype), attn_mix)

    def stacked(xs):  # a cut with no attention layer has block leaves of no layers
        return jnp.stack(xs) if xs else jnp.zeros(
            (0, b, t, c.n_kv_heads, c.head_dim), cache_dtype)

    return _logits(params, x, c), (stacked(ks), stacked(vs), states, tails)


def apply(params, tokens, c: GraniteHybridConfig, cache_dtype=None):
    """tokens [B, T] -> logits [B, T, V] float32 (the full forward)."""
    cache_dtype = cache_dtype or params["embed"].dtype
    return prefill(params, tokens, c, cache_dtype)[0]


def chunk(params, tokens, cpos, stage, c: GraniteHybridConfig,
          return_logits: bool = True):
    """One bucket of chunked prefill at absolute position ``cpos`` against a
    stage (k, v [La, 1, S, KV, Dh], state [Ls, 1, H / k, N, k P], tails [Ls,
    1, conv - 1, W]): the bucket's keys and values are written at ``cpos`` and
    its queries attend the stage up to their own positions; the scan goes on
    from the stage's state and tails. -> (logits or None, stage)."""
    b, t = tokens.shape
    k_st, v_st, states, tails = stage
    s_len = k_st.shape[2]
    positions = cpos + jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    mask = jnp.arange(s_len)[None, None, :] <= positions[:, :, None]

    def attn_mix(i, a, ap):
        nonlocal k_st, v_st
        q, k, v = attn_project(a, ap, c, k_st.dtype)
        k_st = jax.lax.dynamic_update_slice(k_st, k[None], (i, 0, cpos, 0, 0))
        v_st = jax.lax.dynamic_update_slice(v_st, v[None], (i, 0, cpos, 0, 0))
        return attn_causal(q, k_st[i], v_st[i], mask, ap, c)

    x, states, tails = _run_bucket(params, tokens, c, states, tails, attn_mix)
    logits = _logits(params, x, c) if return_logits else None
    return logits, (k_st, v_st, states, tails)


def decode_step(params, tok, pos, active, arena, tables, c: GraniteHybridConfig,
                attn_fn: Optional[Callable] = None):
    """One decode step of a slot batch off the arena (block leaves k, v ``[La,
    N, bs, KV, Dh]`` through the tables [B, nb]; slot leaves state ``[Ls,
    B + 1, H / k, N, k P]`` and tails ``[Ls, B + 1, conv - 1, W]``, lane b row b).
    With ``attn_fn`` (the block-table kernel) the scan runs ``ssm_decode_step``
    too; without it both take their XLA formulation. A dead lane's state and
    tails stay as they were. -> (logits [B, V], arena, pos', aux [5] int32:
    the MoE stats summed over layers, then the (live lane, SSM layer) state
    updates)."""
    from nnstreamer_tpu.kv.block_attn import paged_attention_ref, write_fresh_window
    from nnstreamer_tpu.ops.dispatch import record
    from nnstreamer_tpu.ops.pallas.ssm import ssm_decode_step, ssm_decode_step_ref

    impl = "xla" if attn_fn is None else "pallas"
    record("ssm_recurrence", impl)
    recur = ssm_decode_step_ref if attn_fn is None else ssm_decode_step
    k_arena, v_arena, state, tails = arena
    n = tok.shape[0]
    fill = jnp.where(active, pos, 0)
    live = active[:, None]
    fresh_k, fresh_v = [], []

    def ssm_mix(j, a, sp):
        nonlocal state, tails
        z, x, bm, cm, dt, la, window = ssm_project(a, live, tails[j, :n], sp, c)
        tails = tails.at[j, :n].set(jnp.where(
            active[:, None, None], window[:, 1:].astype(tails.dtype), tails[j, :n]))
        state, y = recur(state, x[:, 0], bm[:, 0], cm[:, 0], dt[:, 0],
                         jnp.exp(la[:, 0]), sp["d_skip"], active, layer=j)
        return ssm_output(y[:, None], z, sp, c)

    def attn_mix(i, a, ap):
        q, k, v = attn_project(a, ap, c, k_arena.dtype)
        fresh_k.append(k)
        fresh_v.append(v)
        # the history off the arena through the tables (the kernel reads the
        # WHOLE leaf at layer i; its oracle one layer's), the pending token's
        # own column, not in the arena yet, folded last
        if attn_fn is not None:
            o = attn_fn(q, k_arena, v_arena, tables, fill, (k, v), layer=i)
        else:
            o = paged_attention_ref(q, k_arena[i], v_arena[i], tables, fill, (k, v),
                                    scale=c.attn_scale)
        return _mm("bte,ed->btd", o.reshape(n, 1, -1), ap["wo"])

    x, stats = _layers(params, _embed(params, tok, c)[:, None, :], c, live,
                       ssm_mix, attn_mix)
    blocks = (k_arena, v_arena)
    if fresh_k:
        blocks = write_fresh_window(
            blocks, tables, (jnp.stack(fresh_k), jnp.stack(fresh_v)),
            pos, 1, active, False, per_layer=True)
    aux = jnp.concatenate([stats, (jnp.sum(active) * c.n_ssm)[None].astype(jnp.int32)])
    return (_logits(params, x, c)[:, 0], tuple(blocks) + (state, tails),
            pos + active.astype(jnp.int32), aux)


# -- the family the batcher serves -------------------------------------------


class GraniteHybridFamily:
    """What ``ContinuousBatcher``'s paged path asks of a block family
    (models/family.py), for the Granite-4.0-H layers: the dense family's two
    block leaves (the attention layers' keys and values) and two slot leaves
    (the Mamba-2 layers' state and convolution tails)."""

    name = "granite_hybrid"
    pad_id = -1                       # padding routes nowhere and moves no state
    slot_leaves = 2
    aux_names: Tuple[str, ...] = AUX_NAMES
    aux_prefix = "moe_"               # stats() keys: moe_tokens, ..., moe_state_updates
    decode_kernel = "paged_decode_attention"
    # what the paged path offers and this family does not carry: the state at
    # a block boundary is not kept, so a prefix cannot be adopted
    unsupported = ("prefix sharing", "speculate", "cache-dtype=int8",
                   "kv-layout=slot", "windowed", "mesh", "draft model",
                   "migration", "snapshot")

    def __init__(self, config: GraniteHybridConfig, dtype):
        self.config = config
        self.dtype = jnp.dtype(dtype)

    def arena(self, n_blocks: int, block_size: int, quantized: bool = False,
              n_slots: int = 0):
        from nnstreamer_tpu.kv.gather import init_arena

        c = self.config
        return (init_arena(c.n_attn, n_blocks, block_size, c.n_kv_heads, c.head_dim,
                           False, self.dtype)
                + empty_slot_stage(c, n_slots + 1, self.dtype))

    def stage(self, length: int):
        c = self.config
        shape = (c.n_attn, 1, length, c.n_kv_heads, c.head_dim)
        return ((jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype))
                + empty_slot_stage(c, 1, self.dtype))

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
        from nnstreamer_tpu.ops.pallas.paged_attention import make_paged_attention

        return make_paged_attention(scale=self.config.attn_scale)

    def note_aux(self, counts: Dict[str, int], registry) -> None:
        """One harvested pump's counters (``AUX_NAMES``, summed on the device
        over the pump's steps and layers): an ``nns.moe.routing`` and an
        ``nns.state.update`` instant, and their counters."""
        from nnstreamer_tpu import trace as _trace

        c = self.config
        updates = counts["state_updates"]
        # one layer's state of one slot, read and written
        per_update = 2 * c.ssm_heads * c.ssm_head_dim * c.ssm_state * 4
        _trace.instant("nns.moe.routing", **{k: counts[k] for k in MOE_STATS})
        _trace.instant("nns.state.update", slot_layers=updates,
                       bytes=updates * per_update)
        if registry is None:
            return
        for k in MOE_STATS:
            registry.counter(f"nns_moe_{k}_total").inc(counts[k])
        registry.counter("nns_slot_state_updates_total").inc(updates)
