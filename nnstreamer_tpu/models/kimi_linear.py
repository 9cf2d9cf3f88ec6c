"""Kimi-Linear block family: Kimi Delta Attention (KDA) layers beside latent
(MLA, no positional encoding) layers, a leading dense FFN, then sigmoid-routed
experts with a shared expert, for one chip's share of the routed experts.

Layer l of 1..n_layers, ``x`` the float32 residual, every norm an RMSNorm::

    x = x + Attn_l(norm(x));  x = x + FFN_l(norm(x))

``Attn_l`` is MLA where l is in ``full_attn_layers`` and KDA elsewhere; ``FFN_l``
is a SwiGLU of width ``d_ff`` for the first ``n_dense`` layers and the expert
layer after them.

KDA, per head (H heads of d_k = d_v)::

    q~, k~, v = SiLU(conv4(a W_q)), SiLU(conv4(a W_k)), SiLU(conv4(a W_v))
    q = q~ / |q~| / sqrt(d_k);  k = k~ / |k~|
    g_t = -exp(A_log) softplus(W_fb (W_fa a_t) + dt_bias),  alpha_t = exp(g_t)
    beta_t = sigmoid(a_t W_beta)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    y_t = W_o [ RMSNorm_head(S_t^T q_t) * sigmoid(W_gb (W_ga a_t)) ]

The model keeps TWO kinds of cache. An MLA layer leaves, per token, the normed
latent ``c`` and the shared key ``k_r`` (unrotated, padded to whole lanes): the
block leaves ``[MLA layers, N, bs, ...]`` LongCat-Flash's family has, read by
the same kernel. A KDA layer leaves nothing per token: per SLOT it keeps the
float32 state ``S`` [H, d_k, d_v] and the last ``conv - 1`` inputs of its
convolutions: the slot leaves ``[KDA layers, n_slots + 1, ...]`` (lane b is row
b, the last row is scratch), carried and donated with the block leaves.
Prefill and ``chunk`` compute the recurrence chunk by chunk (chunks of 64:
inside a chunk by the triangular system of the delta rule, between chunks
through ``S``; every decay is ``exp(G_i - G_j)`` for i >= j in float32); decode
is one kernel that reads each head's state once and writes it in place
(ops/pallas/kda.py), or the plain recurrence off a TPU. Padding (ids < 0)
leaves the state as it was: ``beta = 0``, ``g = 0`` there, and the convolution
tail is the last three REAL inputs.

The expert layer routes over every router output (sigmoid in float32 at highest
precision, top-k of ``s + bias``, weights ``s / sum(s picked) * scale``), runs
the pairs that fall on the experts held here through LongCat-Flash's
sort-by-held-expert dispatch (a prompt's bucket) or each held expert that
some token picked over every token, weighted by its gate (a decode step's few
tokens: ``ops/pallas/held_experts.held_experts_ffn``), and adds the shared
expert for every token. What the absent experts would have added is
left out.

Storage dtype is stated by the caller, as in models/longcat.py: weights, latent
cache and convolution inputs in ``dtype``; residual, norms, softmax, router,
gates and the recurrent state float32. Weights follow the recipe of
``benchmark/reference/kimi_linear.py``; the two modules share no code.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from nnstreamer_tpu.models.longcat import (
    LANES,
    NEG_INF,
    _draw,
    _draw_experts,
    _mm,
    dispatch_held,
    ffn,
    mla_absorb_q,
    mla_attend_absorbed,
    mla_attend_expanded,
    mla_unabsorb,
    rmsnorm,
)
from nnstreamer_tpu.ops.dispatch import record
from nnstreamer_tpu.ops.pallas._compat import pallas_ok
from nnstreamer_tpu.ops.pallas.held_experts import (
    gates_of,
    held_experts_ffn,
    held_experts_ffn_ref,
)

# the counters one decode step adds up on the device (nns.moe.routing,
# nns.state.update)
MOE_STATS = ("tokens", "local_pairs", "experts_hit", "picks")
AUX_NAMES = MOE_STATS + ("state_updates",)

# key schedule and draws of ``seed:<n>`` (stated in the configuration's file)
_K_WQ, _K_WK, _K_WV, _K_WO, _K_WFA, _K_WFB, _K_WGA, _K_WGB, _K_WBETA = range(1, 10)
_K_CONV_Q, _K_CONV_K, _K_CONV_V, _K_A_LOG, _K_DT_BIAS = 10, 11, 12, 13, 14
_M_WQ, _M_WKVA, _M_WKVB, _M_WO = 20, 21, 22, 23
_FFN_GATE, _FFN_UP, _FFN_DOWN = 30, 31, 32
_ROUTER, _ROUTER_BIAS, _EXP_GATE, _EXP_UP, _EXP_DOWN = 40, 41, 50, 51, 52
_SH_GATE, _SH_UP, _SH_DOWN = 60, 61, 62
_EMBED, _HEAD, _LAYERS = 1, 2, 3
_EMBED_STD, _ROUTER_BIAS_STD = 0.02, 1e-2
_A_RANGE, _DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)
_NORM_EPS = 1e-6

KDA_CHUNK = 64
# a quarter of the picks are local when a quarter of the experts are held (2
# of 8 a token): a bucket of more than MOE_FEW_PAIRS pairs tries 5/16 of them
# first (a prompt's 4096 pairs hold 1024 +- 28 local ones)
MOE_FEW = 5 / 16
# up to this many tokens (a decode step's lanes) each held expert that some
# token picked streams once, every token through it (``held_experts_ffn``): at
# 128 tokens an expert is 1.8 GFLOP, under the time its weights take to
# stream; the grouped matmul over 4 pairs an expert took 2.3 ms a layer
# (PERF.md section 6)
MOE_DENSE_TOKENS = 128
_HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class KimiLinearConfig:
    """Published widths by default; ``n_layers``, ``n_held`` and ``vocab``
    are the chip's share."""

    d_model: int = 2304
    n_heads: int = 32
    kv_rank: int = 512
    nope: int = 128
    rope: int = 64
    v_dim: int = 128
    kda_heads: int = 32
    kda_dim: int = 128
    conv: int = 4
    gate_rank: int = 128
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    n_dense: int = 1
    d_ff: int = 9216
    d_expert: int = 1024
    n_routed: int = 256
    topk: int = 8
    scale: float = 2.446
    eps: float = 1e-5
    n_layers: int = 27
    vocab: int = 163840
    n_held: int = 256
    expert_offset: int = 0

    def __post_init__(self):
        if not 0 <= self.expert_offset <= self.n_routed - self.n_held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + self.n_held})"
                f" are not among the {self.n_routed} routed experts"
            )
        if self.n_held < 1:
            raise ValueError("experts_held must be at least 1")

    @property
    def kr_width(self) -> int:
        """Lanes of the k_r arena leaf: ``rope`` padded to whole lanes."""
        return -(-self.rope // LANES) * LANES

    @property
    def mla_layers(self) -> Tuple[int, ...]:
        """The 1-based layers that are MLA, of those this model has."""
        return tuple(l for l in self.full_attn_layers if l <= self.n_layers)

    @property
    def n_mla(self) -> int:
        return len(self.mla_layers)

    @property
    def n_kda(self) -> int:
        return self.n_layers - self.n_mla

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_dim


def config_from_options(options: Dict[str, str]) -> KimiLinearConfig:
    """``custom=`` of ``zoo:kimi_linear_lm``: every width by its short name,
    ``n_layers``, ``experts_held``, ``expert_offset``, ``vocab``."""
    names = {
        "d_model": int, "n_heads": int, "kv_rank": int, "nope": int, "rope": int,
        "v_dim": int, "kda_heads": int, "kda_dim": int, "conv": int,
        "gate_rank": int, "n_dense": int, "d_ff": int, "d_expert": int,
        "n_routed": int, "topk": int, "scale": float, "eps": float,
        "n_layers": int, "vocab": int, "expert_offset": int,
    }
    kw = {k: conv(options[k]) for k, conv in names.items() if k in options}
    if "experts_held" in options:
        kw["n_held"] = int(options["experts_held"])
    elif "n_routed" in kw:
        kw["n_held"] = kw["n_routed"]
    return KimiLinearConfig(**kw)


# -- weights -----------------------------------------------------------------


def init_params(c: KimiLinearConfig, seed: int, dtype=jnp.bfloat16) -> Dict:
    """Draw the weights of ``seed`` tensor by tensor in float32 (every matrix
    normal(0, 1/fan_in)) and round each once to ``dtype``. Layers are a list:
    they differ in kind, and the step programs unroll them."""
    root = jax.random.PRNGKey(seed)
    d, h, cw, r = c.d_model, c.n_heads, c.kda_width, c.gate_rank

    def layer(index: int) -> Dict:
        lk = jax.random.fold_in(jax.random.fold_in(root, _LAYERS), index - 1)

        def t(tensor, shape, dt=dtype):
            return _draw(jax.random.fold_in(lk, tensor), shape[0] ** -0.5,
                         shape=shape, dtype=dt)

        def uniform(tensor, n, lo, hi):
            return jax.random.uniform(jax.random.fold_in(lk, tensor), (n,),
                                      jnp.float32, lo, hi)

        def swiglu(gate, up, down, width):
            return {"w_gate": t(gate, (d, width)), "w_up": t(up, (d, width)),
                    "w_down": t(down, (width, d))}

        def kda():
            dt_ = jnp.exp(uniform(_K_DT_BIAS, cw, math.log(_DT_RANGE[0]),
                                  math.log(_DT_RANGE[1])))
            return {
                # q, k and v side by side: one projection, one convolution
                "wqkv": jnp.concatenate(
                    [t(i, (d, cw)) for i in (_K_WQ, _K_WK, _K_WV)], axis=1),
                "conv": jnp.concatenate(
                    [t(i, (c.conv, cw)) for i in (_K_CONV_Q, _K_CONV_K, _K_CONV_V)],
                    axis=1),
                "wfa": t(_K_WFA, (d, r)), "wfb": t(_K_WFB, (r, cw)),
                "wga": t(_K_WGA, (d, r)), "wgb": t(_K_WGB, (r, cw)),
                "wbeta": t(_K_WBETA, (d, c.kda_heads)),
                "a_log": jnp.log(uniform(_K_A_LOG, c.kda_heads, *_A_RANGE)),
                "dt_bias": dt_ + jnp.log(-jnp.expm1(-dt_)),
                "o_norm": jnp.ones((c.kda_dim,), jnp.float32),
                "wo": t(_K_WO, (cw, d)),
            }

        def mla():
            wkvb = t(_M_WKVB, (c.kv_rank, h * (c.nope + c.v_dim)))
            wkvb = wkvb.reshape(c.kv_rank, h, c.nope + c.v_dim)
            return {
                "wq": t(_M_WQ, (d, h * (c.nope + c.rope))),
                "wkva": t(_M_WKVA, (d, c.kv_rank + c.rope)),
                "kv_norm": jnp.ones((c.kv_rank,), jnp.float32),
                "wkv_k": wkvb[..., :c.nope],
                "wkv_v": wkvb[..., c.nope:],
                "wo": t(_M_WO, (h * c.v_dim, d)),
            }

        def experts(tensor, shape):
            return _draw_experts(
                jax.random.fold_in(lk, tensor), c.expert_offset, shape[0] ** -0.5,
                n=c.n_held, shape=shape, dtype=dtype)

        lp = {
            "norm_in": jnp.ones((d,), jnp.float32),
            "norm_post": jnp.ones((d,), jnp.float32),
            "attn": mla() if index in c.mla_layers else kda(),
        }
        if index <= c.n_dense:
            lp["ffn"] = swiglu(_FFN_GATE, _FFN_UP, _FFN_DOWN, c.d_ff)
            return lp
        lp.update({
            # the router stays float32: it runs at highest precision
            "router": t(_ROUTER, (d, c.n_routed), jnp.float32),
            "router_bias": _draw(jax.random.fold_in(lk, _ROUTER_BIAS),
                                 _ROUTER_BIAS_STD, shape=(c.n_routed,),
                                 dtype=jnp.float32),
            "e_gate": experts(_EXP_GATE, (d, c.d_expert)),
            "e_up": experts(_EXP_UP, (d, c.d_expert)),
            "e_down": experts(_EXP_DOWN, (c.d_expert, d)),
            "shared": swiglu(_SH_GATE, _SH_UP, _SH_DOWN, c.d_expert),
        })
        return lp

    return {
        "embed": _draw(jax.random.fold_in(root, _EMBED), _EMBED_STD,
                       shape=(c.vocab, d), dtype=dtype),
        "layers": [layer(i) for i in range(1, c.n_layers + 1)],
        "ln_f": jnp.ones((d,), jnp.float32),
        "head": _draw(jax.random.fold_in(root, _HEAD), d ** -0.5,
                      shape=(d, c.vocab), dtype=dtype),
    }


# -- Kimi Delta Attention ----------------------------------------------------


def _stored(x, dtype):
    """x float32 rounded to what ``dtype`` holds, still float32. For bfloat16
    an explicit ``reduce_precision``: a convert to bfloat16 and back the TPU
    compiler may drop (``xla_allow_excess_precision``), and the convolution
    would then see inputs the cache never held."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype).astype(jnp.float32)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _NORM_EPS)


def kda_project(a, live, tail, ap: Dict, c: KimiLinearConfig):
    """a [B, T, d] float32 (normed), live [B, T] bool, tail [B, conv - 1, 3C]
    the convolutions' inputs at the positions before these -> q, k, v, g
    [B, T, H, d_k] float32, beta [B, T, H], window [B, conv - 1 + T, 3C] float32
    (the tail then these positions' inputs, rounded as cached). Where ``live`` is false
    ``beta`` and ``g`` are zero: the token leaves the state as it was."""
    b, t, _ = a.shape
    h, dk, taps = c.kda_heads, c.kda_dim, c.conv
    u = _stored(_mm("btd,dc->btc", a, ap["wqkv"]), tail.dtype)
    window = jnp.concatenate([tail.astype(jnp.float32), u], axis=1)
    w32 = ap["conv"].astype(jnp.float32)
    y = sum(window[:, i:i + t] * w32[i] for i in range(taps))
    q, k, v = (z.reshape(b, t, h, dk) for z in jnp.split(jax.nn.silu(y), 3, axis=-1))
    f = _mm("btr,rc->btc", _mm("btd,dr->btr", a, ap["wfa"]), ap["wfb"])
    g = -jnp.exp(ap["a_log"])[:, None] * jax.nn.softplus(
        f + ap["dt_bias"]).reshape(b, t, h, dk)
    beta = jax.nn.sigmoid(_mm("btd,dh->bth", a, ap["wbeta"]))
    keep = live[..., None]
    return (_l2norm(q) * dk ** -0.5, _l2norm(k), v,
            jnp.where(keep[..., None], g, 0.0), jnp.where(keep, beta, 0.0), window)


def kda_output(o, a, ap: Dict, c: KimiLinearConfig):
    """o [B, T, H, d_v] float32 (S^T q) -> the layer's [B, T, d]: the head
    norm, the low-rank output gate, W_o."""
    b, t = o.shape[:2]
    gate = _mm("btr,rc->btc", _mm("btd,dr->btr", a, ap["wga"]), ap["wgb"])
    o = rmsnorm(o, ap["o_norm"], c.eps) * jax.nn.sigmoid(gate).reshape(o.shape)
    return _mm("btc,cd->btd", o.reshape(b, t, -1), ap["wo"])


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular a [..., C, C]: a is nilpotent,
    so the inverse is the finite product prod_m (I + (-a)^(2^m))."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)
    power = -a
    inv = eye + power
    for _ in range(max(0, math.ceil(math.log2(n)) - 1)):
        power = jnp.matmul(power, power, precision=_HI)
        inv = inv + jnp.matmul(inv, power, precision=_HI)
    return inv


def kda_chunked(q, k, v, g, beta, state, chunk: int = KDA_CHUNK):
    """The recurrence over T positions, chunk by chunk. q, k, g [B, T, H, dk],
    v [B, T, H, dv], beta [B, T, H] float32; state [B, H, dk, dv] -> (o
    [B, T, H, dv], state after T). With G the running sum of g inside a chunk
    and S the state at its start, the chunk's new values W solve
    ``(I + A) W = beta (V - (K e^G) S)``, ``A_ij = beta_i k_i.(e^(G_i - G_j)
    k_j)`` for j < i; ``O = (Q e^G) S + P W``, ``P_ij = q_i.(e^(G_i - G_j)
    k_j)`` for j <= i; and the state moves to ``e^(G_C) S + (K e^(G_C - G))^T
    W``. Every exponent is of a sum of g over positions i >= j, never positive."""
    b, t, h, dk = q.shape
    cs = min(chunk, t)
    pad = -t % cs
    if pad:  # whole chunks: the padding decays nothing and writes nothing
        q, k, v, g = (jnp.pad(z, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for z in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (t + pad) // cs

    def chunks(z):  # [B, T, H, ...] -> [N, B, H, C, ...]
        z = z.reshape((b, n, cs) + z.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(z, 3, 2), 1, 0)

    rows = jnp.arange(2 * cs)[:, None] % cs
    cols = jnp.arange(cs)[None, :]
    # the q rows see j <= i, the k rows j < i
    seen = jnp.where(jnp.arange(2 * cs)[:, None] < cs, rows >= cols, rows > cols)
    mm = lambda spec, x, y: jnp.einsum(spec, x, y, precision=_HI)  # noqa: E731

    def one(state, xs):
        qc, kc, vc, gc, bc = xs                       # [B, H, C, ...]
        gsum = jnp.cumsum(gc, axis=2)
        x2 = jnp.concatenate([qc, kc], axis=2)        # [B, H, 2C, dk]
        g2 = jnp.concatenate([gsum, gsum], axis=2)
        diff = g2[:, :, :, None, :] - gsum[:, :, None, :, :]
        decay = jnp.exp(jnp.where(seen[:, :, None], diff, -jnp.inf))
        m = jnp.sum(x2[:, :, :, None, :] * decay * kc[:, :, None, :, :], axis=-1)
        inv = _unit_lower_inverse(bc[..., None] * m[:, :, cs:])
        eg = jnp.exp(gsum)
        w = mm("bhij,bhjv->bhiv", inv,
               bc[..., None] * (vc - mm("bhck,bhkv->bhcv", kc * eg, state)))
        o = mm("bhck,bhkv->bhcv", qc * eg, state) + mm("bhij,bhjv->bhiv", m[:, :, :cs], w)
        last = gsum[:, :, -1:]
        state = (jnp.exp(last[:, :, 0])[..., None] * state
                 + mm("bhck,bhcv->bhkv", kc * jnp.exp(last - gsum), w))
        return state, o

    state, o = jax.lax.scan(one, state, tuple(chunks(z) for z in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, n * cs, h, -1)
    return o[:, :t], state


def kda_recurrent(q, k, v, g, beta, state):
    """The same recurrence token by token (the oracle of ``kda_chunked``)."""
    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        u = jnp.sum(k_t[..., None] * s, axis=-2)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - u)[:, :, None, :]
        return s, jnp.sum(q_t[..., None] * s, axis=-2)

    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _real_tail(window, live, taps: int):
    """The last ``taps - 1`` REAL inputs of each row: window [B, taps - 1 + T,
    C] is the old tail then a bucket's inputs, of which the first sum(live)
    are real (padding sits at the end)."""
    n_real = jnp.sum(live, axis=1).astype(jnp.int32)
    return jax.vmap(
        lambda w, n: jax.lax.dynamic_slice_in_dim(w, n, taps - 1, axis=0)
    )(window, n_real)


# -- latent attention, no rotation -------------------------------------------


def mla_project(a, ap: Dict, c: KimiLinearConfig):
    """a [B, T, d] float32 -> q_nope [B,T,H,nope], q_rope [B,T,H,rope] (not
    rotated) and each position's cache entry: latent [B,T,kv_rank], k_r
    [B,T,rope]."""
    b, t, _ = a.shape
    q = _mm("btd,de->bte", a, ap["wq"]).reshape(b, t, c.n_heads, c.nope + c.rope)
    ckr = _mm("btd,de->bte", a, ap["wkva"])
    lat = rmsnorm(ckr[..., :c.kv_rank], ap["kv_norm"], c.eps)
    return q[..., :c.nope], q[..., c.nope:], lat, ckr[..., c.kv_rank:]


def _pad_kr(k_r, c: KimiLinearConfig):
    return jnp.pad(k_r, ((0, 0), (0, 0), (0, c.kr_width - c.rope)))


# -- the expert layer --------------------------------------------------------


def route(b, lp: Dict, c: KimiLinearConfig):
    """b [T, d] float32 -> (idx [T, topk] router outputs chosen, w [T, topk]
    their weights ``s / sum(s chosen) * scale``). The bias moves the choice,
    not the weight."""
    s = jax.nn.sigmoid(jnp.einsum("td,dr->tr", b, lp["router"], precision=_HI))
    _, idx = jax.lax.top_k(s + lp["router_bias"], c.topk)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, picked / jnp.sum(picked, axis=-1, keepdims=True) * c.scale


def experts_dense(b, w, local, group, lp: Dict, n: int):
    """The held experts' weighted outputs by three batched matmuls: every
    held expert over every token, weighted by its gate (zero where the token
    did not pick it). For a decode step's few tokens the matrix unit is idle
    and the weights stream once at its pace; the grouped matmul's many small
    groups (4 pairs an expert) do not. Arguments and results as
    ``longcat.dispatch_held``."""
    dt = lp["e_gate"].dtype
    hit = local[..., None] & (group[..., None] == jnp.arange(n))     # [T, k, n]
    gates = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)        # [T, n]
    x = b.astype(dt)
    gate = jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["e_gate"],
                                  preferred_element_type=jnp.float32))
    up = jnp.einsum("td,edf->etf", x, lp["e_up"], preferred_element_type=jnp.float32)
    ys = jnp.einsum("etf,efd->etd", (gate * up).astype(dt), lp["e_down"],
                    preferred_element_type=jnp.float32)
    y = jnp.einsum("te,etd->td", gates, ys, precision=_HI)
    return y, jnp.sum(jnp.any(hit, axis=1), axis=0).astype(jnp.int32)


def moe(b, live, lp: Dict, c: KimiLinearConfig, shared: bool = True):
    """The expert layer's share. b [T, d] float32 (normed), live [T] bool ->
    (y [T, d] float32: the held experts' weighted outputs and, where
    ``shared``, the shared expert's; stats [4] int32 in ``MOE_STATS`` order).
    Up to ``MOE_DENSE_TOKENS`` tokens (a decode step) each held expert that
    some token picked runs over every token (``held_experts_ffn``); a
    prompt's bucket takes the sort-by-held-expert dispatch."""
    e0, n = c.expert_offset, c.n_held
    idx, w = route(b, lp, c)
    local = live[:, None] & (idx >= e0) & (idx < e0 + n)
    group = jnp.where(local, idx - e0, n)
    if b.shape[0] <= MOE_DENSE_TOKENS:
        ok, _ = pallas_ok("held_experts_ffn", lp["e_gate"].dtype)
        record("moe_experts", "pallas" if ok else "jnp")
        y, sizes = (held_experts_ffn if ok else held_experts_ffn_ref)(
            b.astype(lp["e_gate"].dtype), gates_of(w, group, n),
            lp["e_gate"], lp["e_up"], lp["e_down"])
    else:
        y, sizes = dispatch_held(b, w, local, group, lp, n, few=MOE_FEW)
    if shared:
        y = y + ffn(b[None], lp["shared"])[0]
    stats = jnp.stack([
        jnp.sum(live), jnp.sum(local), jnp.sum(sizes > 0), jnp.sum(live) * c.topk,
    ]).astype(jnp.int32)
    return y, stats


# -- whole forwards ----------------------------------------------------------


def _embed(params, tokens):
    return params["embed"][jnp.maximum(tokens, 0)].astype(jnp.float32)


def _logits(params, x, c: KimiLinearConfig):
    return _mm("...d,dv->...v", rmsnorm(x, params["ln_f"], c.eps), params["head"])


def _layers(params, x, c: KimiLinearConfig, live, kda_attend, mla_attend):
    """Every layer over x [B, T, d]. ``kda_attend(j, a, ap)`` / ``mla_attend(i,
    a, ap)`` -> the attention's [B, T, d] for KDA layer j / MLA layer i (each
    kind counted on its own: the index into its cache). Returns (x, MoE stats
    summed over the expert layers)."""
    b_, t_, d = x.shape
    stats = jnp.zeros((len(MOE_STATS),), jnp.int32)
    seen = {"kda": 0, "mla": 0}
    for index, lp in enumerate(params["layers"], start=1):
        kind = "mla" if index in c.mla_layers else "kda"
        with jax.named_scope("nns." + kind):
            a = rmsnorm(x, lp["norm_in"], c.eps)
            attend = mla_attend if kind == "mla" else kda_attend
            x = x + attend(seen[kind], a, lp["attn"])
            seen[kind] += 1
        b = rmsnorm(x, lp["norm_post"], c.eps)
        if "ffn" in lp:
            with jax.named_scope("nns.ffn"):
                x = x + ffn(b, lp["ffn"])
        else:
            with jax.named_scope("nns.moe"):
                y, st = moe(b.reshape(-1, d), live.reshape(-1), lp, c)
                x = x + y.reshape(b_, t_, d)
                stats = stats + st
    return x, stats


def _run_bucket(params, tokens, c: KimiLinearConfig, states, tails, mla_attend):
    """What prefill and chunk share: a bucket's layers with KDA in the
    chunkwise form from ``states`` [Lk, B, H, dk, dv] and ``tails`` [Lk, B,
    conv - 1, 3C] -> (x, states, tails after the bucket's real tokens)."""
    live = tokens >= 0
    new_states, new_tails = [], []

    def kda_attend(j, a, ap):
        q, k, v, g, beta, window = kda_project(a, live, tails[j], ap, c)
        o, s = kda_chunked(q, k, v, g, beta, states[j])
        new_states.append(s)
        new_tails.append(_real_tail(window, live, c.conv))
        return kda_output(o, a, ap, c)

    x, _ = _layers(params, _embed(params, tokens), c, live, kda_attend, mla_attend)
    return x, jnp.stack(new_states), jnp.stack(new_tails).astype(tails.dtype)


def empty_slot_stage(c: KimiLinearConfig, batch: int, dtype):
    """Zero state and convolution tails of ``batch`` sequences: a prompt's
    start."""
    return (jnp.zeros((c.n_kda, batch, c.kda_heads, c.kda_dim, c.kda_dim), jnp.float32),
            jnp.zeros((c.n_kda, batch, c.conv - 1, 3 * c.kda_width), dtype))


def prefill(params, tokens, c: KimiLinearConfig, cache_dtype):
    """tokens [B, T] (ids < 0 are padding, at the end) -> (logits [B, T, V]
    float32, stage: the latents (lat [Lm, B, T, kv_rank], k_r [Lm, B, T,
    kr_width]) in the cache's dtype, then the state [Lk, B, H, dk, dv] float32
    and the convolution tails [Lk, B, conv - 1, 3C] after the real tokens)."""
    b, t = tokens.shape
    mask = jnp.broadcast_to(
        (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])[None], (b, t, t))
    lats, krs = [], []

    def mla_attend(i, a, ap):
        q_nope, q_rope, lat, k_r = mla_project(a, ap, c)
        lat, k_r = lat.astype(cache_dtype), k_r.astype(cache_dtype)
        lats.append(lat)
        krs.append(_pad_kr(k_r, c))
        o = mla_attend_expanded(q_nope, q_rope, lat, k_r, ap, c, mask)
        return _mm("bte,ed->btd", o, ap["wo"])

    x, states, tails = _run_bucket(
        params, tokens, c, *empty_slot_stage(c, b, cache_dtype), mla_attend)
    def stacked(xs, width):  # a cut with no MLA layer has block leaves of no layers
        return jnp.stack(xs) if xs else jnp.zeros((0, b, t, width), cache_dtype)

    return _logits(params, x, c), (
        stacked(lats, c.kv_rank), stacked(krs, c.kr_width), states, tails)


def apply(params, tokens, c: KimiLinearConfig, cache_dtype=None):
    """tokens [B, T] -> logits [B, T, V] float32 (the full forward)."""
    cache_dtype = cache_dtype or params["embed"].dtype
    return prefill(params, tokens, c, cache_dtype)[0]


def chunk(params, tokens, cpos, stage, c: KimiLinearConfig,
          return_logits: bool = True):
    """One bucket of chunked prefill at absolute position ``cpos`` against a
    stage (lat [Lm, 1, S, kv_rank], k_r [Lm, 1, S, kr_width], state [Lk, 1, H,
    dk, dv], tails [Lk, 1, conv - 1, 3C]): the bucket's latents are written at
    ``cpos`` and its queries attend the stage up to their own positions in
    the absorbed form; the recurrence goes on from the stage's state and
    tails. -> (logits or None, stage)."""
    b, t = tokens.shape
    lat_st, kr_st, states, tails = stage
    s_len = lat_st.shape[2]
    positions = cpos + jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    mask = jnp.arange(s_len)[None, None, :] <= positions[:, :, None]

    def mla_attend(i, a, ap):
        nonlocal lat_st, kr_st
        q_nope, q_rope, lat, k_r = mla_project(a, ap, c)
        lat_st = jax.lax.dynamic_update_slice(
            lat_st, lat.astype(lat_st.dtype)[None], (i, 0, cpos, 0))
        kr_st = jax.lax.dynamic_update_slice(
            kr_st, _pad_kr(k_r, c).astype(kr_st.dtype)[None], (i, 0, cpos, 0))
        o = mla_attend_absorbed(
            mla_absorb_q(q_nope, ap), q_rope, lat_st[i], kr_st[i], ap, c, mask)
        return _mm("bte,ed->btd", o, ap["wo"])

    x, states, tails = _run_bucket(params, tokens, c, states, tails, mla_attend)
    logits = _logits(params, x, c) if return_logits else None
    return logits, (lat_st, kr_st, states, tails)


def decode_step(params, tok, pos, active, arena, tables, c: KimiLinearConfig,
                attn_fn: Optional[Callable] = None):
    """One decode step of a slot batch off the arena (block leaves lat, k_r
    ``[Lm, N, bs, ...]`` through the tables [B, nb]; slot leaves state ``[Lk,
    B + 1, H, dk, dv]`` and tails ``[Lk, B + 1, conv - 1, 3C]``, lane b row
    b). With ``attn_fn`` (the latent block-table kernel) the recurrence runs
    ``kda_decode_step`` too; without it both take their XLA formulation. A
    dead lane's state and tails stay as they were. -> (logits [B, V], arena,
    pos', aux [5] int32: the MoE stats summed over layers, then the (live
    lane, KDA layer) state updates)."""
    from nnstreamer_tpu.kv.block_attn import write_fresh_window
    from nnstreamer_tpu.ops.pallas.kda import kda_decode_step, kda_decode_step_ref
    from nnstreamer_tpu.ops.pallas.mla_attention import mla_paged_attention_ref

    impl = "xla" if attn_fn is None else "pallas"
    record("mla_attention", impl)
    record("kda_recurrence", impl)
    recur = kda_decode_step_ref if attn_fn is None else kda_decode_step
    lat_arena, kr_arena, state, tails = arena
    n = tok.shape[0]
    fill = jnp.where(active, pos, 0)
    live = active[:, None]
    fresh_lat, fresh_kr = [], []
    sm_scale = 1.0 / math.sqrt(c.nope + c.rope)

    def kda_attend(j, a, ap):
        nonlocal state, tails
        q, k, v, g, beta, window = kda_project(a, live, tails[j, :n], ap, c)
        tails = tails.at[j, :n].set(jnp.where(
            active[:, None, None], window[:, 1:].astype(tails.dtype), tails[j, :n]))
        state, o = recur(state, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                         beta[:, 0], active, layer=j)
        return kda_output(o[:, None], a, ap, c)

    def mla_attend(i, a, ap):
        q_nope, q_rope, lat, k_r = mla_project(a, ap, c)
        lat = lat.astype(lat_arena.dtype)
        k_r = _pad_kr(k_r, c).astype(kr_arena.dtype)
        fresh_lat.append(lat)
        fresh_kr.append(k_r)
        # as longcat.decode_step's attend: the history off the arena (the
        # kernel, or the XLA view path), merged with the pending token's own
        # column, which is not in the arena yet (one online-softmax step)
        q_lat = mla_absorb_q(q_nope, ap)                       # [B, 1, H, kv_rank]
        dt = lat.dtype
        qr = jnp.pad(q_rope, ((0, 0),) * 3 + ((0, c.kr_width - c.rope),))
        s1 = (jnp.einsum("bthr,btr->bh", q_lat.astype(dt), lat,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bthd,btd->bh", qr.astype(dt), k_r,
                           preferred_element_type=jnp.float32)) * sm_scale
        o_h, m_h, l_h = (attn_fn or mla_paged_attention_ref)(
            q_lat[:, 0].astype(dt), qr[:, 0].astype(dt), lat_arena, kr_arena,
            tables, fill, layer=i, scale=sm_scale)
        m = jnp.maximum(m_h, s1)
        alpha = jnp.where(m_h <= NEG_INF, 0.0, jnp.exp(m_h - m)) * l_h
        p1 = jnp.exp(s1 - m)
        o_lat = (o_h * alpha[..., None]
                 + p1[..., None] * lat[:, 0, None, :].astype(jnp.float32)
                 ) / (alpha + p1)[..., None]
        return _mm("bte,ed->btd", mla_unabsorb(o_lat[:, None], ap), ap["wo"])

    x, stats = _layers(params, _embed(params, tok)[:, None, :], c, live,
                       kda_attend, mla_attend)
    blocks = write_fresh_window(
        (lat_arena, kr_arena), tables, (jnp.stack(fresh_lat), jnp.stack(fresh_kr)),
        pos, 1, active, False, per_layer=True)
    aux = jnp.concatenate([stats, (jnp.sum(active) * c.n_kda)[None].astype(jnp.int32)])
    return (_logits(params, x, c)[:, 0], tuple(blocks) + (state, tails),
            pos + active.astype(jnp.int32), aux)


# -- the family the batcher serves -------------------------------------------


class KimiLinearFamily:
    """What ``ContinuousBatcher``'s paged path asks of a block family
    (models/family.py), for the Kimi-Linear layers: two block leaves (the MLA
    layers' latents) and two slot leaves (the KDA layers' state and
    convolution tails)."""

    name = "kimi_linear"
    pad_id = -1                       # padding routes nowhere and moves no state
    slot_leaves = 2
    aux_names: Tuple[str, ...] = AUX_NAMES
    aux_prefix = "moe_"               # stats() keys: moe_tokens, ..., moe_state_updates
    decode_kernel = "mla_paged_decode_attention"
    # what the paged path offers and this family does not carry: the state at
    # a block boundary is not kept, so a prefix cannot be adopted
    unsupported = ("prefix sharing", "speculate", "cache-dtype=int8",
                   "kv-layout=slot", "windowed", "mesh", "draft model",
                   "migration", "snapshot")

    def __init__(self, config: KimiLinearConfig, dtype):
        self.config = config
        self.dtype = jnp.dtype(dtype)

    def _block_leaves(self, lead):
        c = self.config
        return (jnp.zeros(lead + (c.kv_rank,), self.dtype),
                jnp.zeros(lead + (c.kr_width,), self.dtype))

    def arena(self, n_blocks: int, block_size: int, quantized: bool = False,
              n_slots: int = 0):
        c = self.config
        return (self._block_leaves((c.n_mla, n_blocks + 1, block_size))
                + empty_slot_stage(c, n_slots + 1, self.dtype))

    def stage(self, length: int):
        c = self.config
        return (self._block_leaves((c.n_mla, 1, length))
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
        from nnstreamer_tpu.ops.pallas.mla_attention import mla_paged_decode_attention

        return mla_paged_decode_attention

    def note_aux(self, counts: Dict[str, int], registry) -> None:
        """One harvested pump's counters (``AUX_NAMES``, summed on the device
        over the pump's steps and layers): an ``nns.moe.routing`` and an
        ``nns.state.update`` instant, and their counters."""
        from nnstreamer_tpu import trace as _trace

        c = self.config
        updates = counts["state_updates"]
        per_update = 2 * c.kda_heads * c.kda_dim * c.kda_dim * 4  # read + written
        _trace.instant("nns.moe.routing", **{k: counts[k] for k in MOE_STATS})
        _trace.instant("nns.state.update", slot_layers=updates,
                       bytes=updates * per_update)
        if registry is None:
            return
        for k in MOE_STATS:
            registry.counter(f"nns_moe_{k}_total").inc(counts[k])
        registry.counter("nns_slot_state_updates_total").inc(updates)
