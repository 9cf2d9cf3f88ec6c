"""Block-native paged attention tests (kv/block_attn.py,
ops/pallas/paged_attention.py, docs/llm-serving.md).

The load-bearing invariants on top of test_kv_paged.py's slot-parity
matrix: block↔slot-oracle byte-identical streams on mixed-length
submits, the Pallas block-table kernel against its jnp online-softmax
reference in interpret mode (>1-block fills, int8 scales, scratch
predication), and the in-place single-block write leaving shared/CoW
blocks untouched. Kept lean under the tier-1 DOTS budget: one tiny
model, two shared batchers for every batcher-level test, greedy step()
drains (the spec/sampling compiles already ride test_kv_paged's
batchers), function-level kernel cells.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.models.serving import ContinuousBatcher

N_HEADS = 2


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(
        jax.random.PRNGKey(3), vocab=127, d_model=32, n_heads=N_HEADS,
        n_layers=2,
    )


def _mk(params, **kw):
    base = dict(n_slots=2, max_len=64, prompt_len=16,
                kv_layout="paged", block_size=16)
    base.update(kw)
    return ContinuousBatcher(params, N_HEADS, **base)


@pytest.fixture(scope="module")
def block_cb(params):
    return _mk(params)


@pytest.fixture(scope="module")
def slot_cb(params):
    return ContinuousBatcher(
        params, N_HEADS, n_slots=2, max_len=64, prompt_len=16
    )


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 127, (n,)).astype(np.int32)


def _drain(cb, rids):
    # per-token step() drains (a pump of one): the longer pumps and the
    # spec programs are already covered by test_kv_paged — skipping them
    # here keeps this file's compile bill inside the tier-1 budget
    while any(cb.result(r) is None for r in rids):
        cb.step()
    return [cb.result(r) for r in rids]


# -- batcher-level parity ----------------------------------------------------

def test_block_vs_slot_parity(block_cb, slot_cb):
    """Two greedy requests with multi-block prompts: the paged batcher
    and the slot layout (the oracle) emit byte-identical streams. The
    full parity matrix — sampling, int8, prefix sharing, eviction — is
    pinned by test_kv_paged.py; this cell is the mixed-length one (greedy
    keeps the compile bill to one pump program per batcher)."""
    # bucket-sized prompts (≤ prompt_len) keep the chunked-prefill
    # programs out of this file's compile bill; multi-block reads and
    # the cross-boundary width-1 write still happen — lane 1 decodes
    # from fill 13 into block 2
    subs = [(_prompt(5, 1), 6), (_prompt(13, 2), 5)]
    assert block_cb.stats()["attn_impl"] == "xla"
    assert "kv_attn" not in block_cb.stats()
    rb = [block_cb.submit(p, n) for p, n in subs]
    rs = [slot_cb.submit(p, n) for p, n in subs]
    assert _drain(block_cb, rb) == _drain(slot_cb, rs)


def test_in_place_write_leaves_shared_blocks_untouched(block_cb):
    """The width-1 in-place block update only touches the decoding
    request's privately-owned blocks: a registered (pinned, shared)
    prefix's arena blocks are bitwise unchanged by a sharer's decode."""
    sysp = _prompt(32, 7)  # 2 full blocks, pinned by registration
    pid = block_cb.register_prefix(sysp)
    blocks = list(block_cb._prefixes_paged[pid][1])
    assert len(blocks) == 2

    def read(b):
        ks, vs = block_cb._read_block(
            block_cb._cache, jnp.asarray(b, jnp.int32)
        )
        return np.asarray(ks).copy(), np.asarray(vs).copy()

    before = [read(b) for b in blocks]
    r = block_cb.submit(_prompt(3, 8), 4, prefix=pid)
    _drain(block_cb, [r])
    after = [read(b) for b in blocks]
    for (k0, v0), (k1, v1) in zip(before, after):
        assert (k0 == k1).all() and (v0 == v1).all()
    assert block_cb.unregister_prefix(pid)


# -- Pallas block-table kernel vs the jnp online-softmax reference ---------

def _rand_case(seed, B=3, H=4, KV=2, D=16, bs=8, nb=4, N=14):
    """Random arena + tables with >1-block fills, scratch-mapped table
    tails, and NONZERO scratch content (block 0) so masking — not
    initialization — is what keeps dead columns at exact zero weight."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((N + 1, bs, KV, D)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((N + 1, bs, KV, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, N + 1))[: B * nb]
        .reshape(B, nb).astype(np.int32)
    )
    # lane 0: ALL-scratch table at pos 0 (nothing live but the fresh
    # token — the @pl.when predication case, asserted exactly below);
    # lane 1: >1-block fill with a scratch-mapped tail
    tables = tables.at[0, :].set(0).at[1, 3:].set(0)
    pos = jnp.asarray([0, 2 * bs + 3, nb * bs - 1], jnp.int32)
    fk = jnp.asarray(rng.standard_normal((B, 1, KV, D)), jnp.float32)
    fv = jnp.asarray(rng.standard_normal((B, 1, KV, D)), jnp.float32)
    return q, ck, cv, tables, pos, fk, fv


def _exact(q, ck, cv, tables, pos, fk, fv):
    """The batcher's exact formulation: take → write fresh at pos →
    full masked softmax ≤ pos (bitwise the gathered view's math)."""
    b, nb = tables.shape
    bs = ck.shape[1]
    vk = jnp.take(ck, tables, axis=0).reshape(
        b, nb * bs, ck.shape[2], ck.shape[3]
    )
    vv = jnp.take(cv, tables, axis=0).reshape(
        b, nb * bs, cv.shape[2], cv.shape[3]
    )
    dus = jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0, 0))
    )
    vk, vv = dus(vk, fk, pos), dus(vv, fv, pos)
    mask = jnp.arange(nb * bs)[None, :] <= pos[:, None]
    return tfm.cache_attention(q, vk, vv, mask[:, None, :])


def test_kernel_interpret_parity_fp():
    from nnstreamer_tpu.kv.block_attn import paged_attention_ref
    from nnstreamer_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
    )

    q, ck, cv, tables, pos, fk, fv = _rand_case(0)
    ex = _exact(q, ck, cv, tables, pos, fk, fv)
    ref = paged_attention_ref(q, ck, cv, tables, pos, (fk, fv))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ex), atol=2e-5)
    out = paged_decode_attention(
        q, ck, cv, tables, pos, fk, fv, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # lane 0 (pos=0, all-scratch table): the one live column is the
    # fresh token, so its softmax weight is exactly 1 and arbitrary
    # scratch content contributes exact zeros — for kernel AND ref
    B, KV, D = fv.shape[0], fv.shape[2], fv.shape[3]
    want0 = np.broadcast_to(
        np.asarray(fv)[0, :, :, None, :], (1, KV, 2, D)
    ).reshape(1, 4, D)
    for got in (out, ref):
        np.testing.assert_allclose(np.asarray(got)[0], want0, atol=1e-5)


def test_kernel_interpret_parity_int8_scales():
    from nnstreamer_tpu.kv.block_attn import paged_attention_ref
    from nnstreamer_tpu.models.serving import dequantize_kv, quantize_kv
    from nnstreamer_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
    )

    q, ck, cv, tables, pos, fk, fv = _rand_case(1)
    k8, ks = quantize_kv(ck)
    v8, vs = quantize_kv(cv)
    ex = _exact(q, dequantize_kv(k8, ks), dequantize_kv(v8, vs),
                tables, pos, fk, fv)
    ref = paged_attention_ref(
        q, k8, v8, tables, pos, (fk, fv), k_scale=ks, v_scale=vs
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ex), atol=2e-5)
    out = paged_decode_attention(
        q, k8, v8, tables, pos, fk, fv, k_scale=ks, v_scale=vs,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _paged_spec():
    from nnstreamer_tpu.ops.pallas import paged_attention  # noqa: F401
    from nnstreamer_tpu.ops.pallas import registry

    return registry.get("paged_decode_attention")


@pytest.mark.parametrize(
    "case", [c.name for c in _paged_spec().tier1_cases()]
)
def test_kernel_registry_tier1_case(case):
    """Every tier-1 ``ShapeCase`` the kernel registers, against
    ``paged_attention_ref`` in interpret mode: the chunked grid (several
    blocks a grid step, a ragged last chunk), fills 0 / part of a block
    / a chunk's edge / the whole table, a finished lane whose stale
    table points at NaN blocks, 4 queries a KV head over 8 KV heads,
    int8 scales, a layer of a whole arena leaf."""
    spec = _paged_spec()
    params = next(c.params for c in spec.cases if c.name == case)
    got, want, atol = spec.run_case(dict(params))
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)


def test_block_attention_impl_dispatch():
    from nnstreamer_tpu.kv import block_attn as kvb

    q, ck, cv, tables, pos, fk, fv = _rand_case(3)
    jnp_out = kvb.block_attention(q, ck, cv, tables, pos, (fk, fv),
                                  impl="jnp")
    pl_out = kvb.block_attention(q, ck, cv, tables, pos, (fk, fv),
                                 impl="pallas")  # interpret off-TPU
    np.testing.assert_allclose(
        np.asarray(pl_out), np.asarray(jnp_out), atol=2e-5
    )
    with pytest.raises(ValueError, match="impl"):
        kvb.block_attention(q, ck, cv, tables, pos, (fk, fv), impl="cuda")


# -- the kernel behind the batcher, and the default rule ---------------------

def test_pump_stream_pallas_equals_xla(params):
    """One greedy batch through ``step_pump(8)``: the block-table kernel
    (interpret mode here; the arena leaf whole, the layer index scanned,
    fill 0 on lanes that finish mid-pump) emits the XLA view path's
    tokens. Three requests on three slots of four: one lane stays
    inactive with a zero table from the first launch on, one finishes
    inside a pump and idles out while the others run."""
    subs = [(_prompt(5, 11), 12), (_prompt(16, 12), 19), (_prompt(9, 13), 3)]
    streams = {}
    for impl in ("xla", "pallas"):
        cb = _mk(params, n_slots=4, attn_impl=impl)
        assert cb.stats()["attn_impl"] == impl
        rids = [cb.submit(p, n) for p, n in subs]
        while any(cb.result(r) is None for r in rids):
            cb.step_pump(8)
        streams[impl] = [cb.result(r) for r in rids]
    assert streams["pallas"] == streams["xla"]
    assert [len(t) for t in streams["xla"]] == [12, 19, 3]


@pytest.mark.parametrize(
    "backend,kw,want",
    [
        ("tpu", dict(), "pallas"),
        ("cpu", dict(), "xla"),
        ("tpu", dict(attn_impl="xla"), "xla"),
        ("cpu", dict(attn_impl="pallas"), "pallas"),
        ("tpu", dict(kv_layout="slot", block_size=16), "xla"),
        ("tpu", dict(cache_dtype="int8"), "pallas"),
    ],
    ids=["tpu-unset", "cpu-unset", "tpu-xla", "cpu-pallas", "tpu-slot",
         "tpu-int8"],
)
def test_default_attn_impl_rule(params, monkeypatch, backend, kw, want):
    """``attn_impl`` unset resolves by the rule of
    ``block_attention(impl="auto")``: the block-table kernel for the
    paged layout where ``jax.default_backend()`` is a TPU
    and the registry passes the arena dtype, the XLA formulation
    everywhere else; an explicit value keeps its meaning. The backend is
    steered HERE (construction traces and compiles nothing), not through
    an option of the program."""
    from nnstreamer_tpu.ops import dispatch

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    before = dispatch.tally.snapshot().get(("serving_attention", want), 0)
    cb = _mk(params, **kw)
    assert cb._attn_impl == want
    if kw.get("kv_layout") != "slot":
        assert cb.stats()["attn_impl"] == want
    after = dispatch.tally.snapshot()[("serving_attention", want)]
    assert after == before + 1


def test_default_rule_honours_registry_gate(params, monkeypatch):
    """On a TPU the unset default still asks the registry: with the
    process-wide fallback drill set it degrades to XLA, as an explicit
    ``pallas`` request does."""
    from nnstreamer_tpu.ops.pallas._compat import DISABLE_ENV

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _mk(params).stats()["attn_impl"] == "pallas"
    monkeypatch.setenv(DISABLE_ENV, "1")
    assert _mk(params).stats()["attn_impl"] == "xla"


def test_launch_span_counts_live_blocks(params):
    """``nns.pump.launch`` carries ``live_blocks``: the sum over active
    slots of ceil(fill / block_size) from the host's own positions."""
    from nnstreamer_tpu import trace as nns_trace

    cb = _mk(params, n_slots=4)
    seen = []
    real = nns_trace.span

    def spy(name, **attrs):
        if name == "nns.pump.launch":
            seen.append(attrs)
        return real(name, **attrs)

    rids = [cb.submit(_prompt(5, 21), 20), cb.submit(_prompt(16, 22), 20)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(nns_trace, "span", spy)
        while any(cb.result(r) is None for r in rids):
            cb.step_pump(8)
    # both admitted by the first pump (its prefill budget follows the
    # queue), 8 tokens a launch: fills (5, 16), (13, 24), (21, 32)
    assert [a["live_blocks"] for a in seen] == [1 + 1, 1 + 2, 2 + 2]
    assert [a["active"] for a in seen] == [2, 2, 2]


# -- configuration / lint ---------------------------------------------------

def test_kv_attn_is_gone(params):
    """The paged decode has one formulation and no option that names
    another: the constructor argument is a TypeError, and a launch string
    that still carries the property is told so like any unknown one."""
    from nnstreamer_tpu.analysis import lint

    with pytest.raises(TypeError, match="kv_attn"):
        _mk(params, kv_attn="block")
    r = lint("tensorsrc dimensions=4 types=int32 num-frames=1 ! "
             "tensor_llm_serversink id=92 kv-layout=paged kv-attn=gather")
    assert [d.code for d in r.diagnostics] == ["NNS-W101"]
    assert "unknown property 'kv-attn' for tensor_llm_serversink" in (
        r.diagnostics[0].message
    )
