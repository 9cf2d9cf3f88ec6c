"""Continuous-batching server tests (models/serving.py).

The load-bearing invariant: a request served in a busy, staggered batch
produces exactly the greedy tokens models/decode.generate() produces for
it alone — slots are isolated despite sharing one cache array and one
compiled step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.models import decode as dec
from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.models.serving import ContinuousBatcher

N_HEADS = 4


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(
        jax.random.PRNGKey(7), vocab=257, d_model=64, n_heads=N_HEADS,
        n_layers=2,
    )


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 257, (n,)).astype(np.int32)


def _alone(params, prompt, n_new):
    toks = dec.generate(
        params, jnp.asarray(prompt)[None, :], N_HEADS, n_new
    )
    return [int(t) for t in np.asarray(toks)[0]]


def _sliding_reference(params, prompt, n_new, W):
    """Greedy tokens under EXACT sliding-window attention: every token
    (prompt ingestion included) attends precisely the previous W
    positions, computed token-by-token on an UNBOUNDED cache with a
    banded mask — the ground truth the W-ring implementations must
    reproduce bit-exactly."""
    import functools

    from nnstreamer_tpu.models.serving import batched_decode_step

    def attn(q, ck, cv, pos):
        idx = jnp.arange(ck.shape[1])[None, :]
        mask = (idx <= pos[:, None]) & (idx > pos[:, None] - W)
        return tfm.cache_attention(q, ck, cv, mask[:, None, :])

    step = jax.jit(
        functools.partial(
            batched_decode_step, params, n_heads=N_HEADS, attn_fn=attn
        )
    )
    L, d = params["blocks"]["ln1"].shape
    kv = tfm.n_kv_heads_of(params["blocks"]["wqkv"], d, N_HEADS)
    hd = d // N_HEADS
    max_len = len(prompt) + n_new + 1
    cache = (
        jnp.zeros((L, 1, max_len, kv, hd)),
        jnp.zeros((L, 1, max_len, kv, hd)),
    )
    pos = jnp.asarray([0], jnp.int32)
    active = jnp.asarray([True])
    logits = None
    for t in prompt:
        logits, cache, pos = step(
            jnp.asarray([int(t)], jnp.int32), pos, active, cache
        )
    out = []
    for _ in range(n_new):
        tok = int(np.asarray(jnp.argmax(logits[0])))
        out.append(tok)
        logits, cache, pos = step(
            jnp.asarray([tok], jnp.int32), pos, active, cache
        )
    return out


def test_single_request_matches_generate(params):
    cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=64,
                           prompt_len=16)
    prompt = _prompt(10, 0)
    rid = cb.submit(prompt, 8)
    while cb.result(rid) is None:
        assert cb.step()  # must make progress
    assert cb.result(rid) == _alone(params, prompt, 8)


def test_staggered_requests_are_isolated(params):
    """B joins mid-flight while A decodes; both match their solo runs."""
    cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=64,
                           prompt_len=16)
    pa, pb = _prompt(12, 1), _prompt(5, 2)
    ra = cb.submit(pa, 10)
    for _ in range(3):
        cb.step()
    rb = cb.submit(pb, 6)
    while cb.result(ra) is None or cb.result(rb) is None:
        cb.step()
    assert cb.result(ra) == _alone(params, pa, 10)
    assert cb.result(rb) == _alone(params, pb, 6)


def test_slot_reuse_after_finish(params):
    """A finishes, C takes its slot while B still runs; C is unpolluted
    by A's stale cache."""
    cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=64,
                           prompt_len=16)
    pa, pb, pc = _prompt(8, 3), _prompt(8, 4), _prompt(14, 5)
    ra = cb.submit(pa, 3)
    rb = cb.submit(pb, 12)
    assert cb.submit(_prompt(4, 9), 2) is None  # batch full
    while cb.result(ra) is None:
        cb.step()
    rc = cb.submit(pc, 7)
    assert rc is not None
    while cb.result(rb) is None or cb.result(rc) is None:
        cb.step()
    assert cb.result(ra) == _alone(params, pa, 3)
    assert cb.result(rb) == _alone(params, pb, 12)
    assert cb.result(rc) == _alone(params, pc, 7)


def test_validation(params):
    cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=32,
                           prompt_len=16)
    with pytest.raises(ValueError, match="empty prompt"):
        cb.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ValueError, match="overflow"):
        cb.submit(np.ones((16,), np.int32), 200)
    with pytest.raises(ValueError, match="max_new_tokens"):
        cb.submit(np.ones((4,), np.int32), 0)
    with pytest.raises(ValueError, match="prompt_len"):
        ContinuousBatcher(params, N_HEADS, max_len=8, prompt_len=16)


def test_budget_one_finishes_at_submit(params):
    cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=32,
                           prompt_len=16)
    prompt = _prompt(6, 6)
    rid = cb.submit(prompt, 1)
    assert cb.result(rid) == _alone(params, prompt, 1)
    assert cb.n_free == 1
    assert cb.step() == {}


def test_done_pool_bounded(params):
    cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=32,
                           prompt_len=8, keep_results=3)
    rids = []
    for seed in range(5):
        rids.append(cb.submit(_prompt(4, seed), 1))
    assert len(cb._done_pool) == 3
    assert cb.result(rids[0]) is None  # evicted (oldest)
    assert cb.result(rids[-1]) is not None


class TestInt8Cache:
    """cache_dtype="int8": 4x smaller KV cache (serving.quantize_kv)."""

    def test_step_logits_close_to_float_cache(self, params):
        from nnstreamer_tpu.models.serving import (
            batched_decode_step, insert_slot, quantize_kv, dequantize_kv,
        )

        prompt = _prompt(10, 11)
        logits_p, (ks, vs), _ = dec.prefill(
            params, jnp.asarray(prompt)[None, :], N_HEADS, 16
        )
        L, _, _, H, Dh = ks.shape
        shape = (L, 2, 32, H, Dh)
        fcache = (jnp.zeros(shape), jnp.zeros(shape))
        qcache = (
            (jnp.zeros(shape, jnp.int8), jnp.ones(shape[:-1])),
            (jnp.zeros(shape, jnp.int8), jnp.ones(shape[:-1])),
        )
        fcache = insert_slot(fcache, ks, vs, 0)
        qcache = insert_slot(qcache, ks, vs, 0)
        tok = jnp.asarray([3, 0], jnp.int32)
        pos = jnp.asarray([10, 0], jnp.int32)
        active = jnp.asarray([True, False])
        lf, _, _ = batched_decode_step(
            params, tok, pos, active, fcache, N_HEADS
        )
        lq, _, _ = batched_decode_step(
            params, tok, pos, active, qcache, N_HEADS
        )
        a, b = np.asarray(lf[0]), np.asarray(lq[0])
        cos = (a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cos > 0.995, f"cosine {cos}"

    def test_quantize_roundtrip_error_bounded(self, params):
        from nnstreamer_tpu.models.serving import quantize_kv, dequantize_kv

        t = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 3, 16))
        q8, sc = quantize_kv(t)
        assert q8.dtype == jnp.int8 and sc.shape == (2, 4, 3)
        err = np.abs(np.asarray(dequantize_kv(q8, sc) - t))
        # symmetric int8: error ≤ half a quantization step per head
        assert (err <= np.asarray(sc)[..., None] * 0.5 + 1e-7).all()

    def test_end_to_end_int8_cache(self, params):
        cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=64,
                               prompt_len=16, cache_dtype="int8")
        pa, pb = _prompt(12, 12), _prompt(6, 13)
        ra = cb.submit(pa, 8)
        rb = cb.submit(pb, 8)
        while cb.result(ra) is None or cb.result(rb) is None:
            assert cb.step() or cb.result(ra) is not None
        # int8 rounding may drift argmax on random-weight logits; the
        # float-cache run must at least agree on the prefill-derived
        # first token (prefill is float in both)
        assert cb.result(ra)[0] == _alone(params, pa, 1)[0]
        assert len(cb.result(ra)) == 8 and len(cb.result(rb)) == 8

    def test_pallas_composes_with_int8(self, params):
        """The decode kernel reads the int8 cache directly (scale
        operands, VMEM dequant) — tokens match the inline-XLA int8 path
        exactly (both attend the same dequantized values)."""
        prompt = _prompt(9, 14)
        outs = {}
        for impl in ("xla", "pallas"):
            cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=48,
                                   prompt_len=16, cache_dtype="int8",
                                   attn_impl=impl)
            rid = cb.submit(prompt, 8)
            while cb.result(rid) is None:
                cb.step()
            outs[impl] = cb.result(rid)
        assert outs["xla"] == outs["pallas"]


def test_submit_releases_slot_when_prefill_fails(params):
    cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=32,
                           prompt_len=8)

    def boom(_):
        raise RuntimeError("prefill exploded")

    cb._prefill = boom
    with pytest.raises(RuntimeError, match="prefill exploded"):
        cb.submit(_prompt(4, 20), 2)
    assert cb.n_free == 1  # slot released, server still serviceable


def test_mesh_sharded_slots_match_unsharded(params):
    """Slots sharded over an 8-device mesh (SPMD decode) produce the same
    greedy tokens as the single-device batcher."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8, axes=("dp",))
    prompts = [_prompt(4 + i, 30 + i) for i in range(3)]
    outs = {}
    for label, kw in (("plain", {}), ("mesh", dict(mesh=mesh))):
        cb = ContinuousBatcher(params, N_HEADS, n_slots=8, max_len=32,
                               prompt_len=16, **kw)
        rids = [cb.submit(p, 5) for p in prompts]
        while any(cb.result(r) is None for r in rids):
            cb.step()
        outs[label] = [cb.result(r) for r in rids]
    assert outs["plain"] == outs["mesh"]


def test_mesh_requires_divisible_slots(params):
    from nnstreamer_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError, match="divide"):
        ContinuousBatcher(params, N_HEADS, n_slots=3,
                          mesh=make_mesh(8, axes=("dp",)))


def test_mesh_plus_pallas_matches_unsharded(params):
    """attn_impl='pallas' + mesh=: the step program is shard_mapped over
    the slot axis, each device running the kernel on its local slots —
    tokens match the unsharded pallas batcher."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8, axes=("dp",))
    prompts = [_prompt(5 + i, 35 + i) for i in range(2)]
    outs = {}
    for label, kw in (
        ("plain", {}),
        ("mesh", dict(mesh=mesh)),
    ):
        cb = ContinuousBatcher(params, N_HEADS, n_slots=8, max_len=32,
                               prompt_len=16, attn_impl="pallas", **kw)
        rids = [cb.submit(p, 5) for p in prompts]
        while any(cb.result(r) is None for r in rids):
            cb.step()
        outs[label] = [cb.result(r) for r in rids]
    assert outs["plain"] == outs["mesh"]


class TestSampling:
    def test_sampled_deterministic_per_seed(self, params):
        outs = []
        for _ in range(2):
            cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=48,
                                   prompt_len=16)
            rid = cb.submit(_prompt(8, 40), 10, temperature=0.9, seed=123)
            while cb.result(rid) is None:
                cb.step()
            outs.append(cb.result(rid))
        assert outs[0] == outs[1]

    def test_different_seeds_diverge(self, params):
        outs = []
        for seed in (1, 2):
            cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=48,
                                   prompt_len=16)
            rid = cb.submit(_prompt(8, 41), 12, temperature=1.5, seed=seed)
            while cb.result(rid) is None:
                cb.step()
            outs.append(cb.result(rid))
        assert outs[0] != outs[1]  # astronomically unlikely to collide

    def test_mixed_batch_greedy_stream_unaffected(self, params):
        """A sampling request sharing the batch must not perturb a greedy
        request's tokens (host-side picks are per-slot)."""
        pg = _prompt(9, 42)
        cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=48,
                               prompt_len=16)
        rg = cb.submit(pg, 8)  # greedy
        rs = cb.submit(_prompt(5, 43), 8, temperature=1.0, seed=7)
        while cb.result(rg) is None or cb.result(rs) is None:
            cb.step()
        assert cb.result(rg) == _alone(params, pg, 8)

    def test_top_k_one_is_greedy(self, params):
        p = _prompt(7, 44)
        cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=48,
                               prompt_len=16)
        rid = cb.submit(p, 6, temperature=0.8, top_k=1, seed=5)
        while cb.result(rid) is None:
            cb.step()
        assert cb.result(rid) == _alone(params, p, 6)


def test_stop_token_ends_request_early(params):
    """The request finishes as soon as its stop token is emitted; the
    stop token stays in the output (EOS-id semantics)."""
    prompt = _prompt(8, 60)
    full = _alone(params, prompt, 12)
    stop = full[4]  # force an early stop at a token we know appears
    cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=64,
                           prompt_len=16)
    rid = cb.submit(prompt, 12, stop_token=stop)
    while cb.result(rid) is None:
        cb.step()
    got = cb.result(rid)
    assert got == full[:5]
    assert got[-1] == stop
    assert cb.n_free == 1


class TestSlidingWindow:
    def test_window_large_enough_matches_plain(self, params):
        """When no wrap happens, windowed == plain (same programs,
        identical ring/prefix masks)."""
        prompt = _prompt(10, 70)
        outs = {}
        for label, kw in (("plain", {}), ("ring", dict(windowed=True))):
            cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=64,
                                   prompt_len=16, **kw)
            rid = cb.submit(prompt, 8)
            while cb.result(rid) is None:
                cb.step()
            outs[label] = cb.result(rid)
        assert outs["plain"] == outs["ring"]

    def test_generation_beyond_cache_length(self, params):
        """A generation much longer than the cache runs in fixed memory
        and every token is finite/valid (the whole point of the ring)."""
        cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=24,
                               prompt_len=16, windowed=True)
        prompt = _prompt(8, 71)
        rid = cb.submit(prompt, 60)  # 8 + 60 >> 24
        while cb.result(rid) is None:
            assert cb.step()
        toks = cb.result(rid)
        assert len(toks) == 60
        assert all(0 <= t < 257 for t in toks)

    def test_ring_matches_sliding_mask_on_unbounded_cache(self, params):
        """The real post-wrap check: the ring stream must equal a
        reference stream computed on an UNBOUNDED cache whose attention
        is masked to exactly the last W positions (_sliding_reference) —
        byte-identical through many wrapped steps."""
        W = 16
        n_new = 40  # wraps the W-ring several times
        prompt = _prompt(10, 72)
        cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=W,
                               prompt_len=16, windowed=True)
        rid = cb.submit(prompt, n_new)
        while cb.result(rid) is None:
            cb.step()
        assert cb.result(rid) == _sliding_reference(params, prompt, n_new, W)

    def test_ring_with_pallas_kernel(self, params):
        """windowed composes with the Pallas kernel (its <=pos mask
        saturates identically past the wrap)."""
        prompt = _prompt(8, 73)
        outs = {}
        for impl in ("xla", "pallas"):
            cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=16,
                                   prompt_len=16, windowed=True,
                                   attn_impl=impl)
            rid = cb.submit(prompt, 20)
            while cb.result(rid) is None:
                cb.step()
            outs[impl] = cb.result(rid)
        assert outs["xla"] == outs["pallas"]


class TestChunkedPrefill:
    @pytest.mark.parametrize("plen", [17, 32, 41])  # partial/exact/2.5 buckets
    def test_long_prompt_matches_generate(self, params, plen):
        """Prompts longer than the bucket prefill in chunks and still
        yield exactly the solo-generation tokens."""
        prompt = _prompt(plen, 80 + plen)
        cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=64,
                               prompt_len=16)
        rid = cb.submit(prompt, 6)
        while cb.result(rid) is None:
            cb.step()
        assert cb.result(rid) == _alone(params, prompt, 6)

    def test_prompt_beyond_cache_rejected(self, params):
        cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=32,
                               prompt_len=16)
        with pytest.raises(ValueError, match="> max_len"):
            cb.submit(_prompt(40, 90), 2)

    @pytest.mark.parametrize("plen", [20, 32, 50])  # ≤W, =W, wraps W
    def test_windowed_long_prompt_matches_sliding_reference(
        self, params, plen
    ):
        """Windowed chunked prefill (decode.windowed_chunk ring prefill)
        matches a reference computed on an unbounded cache with an exact
        sliding-window attention mask — including prompts LONGER than
        the window (the ring keeps the last W prompt tokens)."""
        W = 32
        n_new = 6
        prompt = _prompt(plen, 91 + plen)
        cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=W,
                               prompt_len=16, windowed=True)
        rid = cb.submit(prompt, n_new)
        while cb.result(rid) is None:
            cb.step()
        assert cb.result(rid) == _sliding_reference(
            params, prompt, n_new, W
        )

    def test_windowed_chunk_alignment_required(self, params):
        """Unaligned windowed configs serve bucket-sized prompts fine;
        a LONG prompt is rejected before any slot is claimed."""
        cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=24,
                               prompt_len=16, windowed=True)
        with pytest.raises(ValueError, match="multiple of prompt_len"):
            cb.submit(_prompt(20, 95), 2)
        assert cb.n_free == 1  # nothing claimed by the rejected submit


class TestPrefixCaching:
    def test_prefix_matches_concat_prompt(self, params):
        """submit(prefix=id) yields exactly the tokens of solo generation
        on prefix+prompt — for short, bucket-crossing, and multi-bucket
        prefix lengths."""
        cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=96,
                               prompt_len=16)
        for plen, tlen in ((5, 7), (16, 10), (23, 20), (37, 4)):
            pfx_toks = _prompt(plen, 100 + plen)
            prompt = _prompt(tlen, 200 + tlen)
            pid = cb.register_prefix(pfx_toks)
            rid = cb.submit(prompt, 6, prefix=pid)
            while cb.result(rid) is None:
                cb.step()
            full = np.concatenate([pfx_toks, prompt])
            assert cb.result(rid) == _alone(params, full, 6), (
                f"prefix {plen} + prompt {tlen} diverged"
            )

    def test_prefix_shared_across_requests(self, params):
        """Two concurrent requests share one registered prefix."""
        cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=64,
                               prompt_len=16)
        pfx_toks = _prompt(12, 300)
        pid = cb.register_prefix(pfx_toks)
        pa, pb = _prompt(6, 301), _prompt(9, 302)
        ra = cb.submit(pa, 5, prefix=pid)
        rb = cb.submit(pb, 5, prefix=pid)
        while cb.result(ra) is None or cb.result(rb) is None:
            cb.step()
        assert cb.result(ra) == _alone(params, np.concatenate([pfx_toks, pa]), 5)
        assert cb.result(rb) == _alone(params, np.concatenate([pfx_toks, pb]), 5)

    def test_prefix_validation(self, params):
        cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=32,
                               prompt_len=16)
        with pytest.raises(ValueError, match="unknown prefix"):
            cb.submit(_prompt(4, 310), 2, prefix=99)
        pid = cb.register_prefix(_prompt(20, 311))
        with pytest.raises(ValueError, match="> max_len"):
            cb.submit(_prompt(13, 312), 2, prefix=pid)
        wcb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=32,
                                prompt_len=16, windowed=True)
        # windowed prefixes must be bucket-aligned (continuation chunks
        # start at base=plen and must not wrap the ring mid-write)
        with pytest.raises(ValueError, match="multiple of prompt_len"):
            wcb.register_prefix(_prompt(4, 313))
        assert wcb.register_prefix(_prompt(16, 314)) is not None

    def test_windowed_prefix_matches_concat_prompt(self, params):
        """windowed × prefix caching (r4): a prefix always starts at
        absolute position 0, so its ring placement is request-invariant
        — submit(prefix=id) must equal submitting the concatenated
        prompt to a fresh windowed batcher, including through ring
        wraps during generation."""
        W = 32
        pfx_toks = _prompt(16, 330)
        tail = _prompt(6, 331)
        n_new = 30  # 16 + 6 + 30 wraps the W=32 ring
        wcb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=W,
                                prompt_len=16, windowed=True)
        pid = wcb.register_prefix(pfx_toks)
        rid = wcb.submit(tail, n_new, prefix=pid)
        while wcb.result(rid) is None:
            wcb.step()
        ref = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=W,
                                prompt_len=16, windowed=True)
        rr = ref.submit(np.concatenate([pfx_toks, tail]), n_new)
        while ref.result(rr) is None:
            ref.step()
        assert wcb.result(rid) == ref.result(rr)
        # and both equal the exact sliding-window ground truth
        assert wcb.result(rid) == _sliding_reference(
            params, np.concatenate([pfx_toks, tail]), n_new, W
        )

    def test_windowed_prefix_longer_than_window(self, params):
        """A windowed prefix may exceed the window: the stored ring
        holds its last W tokens — exactly what sliding-window semantics
        prescribe for any prefix that long."""
        W = 32
        pfx_toks = _prompt(48, 332)  # 1.5× the window, 3 buckets
        tail = _prompt(5, 333)
        wcb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=W,
                                prompt_len=16, windowed=True)
        pid = wcb.register_prefix(pfx_toks)
        rid = wcb.submit(tail, 8, prefix=pid)
        while wcb.result(rid) is None:
            wcb.step()
        assert wcb.result(rid) == _sliding_reference(
            params, np.concatenate([pfx_toks, tail]), 8, W
        )

    def test_windowed_prefix_with_spec_step(self, params):
        """prefix × windowed × speculation all compose: the spec pump
        serves a prefixed windowed request and matches the plain pump."""
        W = 32
        pfx_toks = np.tile(np.asarray([3, 4, 5, 6], np.int32), 4)  # 16
        tail = np.asarray([3, 4, 5], np.int32)

        def run(spec):
            wcb = ContinuousBatcher(params, N_HEADS, n_slots=1,
                                    max_len=W, prompt_len=16,
                                    windowed=True)
            pid = wcb.register_prefix(pfx_toks)
            rid = wcb.submit(tail, 20, prefix=pid)
            while wcb.result(rid) is None:
                wcb.spec_step(ngram=1) if spec else wcb.step()
            return wcb.result(rid)

        assert run(True) == run(False)


def test_unregister_prefix_releases(params):
    cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=32,
                           prompt_len=16)
    pid = cb.register_prefix(_prompt(8, 320))
    assert cb.unregister_prefix(pid)
    assert not cb.unregister_prefix(pid)
    with pytest.raises(ValueError, match="unknown prefix"):
        cb.submit(_prompt(4, 321), 2, prefix=pid)


def test_stats_surface(params):
    cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=32,
                           prompt_len=16)
    assert cb.stats()["steps"] == 0
    rid = cb.submit(_prompt(6, 400), 5)
    while cb.result(rid) is None:
        cb.step()
    s = cb.stats()
    assert s["steps"] == 4  # first token came from prefill
    assert s["tokens_emitted"] == 4
    assert s["tokens_per_step"] == 1.0
    row = cb.requests()[rid]
    assert row["state"] == "done" and row["tokens"] == 5
    assert row["tpot_ms"] > 0
    assert s["slots_free"] == 2
    assert s["results_pending_pickup"] == 1


def test_mesh_with_int8_cache(params):
    """Slot sharding composes with the quantized cache (scale leaves
    shard on the same slot axis)."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8, axes=("dp",))
    prompt = _prompt(6, 500)
    cb = ContinuousBatcher(params, N_HEADS, n_slots=8, max_len=32,
                           prompt_len=16, mesh=mesh, cache_dtype="int8")
    rid = cb.submit(prompt, 5)
    while cb.result(rid) is None:
        cb.step()
    plain = ContinuousBatcher(params, N_HEADS, n_slots=8, max_len=32,
                              prompt_len=16, cache_dtype="int8")
    rid2 = plain.submit(prompt, 5)
    while plain.result(rid2) is None:
        plain.step()
    assert cb.result(rid) == plain.result(rid2)


def test_top_p_tiny_is_greedy_and_deterministic(params):
    """top_p small enough keeps only the argmax token → equals greedy;
    and a mid-range top_p is deterministic per seed."""
    p = _prompt(7, 600)
    cb = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=48,
                           prompt_len=16)
    rid = cb.submit(p, 6, temperature=0.7, top_p=1e-9, seed=3)
    while cb.result(rid) is None:
        cb.step()
    assert cb.result(rid) == _alone(params, p, 6)
    outs = []
    for _ in range(2):
        cb2 = ContinuousBatcher(params, N_HEADS, n_slots=1, max_len=48,
                                prompt_len=16)
        r = cb2.submit(p, 8, temperature=1.2, top_p=0.8, seed=9)
        while cb2.result(r) is None:
            cb2.step()
        outs.append(cb2.result(r))
    assert outs[0] == outs[1]


def test_device_sampling_at_real_vocab(params):
    """Device-side sampling at a realistic (32k) vocab: the step program
    samples on device and transfers ONE token id per slot — the [B, V]
    logits (128 KB/slot/step at 32k) never cross to host. Validity +
    determinism checked; mixed greedy/sampled batch served together."""
    big = tfm.init_params(
        jax.random.PRNGKey(11), vocab=32768, d_model=64, n_heads=N_HEADS,
        n_layers=1,
    )
    outs = []
    for _ in range(2):
        cb = ContinuousBatcher(big, N_HEADS, n_slots=2, max_len=32,
                               prompt_len=8)
        rs = cb.submit(
            np.asarray([5, 17, 900], np.int32), 6,
            temperature=1.0, top_k=50, top_p=0.9, seed=42,
        )
        rg = cb.submit(np.asarray([3, 4], np.int32), 6)  # greedy neighbor
        while cb.result(rs) is None or cb.result(rg) is None:
            cb.step()
        assert all(0 <= t < 32768 for t in cb.result(rs))
        outs.append((cb.result(rs), cb.result(rg)))
    assert outs[0] == outs[1]  # deterministic per (seed, position)


def test_concurrent_submit_spec_and_streaming_soak(params):
    """Concurrency soak: one thread pumps spec rounds, one pumps plain
    steps, two submitter threads race admissions, and a reader polls
    partials — no deadlock, every request completes, and every greedy
    result matches its solo generation (slot isolation under real
    thread interleaving, the Python-side analogue of the TSAN suites)."""
    import threading

    cb = ContinuousBatcher(params, N_HEADS, n_slots=4, max_len=96,
                           prompt_len=16)
    prompts = [_prompt(4 + i % 9, 400 + i) for i in range(12)]
    rids: dict = {}
    rid_lock = threading.Lock()
    stop = threading.Event()

    def pump(spec):
        while not stop.is_set():
            (cb.spec_step(k=3, ngram=1) if spec else cb.step())

    def submitter(idx0):
        for i in range(idx0, len(prompts), 2):
            while True:
                rid = cb.submit(prompts[i], 6)
                if rid is not None:
                    with rid_lock:
                        rids[i] = rid
                    break
                cb.step()  # batch full: pumping IS the backpressure

    def reader():
        while not stop.is_set():
            with rid_lock:
                known = list(rids.values())
            cb.partials(known)

    threads = [
        threading.Thread(target=pump, args=(True,), daemon=True),
        threading.Thread(target=pump, args=(False,), daemon=True),
        threading.Thread(target=reader, daemon=True),
    ]
    subs = [
        threading.Thread(target=submitter, args=(k,), daemon=True)
        for k in (0, 1)
    ]
    for t in threads + subs:
        t.start()
    for t in subs:
        t.join(timeout=300)
        assert not t.is_alive(), "submitter deadlocked"
    deadline = __import__("time").monotonic() + 300
    while True:
        with rid_lock:
            done = (
                len(rids) == len(prompts)
                and all(cb.result(r) is not None for r in rids.values())
            )
        if done:
            break
        assert __import__("time").monotonic() < deadline, "requests stuck"
    stop.set()
    for t in threads:
        t.join(timeout=10)
    for i, rid in rids.items():
        assert cb.result(rid) == _alone(params, prompts[i], 6), (
            f"request {i} diverged under concurrency"
        )


def test_mesh_with_draft_speculation_matches_unsharded(params):
    """mesh-sharded slots × draft speculation: the spec-round program
    GSPMD-partitions over the slot axis and the (replicated) draft's
    batched proposals feed it — same tokens as the unsharded batcher."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    draft = tfm.init_params(
        jax.random.PRNGKey(77), vocab=257, d_model=32, n_heads=2,
        n_layers=1,
    )
    mesh = make_mesh(8, axes=("dp",))
    prompts = [_prompt(4 + i, 60 + i) for i in range(3)]
    outs = {}
    for label, kw in (("plain", {}), ("mesh", dict(mesh=mesh))):
        cb = ContinuousBatcher(params, N_HEADS, n_slots=8, max_len=48,
                               prompt_len=16, draft_params=draft,
                               draft_n_heads=2, **kw)
        rids = [cb.submit(p, 6) for p in prompts]
        while any(cb.result(r) is None for r in rids):
            cb.spec_step(k=3)
        outs[label] = [cb.result(r) for r in rids]
        assert cb.stats()["spec_rounds"] > 0
    assert outs["plain"] == outs["mesh"]


def test_latency_telemetry_surface(params):
    """requests() reports every request's queue, TTFT and per-token time
    from the SLO ledger — the one latency bookkeeping the batcher has."""
    cb = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=48,
                           prompt_len=16)
    rids = [cb.submit(_prompt(5 + i, 900 + i), 4) for i in range(2)]
    while any(cb.result(r) is None for r in rids):
        cb.step_pump(4)
    rows = cb.requests()
    for rid in rids:
        row = rows[rid]
        assert row["state"] == "done" and row["tokens"] == 4
        assert row["ttft_ms"] > 0.0 and row["tpot_ms"] > 0.0
        assert row["ttft_ms"] >= row["queue_ms"] >= 0.0
