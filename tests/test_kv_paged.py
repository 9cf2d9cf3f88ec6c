"""nns-kv paged KV-cache tests (nnstreamer_tpu/kv/, docs/llm-serving.md).

The load-bearing invariant: paged decode is a *layout*, not a different
decoder — gather → identical batched step → scatter must produce
byte-identical token streams to the contiguous slot layout on the same
request trace (greedy and sampling, fp and int8). On top of that: the
BlockPool's refcount/prefix-index/copy-on-write discipline, chunked
prefill's TTFT bound, preemption→re-prefill, block-table
snapshot/restore, and the NNS-W115 lint.

Budget note: slots are isolated by construction (a request's stream
never depends on batch composition — the continuous-batching invariant
test_serving pins), so ONE module-scoped slot reference and ONE paged
batcher serve most tests here; per-test batchers exist only where the
configuration itself differs (int8, tight pool, restore target). Keeps
the compile count — the file's real cost — low. The widest
parity-matrix cells (long-prompt chunked, eviction, sharing
degradation, snapshot/restore) are marked `slow` under the tier-1
DOTS budget; the fp greedy+sampling and int8 bitwise cells stay
tier-1, and tests/test_kv_block_attn.py pins the block-native
formulation these now run by default against the gather oracle.
"""

import jax
import numpy as np
import pytest

from nnstreamer_tpu.kv.blocks import BlockPool, NoBlocksError
from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.models.serving import ContinuousBatcher

N_HEADS = 4


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(
        jax.random.PRNGKey(7), vocab=257, d_model=64, n_heads=N_HEADS,
        n_layers=2,
    )


@pytest.fixture(scope="module")
def obs_reg():
    from nnstreamer_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.enable()
    yield reg
    obs_metrics.disable()


@pytest.fixture(scope="module")
def slot_ref(params):
    """Shared slot-layout reference, drained per-token (one compiled
    step program for the whole module)."""
    return ContinuousBatcher(params, N_HEADS, n_slots=4, max_len=96,
                             prompt_len=16)


@pytest.fixture(scope="module")
def paged_cb(params, obs_reg):
    """Shared paged batcher (obs registry active, so the SLO metrics
    test can read what the other tests emitted)."""
    return _mk(params)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 257, (n,)).astype(np.int32)


def _rep_prompt(n, seed, period=6):
    base = np.random.default_rng(seed).integers(1, 257, (period,))
    return np.tile(base, -(-n // period))[:n].astype(np.int32)


def _mk(params, paged=True, **kw):
    base = dict(n_slots=4, max_len=96, prompt_len=16)
    if paged:
        base.update(kv_layout="paged", block_size=16)
    base.update(kw)
    return ContinuousBatcher(params, N_HEADS, **base)


def _drain(cb, rids, pump=0):
    while any(cb.result(r) is None for r in rids):
        cb.step_pump(pump) if pump else cb.step()
    return [cb.result(r) for r in rids]


def _ref_streams(slot_ref, subs):
    rids = [slot_ref.submit(p, n, **kw) for p, n, kw in subs]
    return _drain(slot_ref, rids)


# -- BlockPool (host accounting, no device work) ---------------------------

def test_pool_alloc_free_refcount_and_exhaustion():
    pool = BlockPool(4, 16)
    a = pool.alloc(3)
    assert pool.in_use() == 3 and len(set(a)) == 3 and 0 not in a
    pool.adopt(a[0])  # second reference
    pool.free([a[0]])
    assert pool.in_use() == 3  # still referenced once
    pool.free(a)
    assert pool.in_use() == 0
    pool.alloc(4)
    with pytest.raises(NoBlocksError):
        pool.alloc(1)
    with pytest.raises(ValueError):
        pool.free([a[1], a[1], a[1]])  # more frees than references


def test_pool_prefix_index_full_and_partial_match():
    pool = BlockPool(8, 4)
    toks = np.arange(10, dtype=np.int32)  # 2 full blocks + partial(2)
    blocks = pool.alloc(3)
    pool.register(toks, blocks)
    m = pool.match(toks)
    assert m.full == blocks[:2] and m.partial_block == blocks[2]
    assert m.n_partial == 2 and m.n_tokens == 10
    # longer query: partial entry is a prefix of the remainder
    m2 = pool.match(np.arange(16, dtype=np.int32))
    assert m2.n_tokens == 10 and m2.partial_block == blocks[2]
    # diverging content stops the walk with verification, not hashes
    bad = toks.copy()
    bad[5] = 99
    m3 = pool.match(bad)
    assert m3.full == blocks[:1] and m3.n_tokens == 4


def test_pool_cached_tier_reclaim_unindexes():
    pool = BlockPool(2, 4)
    toks = np.arange(8, dtype=np.int32)
    blocks = pool.alloc(2)
    pool.register(toks, blocks)
    pool.free(blocks)  # refcount 0, but indexed → cached, still matchable
    assert pool.match(toks).n_tokens == 8
    got = pool.alloc(2)  # reclaims LRU-cached blocks
    assert sorted(got) == sorted(blocks)
    assert pool.match(toks).n_tokens == 0  # reclaimed = unindexed
    assert pool.snapshot()["index"] == []


def test_pool_cow_counts_and_snapshot_roundtrip():
    pool = BlockPool(6, 4)
    toks = np.arange(6, dtype=np.int32)
    blocks = pool.alloc(2)
    pool.register(toks, blocks)
    b = pool.cow()
    assert b not in blocks and pool.cow_copies == 1
    snap = pool.snapshot()
    pool2 = BlockPool(6, 4)
    pool2.restore(snap)
    assert pool2.match(toks).n_tokens == 6
    assert pool2.in_use() == pool.in_use()
    assert pool2.cow_copies == 1


# -- bitwise parity with the contiguous slot layout ------------------------

def test_paged_parity_greedy_and_sampling(slot_ref, paged_cb):
    """One batch mixing greedy and sampled requests: paged pumps equal
    slot per-token steps byte for byte."""
    subs = [
        (_prompt(5, 1), 8, {}),
        (_prompt(9, 2), 7, {}),
        (_prompt(6, 3), 8, dict(temperature=0.8, top_k=40, seed=5)),
    ]
    rb = [paged_cb.submit(p, n, **kw) for p, n, kw in subs]
    assert _ref_streams(slot_ref, subs) == _drain(paged_cb, rb, pump=4)


@pytest.mark.slow
def test_paged_long_prompt_chunked_prefill_parity(slot_ref, paged_cb):
    """A prompt spanning several prefill buckets admits chunk by chunk
    and still yields the slot layout's exact stream."""
    p = _rep_prompt(60, 12)
    rb = paged_cb.submit(p, 8)
    assert _ref_streams(slot_ref, [(p, 8, {})]) == _drain(
        paged_cb, [rb], pump=4
    )


def test_paged_spec_pump_parity(slot_ref, paged_cb):
    """Device n-gram speculation over the gathered view: streams equal
    the slot layout's plain steps, and proposals actually land."""
    prompts = [_rep_prompt(12, 50 + s, period=4) for s in range(3)]
    acc0 = paged_cb.stats()["spec_accepted_tokens"]
    rb = [paged_cb.submit(p, 10) for p in prompts]
    while any(paged_cb.result(r) is None for r in rb):
        paged_cb.spec_pump(rounds=2, k=3, ngram=1)
    assert _ref_streams(slot_ref, [(p, 10, {}) for p in prompts]) == [
        paged_cb.result(r) for r in rb
    ]
    assert paged_cb.stats()["spec_accepted_tokens"] > acc0


def test_paged_int8_parity(params):
    a = _mk(params, paged=False, cache_dtype="int8", n_slots=2)
    b = _mk(params, cache_dtype="int8", n_slots=2)
    p = _prompt(6, 41)
    ra, rb = a.submit(p, 7), b.submit(p, 7)
    assert _drain(a, [ra], pump=4) == _drain(b, [rb], pump=4)


# -- prefix sharing / copy-on-write ----------------------------------------

def test_prefix_share_refcount_and_stream_parity(slot_ref, paged_cb):
    """Identical leading blocks are adopted (prefix hits), a mid-block
    extension copies-on-write, and neither sharer's stream changes
    (the unshared reference is the slot layout — parity already pinned
    above, so equality here isolates the SHARING as a no-op on
    streams)."""
    st0 = paged_cb.stats()
    p1 = _rep_prompt(24, 5, period=24)            # 1 full + 1 partial
    p2 = np.concatenate([p1, _rep_prompt(8, 2)])  # extends p1 mid-block
    r1 = paged_cb.submit(p1, 4)
    _drain(paged_cb, [r1], pump=4)
    r2 = paged_cb.submit(p2, 4)
    _drain(paged_cb, [r2], pump=4)
    st = paged_cb.stats()
    assert st["kv_prefix_hits"] >= st0["kv_prefix_hits"] + 2
    assert st["kv_cow_copies"] >= st0["kv_cow_copies"] + 1
    assert st["kv_prefix_hit_tokens"] >= st0["kv_prefix_hit_tokens"] + 16
    ref = _ref_streams(slot_ref, [(p1, 4, {}), (p2, 4, {})])
    assert [paged_cb.result(r1), paged_cb.result(r2)] == ref


def test_register_prefix_paged_matches_slot(slot_ref, paged_cb):
    sysp = _rep_prompt(32, 9, period=32)
    pida = slot_ref.register_prefix(sysp)
    pidb = paged_cb.register_prefix(sysp)
    hits0 = paged_cb.stats()["kv_prefix_hits"]
    user = _prompt(7, 3)
    ra = slot_ref.submit(user, 6, prefix=pida)
    rb = paged_cb.submit(user, 6, prefix=pidb)
    assert _drain(slot_ref, [ra]) == _drain(paged_cb, [rb], pump=4)
    assert paged_cb.stats()["kv_prefix_hits"] >= hits0 + 2
    assert paged_cb.unregister_prefix(pidb)
    assert not paged_cb.unregister_prefix(pidb)
    slot_ref.unregister_prefix(pida)


# -- chunked prefill TTFT bound --------------------------------------------

def test_chunked_prefill_interleaves_decode(paged_cb):
    """While a 4-bucket prompt prefills, an already-decoding request
    keeps emitting EVERY pump — the decode stall is bounded by one
    chunk, not by the whole foreign prefill."""
    ra = paged_cb.submit(_prompt(6, 11), 20)
    for _ in range(3):
        paged_cb.step_pump(1)
    rb = paged_cb.submit(_rep_prompt(60, 13), 4)  # 60 tokens = 4 buckets
    pumps_while_prefilling = 0
    while paged_cb.stats()["kv_prefill_queue"] > 0:
        before = len(paged_cb.partials([ra])[ra])
        out = paged_cb.step_pump(1)
        if paged_cb.result(ra) is None:
            # the decoding request advanced in the SAME pump that
            # carried a foreign prefill chunk
            assert len(paged_cb.partials([ra])[ra]) > before, out
            pumps_while_prefilling += 1
    assert pumps_while_prefilling >= 2  # the long prompt really chunked
    _drain(paged_cb, [ra, rb], pump=4)


# -- preemption / eviction → re-prefill ------------------------------------

@pytest.mark.slow
def test_eviction_reprefill_parity(params, slot_ref):
    """A pool too small for three full streams preempts and re-prefills
    — and every stream still equals the slot reference byte for byte."""
    tight = _mk(params, n_slots=3, kv_blocks=9)
    prompts = [_rep_prompt(20, 70 + s) for s in range(3)]
    rt = [tight.submit(p, 40) for p in prompts]
    got = _drain(tight, rt, pump=4)
    assert got == _ref_streams(slot_ref, [(p, 40, {}) for p in prompts])
    assert tight.stats()["kv_preemptions"] > 0
    assert tight.stats()["kv_blocks_in_use"] == 0  # all freed at finish


@pytest.mark.slow
def test_sharing_degradation_unblocks_queue(params, slot_ref):
    """A prefix hit whose copy-on-write block makes the job UNaffordable
    (adopting the partial pulls a block from the pool AND still needs a
    fresh copy) must degrade to unshared staging and complete — and must
    NOT re-adopt the released prefix on the restart, which would restore
    the exact pre-degrade state and livelock the queue head."""
    b = _mk(params, n_slots=2, kv_blocks=6)  # exactly one max_len stream
    pa = _rep_prompt(72, 7)                  # 4 full blocks + partial(8)
    _drain(b, [b.submit(pa, 2)], pump=4)     # ...then cached, indexed
    pb = np.concatenate([pa, _rep_prompt(23, 8)])  # 95 tokens, 6 blocks
    rb = b.submit(pb, 1)
    for _ in range(60):
        b.step_pump(2)
        if b.result(rb) is not None:
            break
    assert b.result(rb) is not None, "degraded admission never completed"
    assert b.result(rb) == _ref_streams(slot_ref, [(pb, 1, {})])[0]


# -- snapshot / restore -----------------------------------------------------

@pytest.mark.slow
def test_snapshot_restore_block_tables(params, paged_cb):
    """Mid-decode snapshot → fresh batcher → restore: identical
    continuation, pool accounting included (PR-7 warm-restart
    discipline at the batcher level)."""
    prompts = [_rep_prompt(20, 80 + s) for s in range(3)]
    rids = [paged_cb.submit(p, 10) for p in prompts]
    while paged_cb.stats()["kv_prefill_queue"] > 0:  # admit everyone
        paged_cb.step_pump(1)
    paged_cb.step_pump(4)  # some mid-stream decode state
    snap = paged_cb.snapshot()
    assert snap["layout"] == "paged" and "pool" in snap
    ref = {r: t for r, t in zip(rids, _drain(paged_cb, rids, pump=4))}
    b2 = _mk(params)
    b2.restore(snap)
    assert {r: t for r, t in zip(rids, _drain(b2, rids, pump=4))} == ref
    # the restored pool kept the prefix index: resubmitting an already-
    # seen prompt hits it
    hits0 = b2.stats()["kv_prefix_hits"]
    _drain(b2, [b2.submit(prompts[0], 4)], pump=4)
    assert b2.stats()["kv_prefix_hits"] > hits0


# -- configuration / guards ------------------------------------------------

def test_paged_rejects_unsupported_combinations(params):
    with pytest.raises(ValueError, match="windowed"):
        ContinuousBatcher(params, N_HEADS, max_len=32, prompt_len=16,
                          windowed=True, kv_layout="paged")
    with pytest.raises(ValueError, match="block_size"):
        ContinuousBatcher(params, N_HEADS, max_len=96, prompt_len=16,
                          kv_layout="paged", block_size=7)
    with pytest.raises(ValueError, match="kv_blocks"):
        ContinuousBatcher(params, N_HEADS, max_len=96, prompt_len=16,
                          kv_layout="paged", block_size=16, kv_blocks=2)
    with pytest.raises(ValueError, match="kv_layout"):
        ContinuousBatcher(params, N_HEADS, kv_layout="virtual")


def test_w115_oversized_static_kv_cache_both_ways():
    from nnstreamer_tpu.analysis import lint

    head = ("tensorsrc dimensions=4 types=int32 num-frames=1 ! "
            "tensor_llm_serversink id=91 n-slots=64 max-len=2048 ")
    r_bad = lint(head + "kv-memory-bound=64M")
    assert "NNS-W115" in r_bad.codes
    assert r_bad.exit_code == 1  # warning, not error
    # paged layout resolves it; no declared bound stays silent
    assert "NNS-W115" not in lint(
        head + "kv-memory-bound=64M kv-layout=paged"
    ).codes
    assert "NNS-W115" not in lint(head.rstrip()).codes
    # a bound the static cache fits under is fine too
    assert "NNS-W115" not in lint(head + "kv-memory-bound=64G").codes


def test_requests_view_and_nns_top_render(paged_cb):
    """The SLO ledger feeds requests() and the nns-top --requests
    table (state, blocks, TTFT/TPOT, deadline)."""
    from nnstreamer_tpu.obs.nns_top import render_requests

    rid = paged_cb.submit(_prompt(6, 33), 4, deadline_s=60.0)
    _drain(paged_cb, [rid], pump=4)
    row = paged_cb.requests()[rid]
    assert row["state"] == "done" and row["tokens"] == 4
    assert row["ttft_ms"] is not None and row["tpot_ms"] is not None
    assert row["deadline_s"] is not None
    snap = {"nodes": {"llmsrv": {
        "serving_requests": {str(rid): row},
        "serving_kv_blocks_in_use": 0,
        "serving_kv_blocks": 24,
        "serving_kv_prefix_hits": 3,
        "serving_kv_migrations_out": 2,
        "serving_kv_migrations_in": 1,
        "serving_request_resumes": 1,
    }}}
    out = render_requests(snap)
    assert str(rid) in out and "done" in out and "prefix-hits=3" in out
    # migration & recovery footer (docs/llm-serving.md)
    assert "migrations=2out/1in" in out and "resumes=1" in out
    # one paged decode formulation: the footer has nothing to name
    assert "kv-attn" not in out and "gather" not in out
    assert "TTFT" in out.splitlines()[0]
    assert "LLM serving" in render_requests({"nodes": {}})


def test_paged_slo_metrics_emit_through_obs(obs_reg, paged_cb):
    """The four cataloged nns_kv_*/nns_request_* metrics were emitted
    by the module's shared batcher (constructed with the registry
    active) as the tests above exercised it."""
    assert obs_reg.find("nns_kv_blocks_in_use") is not None
    hits = obs_reg.find("nns_kv_prefix_hits_total")
    assert hits is not None and hits.value > 0
    assert obs_reg.find("nns_request_ttft_ms").count >= 2
    assert obs_reg.find("nns_request_tpot_ms").count >= 2


@pytest.mark.slow
def test_many_request_churn_soak(params):
    """Churn soak: 24 requests of mixed shapes through a tight pool
    with a shared system prompt — every stream equals its solo slot-
    layout reference, the pool balances to zero, and sharing actually
    happened."""
    rng = np.random.default_rng(0)
    sysp = _rep_prompt(16, 99, period=16)
    b = ContinuousBatcher(params, N_HEADS, n_slots=6, max_len=96,
                          prompt_len=16, kv_layout="paged",
                          block_size=16, kv_blocks=24)
    ref = _mk(params, paged=False, n_slots=1)
    expects = {}
    pending = []
    for i in range(24):
        user = _prompt(int(rng.integers(2, 20)), 200 + i)
        prompt = np.concatenate([sysp, user]) if i % 2 else user
        budget = int(rng.integers(2, 14))
        rid = b.submit(prompt, budget)
        if rid is None:
            b.step_pump(int(rng.integers(1, 6)))
            rid = b.submit(prompt, budget)
        if rid is None:
            continue
        pending.append(rid)
        r = ref.submit(prompt, budget)
        expects[rid] = _drain(ref, [r])[0]
        if i % 3 == 0:
            b.step_pump(int(rng.integers(1, 8)))
    while any(b.result(r) is None for r in pending):
        b.step_pump(4)
    assert {r: b.result(r) for r in pending} == expects
    st = b.stats()
    assert st["kv_blocks_in_use"] == 0
    assert st["kv_prefix_hits"] > 0
