"""Queued prompts share one prefill program (models/decode.prefill_packed,
``ContinuousBatcher._advance_prefill``'s packed pump).

Two halves of one mechanism. The PROGRAM: several prompts laid end to end
in one bucket, each on a block boundary, attending only their own rows,
leave the K/V and the last-row logits each would leave alone in the bucket.
The GATE and the BINS: a pump packs only where it finds two or more
bucket-sized fresh prompts queued under a family that has the program; a
lone prompt, a long one, a prefix hit, a resume and the latent families
take the path they always took.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nnstreamer_tpu.models import decode as dec
from nnstreamer_tpu.models import serving
from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.models.serving import ContinuousBatcher

N_HEADS = 4
VOCAB = 257
P, BS = 512, 16
K = P // BS
TOL = 2e-5  # float32 on the CPU: two sound orders of summation

BINS = {
    "assorted": [1, 15, 16, 17, 200],
    "two-fill-512": [256, 256],
    "k-one-block-prompts": [1 + (7 * r) % BS for r in range(K)],
}


@pytest.fixture(scope="module", params=["mha", "gqa"])
def params(request):
    return tfm.init_params(
        jax.random.PRNGKey(7), vocab=VOCAB, d_model=64, n_heads=N_HEADS,
        n_layers=2, n_kv_heads=2 if request.param == "gqa" else None,
    )


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, (n,)).astype(np.int32)


def _lay_out(prompts, p=P, bs=BS):
    """The arguments of one packed bucket, as ``_prefill_bin`` lays them."""
    tokens = np.zeros((1, p), np.int32)
    positions = np.zeros((p,), np.int32)
    segment = np.full((p,), -1, np.int32)
    last = np.full((p // bs,), -1, np.int32)
    starts, row = [], 0
    for r, x in enumerate(prompts):
        t = len(x)
        tokens[0, row: row + t] = x
        positions[row: row + t] = np.arange(t)
        segment[row: row + t] = r
        last[r] = row + t - 1
        starts.append(row)
        row += -(-t // bs) * bs
    assert row <= p
    return (tokens, positions, segment, last), starts


# -- the program --------------------------------------------------------------


@pytest.mark.parametrize("lens", BINS.values(), ids=BINS.keys())
def test_packed_prompts_leave_what_each_leaves_alone(params, lens):
    """Per prompt of the bin: K/V of its rows and the logits of its last
    row equal ``dec.prefill``'s for that prompt alone in the bucket."""
    prompts = [_prompt(n, 40 + i) for i, n in enumerate(lens)]
    args, starts = _lay_out(prompts)
    logits, (ks, vs) = jax.jit(
        lambda *a: dec.prefill_packed(params, *a, N_HEADS)
    )(*args)
    assert logits.shape == (K, VOCAB) and ks.shape[:3] == (2, 1, P)
    alone = jax.jit(lambda toks: dec.prefill(params, toks, N_HEADS, P))
    for r, (x, row) in enumerate(zip(prompts, starts)):
        t = len(x)
        padded = np.zeros((1, P), np.int32)
        padded[0, :t] = x
        want, (wk, wv), _ = alone(padded)
        np.testing.assert_allclose(logits[r], want[0, t - 1], atol=TOL)
        np.testing.assert_allclose(ks[:, 0, row: row + t], wk[:, 0, :t], atol=TOL)
        np.testing.assert_allclose(vs[:, 0, row: row + t], wv[:, 0, :t], atol=TOL)


def test_rows_sampler_gives_each_request_its_own_first_token():
    """One launch over some rows of a bucket's logits picks, row by row, the
    token ``nns_sample_first`` picks with that request's key, fill and
    filters (greedy rows among sampling ones)."""
    R = serving._FIRST_ROWS
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(0, 3, (K, VOCAB)).astype(np.float32))
    rows = rng.permutation(K)[:R].astype(np.int32)
    temp = np.where(np.arange(R) % 3 == 0, 0.0, 0.9).astype(np.float32)
    topk = np.where(np.arange(R) % 2 == 0, 0, 5).astype(np.int32)
    topp = np.where(np.arange(R) % 4 == 1, 0.8, 1.0).astype(np.float32)
    keys = np.stack([serving.request_key(900 + r) for r in range(R)])
    fill = rng.integers(1, P, (R,)).astype(np.int32)
    got = jax.jit(serving.nns_sample_first_rows)(
        logits, rows, temp, topk, topp, keys, fill)
    one = jax.jit(serving.nns_sample_first)
    want = [one(logits[rows[r]], temp[r: r + 1], topk[r: r + 1],
                topp[r: r + 1], keys[r], fill[r]) for r in range(R)]
    assert len(got) == R
    assert [int(x) for x in got] == [int(x) for x in want]


def _batcher(params, packed=True, **kw):
    base = dict(n_slots=K, max_len=P + 2 * BS, prompt_len=P,
                kv_layout="paged", block_size=BS)
    b = ContinuousBatcher(params, N_HEADS, **{**base, **kw})
    if not packed:
        b._prefill_packed = None  # the oracle: every prompt its own bucket
    return b


def _sampling_kw(s):
    return dict(temperature=0.8, top_k=5, seed=900 + s) if s % 2 else {}


def _drain(cb, rids, n=4):
    while any(cb.result(r) is None for r in rids):
        cb.step_pump(n)
    return [cb.result(r) for r in rids]


@pytest.mark.parametrize("lens", BINS.values(), ids=BINS.keys())
def test_packed_streams_are_the_unpacked_ones(params, lens):
    """Served through a bin, every request continues for 16 tokens as it
    does served through a bucket of its own: greedy requests the same
    greedy tokens, sampling ones (every other) the same first token and
    stream, drawn with their own keys."""
    prompts = [_prompt(n, 60 + i) for i, n in enumerate(lens)]
    out = []
    for packed in (True, False):
        b = _batcher(params, packed)
        rids = [b.submit(x, 16, **_sampling_kw(s))
                for s, x in enumerate(prompts)]
        b.step_pump(1)
        st = b.stats()
        assert st["prefill_prompts"] == len(lens)
        assert st["prefill_packed_programs"] == (1 if packed else 0)
        assert st["prefill_programs"] == (1 if packed else len(lens))
        out.append(_drain(b, rids))
    assert out[0] == out[1]


def test_packed_landing_equals_the_bucket_landing_on_an_int8_arena(params):
    """The landing is ``land_stage`` at the bucket's shape, which quantizes
    per token and head: on an int8 arena a bin's prompts decode as they do
    from buckets of their own."""
    prompts = [_prompt(n, 80 + i) for i, n in enumerate([33, 7, 120, 64])]
    out = []
    for packed in (True, False):
        b = _batcher(params, packed, cache_dtype="int8", n_slots=4)
        rids = [b.submit(x, 12) for x in prompts]
        out.append(_drain(b, rids))
        assert b.stats()["prefill_packed_programs"] == int(packed)
    assert out[0] == out[1]


# -- the gate and the bins (a bucket of 64 = 4 blocks) -----------------------

SMALL = dict(n_slots=8, max_len=192, prompt_len=64, kv_layout="paged",
             block_size=16)
_COUNTS = ("prefill_programs", "prefill_prompts", "prefill_packed_programs")


@pytest.fixture(scope="module")
def small_params():
    return tfm.init_params(
        jax.random.PRNGKey(5), vocab=VOCAB, d_model=32, n_heads=N_HEADS,
        n_layers=1,
    )


def _small(small_params, **kw):
    return ContinuousBatcher(small_params, N_HEADS, **{**SMALL, **kw})


def _counts(cb, st0=None):
    st = cb.stats()
    return tuple(st[k] - (st0[k] if st0 else 0) for k in _COUNTS)


def _calls(cb, name):
    """Count the launches of one of the batcher's programs."""
    real, seen = getattr(cb, name), []

    def spy(*a, **kw):
        seen.append(1)
        return real(*a, **kw)

    setattr(cb, name, spy)
    return seen


def _advance(cb):
    with cb._step_lock:
        cb._advance_prefill()
    return [p.req.rid for p in cb._pending]


def test_a_lone_prompt_takes_the_parents_path(small_params):
    """One packable job queued: no packed program; the bucket program,
    the landing and the one-row sampler the batcher always called."""
    b = _small(small_params)
    spies = {n: _calls(b, n) for n in (
        "_prefill", "_land_stage", "_sample1", "_prefill_packed",
        "_sample_rows")}
    rid = b.submit(_prompt(20, 1), 4)
    assert _advance(b) == [rid]
    assert _counts(b) == (1, 1, 0)
    assert {n: len(s) for n, s in spies.items()} == {
        "_prefill": 1, "_land_stage": 1, "_sample1": 1,
        "_prefill_packed": 0, "_sample_rows": 0}
    _drain(b, [rid])


def test_two_queued_prompts_share_one_program(small_params):
    b = _small(small_params)
    spies = {n: _calls(b, n) for n in (
        "_prefill", "_land_stage", "_sample1", "_prefill_packed",
        "_sample_rows")}
    rids = [b.submit(_prompt(n, 2 + n), 4) for n in (20, 30)]
    assert _advance(b) == rids and not b._prefill_q
    assert _counts(b) == (1, 2, 1)
    assert {n: len(s) for n, s in spies.items()} == {
        "_prefill": 0, "_land_stage": 1, "_sample1": 0,
        "_prefill_packed": 1, "_sample_rows": 1}
    _drain(b, rids)


@pytest.mark.parametrize("lens, want", [
    # 40 + 30 tokens (48 + 32 rows) do not fit 64: 30 opens a second bin,
    # 10 (16 rows) goes back into the first, 20 joins the second
    ((40, 30, 10, 20), (2, 4, 2)),
    # the second bin ends with one prompt: the bucket program for it
    ((40, 30, 10), (2, 3, 1)),
    # no two fit one bucket: nothing to pack, the loop the batcher always ran
    ((40, 40), (2, 2, 0)),
], ids=["two-bins", "bin-and-lone", "none-fit"])
def test_bins_fill_first_fit_and_a_bin_of_one_is_a_lone_prompt(small_params,
                                                              lens, want):
    """Activations are queued in queue order whichever program served."""
    b = _small(small_params)
    lone = _calls(b, "_prefill")
    rids = [b.submit(_prompt(n, 9 + i), 4) for i, n in enumerate(lens)]
    assert _advance(b) == rids
    assert _counts(b) == want
    assert len(lone) == want[0] - want[2]
    _drain(b, rids)


@pytest.mark.parametrize("kind", ["long", "prefix-hit"])
def test_a_job_that_is_not_packable_keeps_its_path_and_place(small_params,
                                                             kind):
    """Between two packable jobs, one longer than the bucket or one that a
    registered prefix matches: the chunk path for it, one bin for the
    other two, activations in queue order; every stream is the one a
    batcher that never packs gives."""
    seen = _prompt(40, 30)
    middle = _prompt(100, 31) if kind == "long" else seen
    prompts = [_prompt(20, 32), middle, _prompt(25, 33)]
    out = []
    for packed in (True, False):
        b = _small(small_params)
        if not packed:
            b._prefill_packed = None
        _drain(b, [b.submit(seen, 2)])  # its blocks stay indexed
        st0, hits0 = b.stats(), b.stats()["kv_prefix_hits"]
        rids = [b.submit(x, 6) for x in prompts]
        activated = _advance(b)
        if packed:
            # the bin, then 100 tokens = 2 buckets / the hit's remainder = 1
            assert activated == rids
            assert _counts(b, st0) == ((3, 3, 1) if kind == "long" else (2, 3, 1))
        assert (b.stats()["kv_prefix_hits"] > hits0) == (kind == "prefix-hit")
        out.append(_drain(b, rids))
    assert out[0] == out[1]


def test_prefill_chunks_caps_the_programs_of_a_packing_pump(small_params):
    """``prefill_chunks=1``: one program a pump while anything decodes; the
    prompts no open bin has room for wait their pump."""
    b = _small(small_params, prefill_chunks=1)
    live = b.submit(_prompt(7, 50), 40)
    b.step_pump(1)
    st0 = b.stats()
    rids = [b.submit(_prompt(n, 51 + n), 4) for n in (40, 30, 10)]
    b.step_pump(1)
    assert _counts(b, st0) == (1, 2, 1)  # 40 and 10 share; 30 waits
    assert b.stats()["kv_prefill_queue"] == 1
    b.step_pump(1)
    assert _counts(b, st0) == (2, 3, 1)  # alone in the queue: the bucket program
    _drain(b, [live] + rids)


def test_a_job_the_pool_cannot_afford_waits_while_the_rest_land(small_params):
    """9 blocks, three live requests holding one each (6 free, a block of
    headroom for every live request): 10 tokens (1 block) land, 30 (2
    blocks + the headroom of four) cannot, 10 more can. One bin, one
    program; the job left out is still queued and is served when blocks
    free."""
    b = _small(small_params, max_len=128, kv_blocks=9)
    live = [b.submit(_prompt(7, 70 + s), 3) for s in range(3)]
    b.step_pump(1)
    assert b._active.sum() == 3
    rids = [b.submit(_prompt(n, 74 + s), 3) for s, n in enumerate((10, 30, 10))]
    st0 = b.stats()
    assert _advance(b) == [rids[0], rids[2]]
    assert _counts(b, st0) == (1, 2, 1)
    assert [j.req.rid for j in b._prefill_q] == [rids[1]]
    assert all(x is not None for x in _drain(b, live + rids))


def _longcat(**kw):
    from nnstreamer_tpu.models import longcat as lc

    cfg = lc.LongcatConfig(
        d_model=64, n_heads=4, q_rank=16, kv_rank=8, nope=8, rope=8, v_dim=8,
        d_ff=128, d_expert=32, n_routed=16, n_zero=8, topk=3, n_layers=2,
        vocab=97, n_held=4, expert_offset=4,
    )
    return ContinuousBatcher(
        lc.init_params(cfg, 3, jnp.float32), cfg.n_heads,
        family=lc.LongcatFamily(cfg, jnp.float32), **{**SMALL, **kw},
    )


def _kimi_linear(**kw):
    from nnstreamer_tpu.models import kimi_linear as kl

    cfg = kl.KimiLinearConfig(
        d_model=64, n_heads=4, kv_rank=16, nope=8, rope=8, v_dim=8,
        kda_heads=4, kda_dim=16, gate_rank=8, d_ff=128, d_expert=32,
        n_routed=8, topk=2, n_layers=5, vocab=97, n_held=4, expert_offset=4,
    )
    return ContinuousBatcher(
        kl.init_params(cfg, 11, jnp.float32), cfg.n_heads,
        family=kl.KimiLinearFamily(cfg, jnp.float32), **{**SMALL, **kw},
    )


def _granite_hybrid(**kw):
    from nnstreamer_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig(
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=8,
        ssm_head_dim=8, ssm_state=16, ssm_chunk=8, attn_layers=(2,), d_expert=32,
        d_shared=48, n_routed=8, topk=3, n_layers=4, vocab=97, n_held=4,
        expert_offset=4,
    )
    return ContinuousBatcher(
        gh.init_params(cfg, 11, jnp.float32), cfg.n_heads,
        family=gh.GraniteHybridFamily(cfg, jnp.float32), **{**SMALL, **kw},
    )


@pytest.mark.parametrize("make", [_longcat, _kimi_linear, _granite_hybrid],
                         ids=["longcat", "kimi-linear", "granite-hybrid"])
def test_the_latent_families_never_pack(make):
    """No packed program to ask for: k queued prompts are k buckets."""
    b = make()
    assert b._prefill_packed is None
    rids = [b.submit(_prompt(n, 90 + n) % 97, 3) for n in (20, 30, 10)]
    assert _advance(b) == rids
    assert _counts(b) == (3, 3, 0)
    _drain(b, rids)


def test_stats_and_span_carry_the_counts(small_params):
    """``stats()`` and the ``nns.pump.prefill`` span say the same: programs
    launched and the prompts they completed, for a packing pump and for one
    that chunks a long prompt."""
    from nnstreamer_tpu import trace

    tr = trace.enable()
    try:
        b = _small(small_params)
        rids = [b.submit(_prompt(n, 95 + n), 3) for n in (20, 30)]
        _advance(b)
        rids.append(b.submit(_prompt(100, 99), 3))
        _drain(b, rids)
        spans = [e["args"] for e in tr.events()
                 if e["name"] == "nns.pump.prefill"]
    finally:
        trace.disable()
    assert all({"prefill_q", "buckets", "programs", "prompts", "activated"}
               <= set(s) for s in spans)
    assert (spans[0]["programs"], spans[0]["prompts"]) == (1, 2)
    assert sum(s["programs"] for s in spans) == 3 == b.stats()["prefill_programs"]
    assert sum(s["prompts"] for s in spans) == 3 == b.stats()["prefill_prompts"]
    assert b.stats()["prefill_packed_programs"] == 1
