"""Multi-step pump tests (models/serving.py step_pump / spec_pump).

The pumps exist to amortize host↔device round trips: N decode steps (or
R whole speculative rounds) per compiled program, ONE device→host read
per pump. The load-bearing invariant is EXACT stream equality with the
per-token paths — a pump is a batching of the step loop, never a
different decoder. Role-match: the per-buffer invoke loop of
gst/nnstreamer/tensor_filter/tensor_filter.c batched along the token
axis.
"""

import jax
import numpy as np
import pytest

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
def draft_params():
    return tfm.init_params(
        jax.random.PRNGKey(11), vocab=257, d_model=32, n_heads=N_HEADS,
        n_layers=1,
    )


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 257, (n,)).astype(np.int32)


def _rep_prompt(n, seed, period=6):
    """Repetitive prompt: the n-gram miner's best case."""
    base = np.random.default_rng(seed).integers(1, 257, (period,))
    return np.tile(base, -(-n // period))[:n].astype(np.int32)


def _drain_steps(cb, rids):
    while any(cb.result(r) is None for r in rids):
        cb.step()


def _drain_pump(cb, rids, n):
    while any(cb.result(r) is None for r in rids):
        cb.step_pump(n)


def _drain_spec_pump(cb, rids, rounds, k, ngram=2):
    while any(cb.result(r) is None for r in rids):
        cb.spec_pump(rounds=rounds, k=k, ngram=ngram)


def _tokens(cb, rids):
    return [cb.result(r) for r in rids]


def _twin(params, **kw):
    return ContinuousBatcher(
        params, N_HEADS, n_slots=4, max_len=96, prompt_len=16, **kw
    )


@pytest.mark.parametrize("n", [1, 3, 8, 64])
def test_step_pump_matches_per_token_steps(params, n):
    """A pump of n is exactly n per-token steps, for any n (including
    n past every budget — idle lanes emit -1 and are dropped)."""
    prompts = [_prompt(5 + s, 100 + s) for s in range(4)]
    a, b = _twin(params), _twin(params)
    ra = [a.submit(p, 9) for p in prompts]
    rb = [b.submit(p, 9) for p in prompts]
    _drain_steps(a, ra)
    _drain_pump(b, rb, n)
    assert _tokens(a, ra) == _tokens(b, rb)


def test_step_pump_stop_token_deactivates_on_device(params):
    """The stop token ends a stream INSIDE the scan — tokens after it
    in the same pump are discarded, exactly like per-token stepping."""
    prompts = [_prompt(5, 7)]
    a, b = _twin(params), _twin(params)
    # pick the 3rd greedy token as the stop token so it triggers mid-pump
    ra = [a.submit(prompts[0], 12)]
    _drain_steps(a, ra)
    stop = _tokens(a, ra)[0][2]
    a2, b2 = _twin(params), _twin(params)
    r2 = [a2.submit(prompts[0], 12, stop_token=stop)]
    r3 = [b2.submit(prompts[0], 12, stop_token=stop)]
    _drain_steps(a2, r2)
    _drain_pump(b2, r3, 8)
    assert _tokens(a2, r2) == _tokens(b2, r3)
    assert _tokens(b2, r3)[0][-1] == stop


def test_step_pump_staggered_admissions_join_next_pump(params):
    """Requests submitted between pumps join at the next pump and still
    produce their solo-greedy stream."""
    a, b = _twin(params), _twin(params)
    p0, p1 = _prompt(5, 1), _prompt(7, 2)
    ra0, rb0 = a.submit(p0, 10), b.submit(p0, 10)
    for _ in range(2):
        a.step()
    b.step_pump(2)
    ra1, rb1 = a.submit(p1, 6), b.submit(p1, 6)
    _drain_steps(a, [ra0, ra1])
    _drain_pump(b, [rb0, rb1], 4)
    assert _tokens(a, [ra0, ra1]) == _tokens(b, [rb0, rb1])


def test_step_pump_sampling_stream_deterministic(params):
    """Sampling slots: the per-(seed, position) key discipline makes a
    pumped stream identical to the per-token stream."""
    p = _prompt(6, 3)
    a, b = _twin(params), _twin(params)
    ra = a.submit(p, 8, temperature=0.8, top_k=40, seed=5)
    rb = b.submit(p, 8, temperature=0.8, top_k=40, seed=5)
    _drain_steps(a, [ra])
    _drain_pump(b, [rb], 8)
    assert a.result(ra) == b.result(rb)


@pytest.mark.parametrize("rounds", [1, 2, 4])
def test_spec_pump_greedy_exact(params, rounds):
    """Greedy speculation is exact by construction: spec_pump streams
    equal plain per-token streams whatever the round batching."""
    prompts = [_rep_prompt(12, 50 + s) for s in range(4)]
    a, b = _twin(params), _twin(params)
    ra = [a.submit(p, 12) for p in prompts]
    rb = [b.submit(p, 12) for p in prompts]
    _drain_steps(a, ra)
    _drain_spec_pump(b, rb, rounds, k=4)
    assert _tokens(a, ra) == _tokens(b, rb)
    st = b.stats()
    assert st["spec_rounds"] >= rounds


def test_spec_pump_acceptance_telemetry_rides_packed_readback(params):
    """Acceptance counters update from the pump's packed vector — no
    separate transfer — and a repetitive context actually accepts."""
    p = _rep_prompt(24, 9, period=4)
    b = _twin(params)
    rb = b.submit(p, 16)
    _drain_spec_pump(b, [rb], 4, k=4, ngram=1)
    st = b.stats()
    assert st["spec_columns"] > 0
    assert st["spec_accepted_tokens"] >= 0
    assert st["tokens_per_step"] >= 1.0  # never worse than plain steps


def test_spec_pump_sampling_exact_vs_host_rounds(params):
    """Sampling speculation: device-mined proposals differ from host
    mining only in WHERE the mining ran — acceptance is the same
    program, so a pumped sampling stream must remain a valid
    deterministic stream (same seed ⇒ same stream on repeat runs)."""
    p = _rep_prompt(16, 21, period=5)
    outs = []
    for _ in range(2):
        b = _twin(params)
        rb = b.submit(p, 10, temperature=0.7, seed=3)
        _drain_spec_pump(b, [rb], 3, k=3, ngram=1)
        outs.append(b.result(rb))
    assert outs[0] == outs[1]


def test_spec_pump_windowed_ring_exact(params):
    """Windowed ring + device n-gram proposals: streams equal the
    windowed per-token stream (verify-then-commit never clobbers the
    ring with rejected columns)."""
    prompts = [_rep_prompt(10, 70 + s) for s in range(3)]
    kw = dict(windowed=True, max_len=32, prompt_len=16)
    a = ContinuousBatcher(params, N_HEADS, n_slots=4, **kw)
    b = ContinuousBatcher(params, N_HEADS, n_slots=4, **kw)
    ra = [a.submit(p, 10) for p in prompts]
    rb = [b.submit(p, 10) for p in prompts]
    _drain_steps(a, ra)
    _drain_spec_pump(b, rb, 3, k=3)
    assert _tokens(a, ra) == _tokens(b, rb)


def test_spec_pump_draft_inscan_exact(params, draft_params):
    """Draft-model proposals mined IN-SCAN (k draft steps per round
    inside the pump program) produce the plain greedy stream."""
    prompts = [_prompt(8, 80 + s) for s in range(4)]
    a = _twin(params)
    b = _twin(params, draft_params=draft_params, draft_n_heads=N_HEADS)
    ra = [a.submit(p, 10) for p in prompts]
    rb = [b.submit(p, 10) for p in prompts]
    _drain_steps(a, ra)
    _drain_spec_pump(b, rb, 3, k=3)
    assert _tokens(a, ra) == _tokens(b, rb)
    assert b.stats()["spec_columns"] > 0  # a draft always proposes


def test_step_pump_draft_cache_stays_synced(params, draft_params):
    """step_pump on a draft batcher advances the draft cache in-scan
    (in lockstep with the target): a spec_pump AFTER a step_pump still
    produces the exact stream — no holes in the draft cache."""
    p = _prompt(6, 31)
    a = _twin(params)
    b = _twin(params, draft_params=draft_params, draft_n_heads=N_HEADS)
    ra = a.submit(p, 12)
    rb = b.submit(p, 12)
    _drain_steps(a, [ra])
    b.step_pump(4)  # first 4 tokens via plain pump
    _drain_spec_pump(b, [rb], 2, k=3)  # rest speculated
    assert a.result(ra) == b.result(rb)


def test_pump_int8_cache_matches_per_token(params):
    """int8 KV cache + pump: quantization happens inside the scan just
    as inside the step — streams match the int8 per-token path."""
    p = _prompt(6, 41)
    a = _twin(params, cache_dtype="int8")
    b = _twin(params, cache_dtype="int8")
    ra = a.submit(p, 8)
    rb = b.submit(p, 8)
    _drain_steps(a, [ra])
    _drain_pump(b, [rb], 8)
    assert a.result(ra) == b.result(rb)


def test_pump_mesh_sharded_slots_match_unsharded(params):
    """Pumps under a slot-sharded mesh (SPMD decode) equal the
    unsharded pumped streams."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8, axes=("dp",))
    prompts = [_prompt(5 + s, 90 + s) for s in range(8)]
    outs = {}
    for label, kw in (("plain", {}), ("mesh", dict(mesh=mesh))):
        cb = ContinuousBatcher(
            params, N_HEADS, n_slots=8, max_len=64, prompt_len=16, **kw
        )
        rids = [cb.submit(p, 8) for p in prompts]
        _drain_pump(cb, rids, 8)
        outs[label] = _tokens(cb, rids)
    assert outs["plain"] == outs["mesh"]


def test_pump_mesh_pallas_spec_pump_compose(params):
    """The full stack in one server: mesh + pallas step pumps and a
    spec pump on the same batcher keep the exact greedy stream."""
    from nnstreamer_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8, axes=("dp",))
    prompts = [_rep_prompt(10, 60 + s) for s in range(8)]
    a = ContinuousBatcher(
        params, N_HEADS, n_slots=8, max_len=64, prompt_len=16
    )
    b = ContinuousBatcher(
        params, N_HEADS, n_slots=8, max_len=64, prompt_len=16,
        mesh=mesh, attn_impl="pallas",
    )
    ra = [a.submit(p, 8) for p in prompts]
    rb = [b.submit(p, 8) for p in prompts]
    _drain_steps(a, ra)
    while any(b.result(r) is None for r in rb):
        b.step_pump(2)
        b.spec_pump(rounds=2, k=3)
    assert _tokens(a, ra) == _tokens(b, rb)


def test_spec_pump_room_clamp_falls_back_near_max_len(params):
    """When the cache is nearly full a wide pump cannot fit: spec_pump
    must clamp rounds / fall back to the shrinking-k host round and
    still finish the stream exactly."""
    p = _prompt(12, 55)
    a = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=32,
                          prompt_len=16)
    b = ContinuousBatcher(params, N_HEADS, n_slots=2, max_len=32,
                          prompt_len=16)
    ra = a.submit(p, 20)  # 12 + 20 = max_len exactly
    rb = b.submit(p, 20)
    _drain_steps(a, [ra])
    _drain_spec_pump(b, [rb], 8, k=4)
    assert a.result(ra) == b.result(rb)


def test_spec_pump_budget_tail_stays_on_warm_programs(params):
    """Regression for the BENCH_CPU_FULL_r05 spec×cb throughput
    collapse (8.0/4.8 vs 37.5 tok/s plain): ``rounds`` is a STATIC
    scan length, so clamping it by live request budgets compiled a
    fresh XLA program for every budget tail — warm-up built rounds=2/1
    programs, the measured drain then compiled rounds=4 inside the
    timed region and re-compiled its way down a 4→2→1 ladder as
    budgets shrank. Pin: after the first pump, draining uneven budget
    tails runs entirely on warm programs (zero new compiles), and per
    program launch spec emits at least as many tokens as a plain pump
    of the same depth — the "spec×cb ≥ plain-cb" cliff guard in
    deterministic launch-count terms rather than flaky wall-clock."""
    b = _twin(params)
    prompts = [_rep_prompt(12, 80 + s, period=4) for s in range(3)]
    # uneven budgets: with the bug, remaining.max() walks 11→…→1 and
    # each power-of-two floor below 4 is a brand-new program
    rids = [b.submit(p, 5 + 3 * s) for s, p in enumerate(prompts)]
    b.spec_pump(rounds=4, k=4, ngram=1)
    warm = b._spec_pump_greedy.func._cache_size()
    spec_launches = 1
    while any(b.result(r) is None for r in rids):
        b.spec_pump(rounds=4, k=4, ngram=1)
        spec_launches += 1
    assert b._spec_pump_greedy.func._cache_size() == warm, (
        "budget tail recompiled spec_pump: the static scan length must "
        "not depend on live budgets (slots idle out on device)"
    )
    assert warm == 1  # one (rounds=4, k=4) greedy program, ever
    # spec×cb ≥ plain-cb per launch: a spec pump certifies ≥ rounds
    # tokens per active stream (1 per round even at zero acceptance),
    # a plain pump of depth n emits exactly n — so spec must never
    # need more launches than plain step_pump(4) on the same load.
    a = _twin(params)
    ra = [a.submit(p, 5 + 3 * s) for s, p in enumerate(prompts)]
    plain_launches = 0
    while any(a.result(r) is None for r in ra):
        a.step_pump(4)
        plain_launches += 1
    assert spec_launches <= plain_launches
    assert _tokens(a, ra) == _tokens(b, rids)  # and byte-identical
    assert b.stats()["spec_accepted_tokens"] > 0  # non-trivial run


def test_steady_pumps_ship_no_host_state(params):
    """Regression beside the no-new-compiles pin above: the per-slot
    budget/stop/active pump state is CARRIED on device between pumps
    (the scan already computes next-pump values), so a steady pump-only
    drain must rebuild + re-ship host state ZERO times. It used to be
    recomputed and H2D-shipped on EVERY pump even when no slot changed.
    The cache invalidates exactly on submit (admission) and finish —
    both pinned here; jax's transfer guard additionally proves the
    steady-state pump launch performs no host→device transfer at all."""
    b = _twin(params)
    rids = [b.submit(_prompt(5 + s, 130 + s), 40) for s in range(3)]
    b.step_pump(4)  # admissions applied, state shipped once
    builds0 = b._host_state_builds
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            b.step_pump(4)
    assert b._host_state_builds == builds0, (
        "steady pumps rebuilt host pump state"
    )
    # admission invalidates: exactly one rebuild at the next pump
    rids.append(b.submit(_prompt(4, 140), 30))
    b.step_pump(4)
    assert b._host_state_builds == builds0 + 1
    # a finishing request invalidates too (slot leaves the batch)
    b2 = _twin(params)
    r2 = [b2.submit(_prompt(5, 141), 3), b2.submit(_prompt(6, 142), 40)]
    b2.step_pump(4)  # request 0 finishes inside this pump
    n = b2._host_state_builds
    b2.step_pump(4)
    assert b2._host_state_builds == n + 1
    # and the carried state stays EXACT: drain to the per-token streams
    a = _twin(params)
    ra = [a.submit(_prompt(5 + s, 130 + s), 40) for s in range(3)]
    ra.append(a.submit(_prompt(4, 140), 30))
    _drain_steps(a, ra)
    _drain_pump(b, rids, 4)
    assert _tokens(a, ra) == _tokens(b, rids)


def test_ngram_device_proposer_mines_recent_context(params):
    """device_ngram_propose finds the most recent suffix match and
    proposes its continuation; -1 where nothing matches."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models.serving import device_ngram_propose

    hist = jnp.asarray(np.array([
        [5, 6, 7, 5, 6, 9, 5, 6] + [-1] * 8,   # pending 6 at pos 7
        [1, 2, 3, 4, 5, 6, 7, 8] + [-1] * 8,   # no repeat: nothing
    ], np.int32))
    pos = jnp.asarray(np.array([7, 7], np.int32))
    props = np.asarray(device_ngram_propose(hist, pos, k=3, g=2))
    # slot 0: latest earlier "5 6" ends at j=4 → proposes hist[5], hist[6]
    assert props[0].tolist() == [9, 5]
    assert props[1].tolist() == [-1, -1]


def test_spec_pump_windowed_ring_wrap_mines_exactly(params):
    """A windowed stream that OUTRUNS the ring (prompt+budget > W):
    hist mirrors the KV ring's a % H layout, so post-wrap device
    n-gram mining stays exact — streams equal the per-token windowed
    reference, and the repetitive workload still accepts proposals
    after the wrap."""
    kw = dict(windowed=True, max_len=16, prompt_len=16)
    a = ContinuousBatcher(params, N_HEADS, n_slots=2, **kw)
    b = ContinuousBatcher(params, N_HEADS, n_slots=2, **kw)
    p = _rep_prompt(12, 77, period=3)
    ra = a.submit(p, 24)  # 12 + 24 >> W=16: wraps mid-generation
    rb = b.submit(p, 24)
    _drain_steps(a, [ra])
    _drain_spec_pump(b, [rb], 3, k=3, ngram=1)
    assert a.result(ra) == b.result(rb)
    st = b.stats()
    # ACCEPTED > 0 pins the exact mining — garbage proposals from a
    # broken unroll would be offered (columns > 0) yet all rejected
    assert st["spec_accepted_tokens"] > 0


def test_ngram_device_proposer_wrap_unrolls_ring():
    """wrap=True: the miner unrolls the ring (token at absolute pos a
    lives at a % H) into stream order before matching — pinned with a
    hand-built wrapped history so a broken unroll cannot hide behind
    verification (wrong proposals are rejected, not exposed)."""
    import jax.numpy as jnp

    from nnstreamer_tpu.models.serving import device_ngram_propose

    # stream (period 5): [1,2,3,5,6]*2 + [1]; abs positions 0..10,
    # H=8 ⇒ ring cell a%8; pending token 1 at abs pos 10 (cell 2)
    hist = jnp.asarray(np.array(
        [[5, 6, 1, 5, 6, 1, 2, 3]], np.int32
    ))
    pos = jnp.asarray(np.array([10], np.int32))
    props = np.asarray(
        device_ngram_propose(hist, pos, k=3, g=2, wrap=True)
    )
    # last H tokens in order: [5,6,1,2,3,5,6,1]; suffix 2-gram (6,1)
    # recurs ending at index 2 → proposals are the following [2, 3]
    assert props[0].tolist() == [2, 3]
    # without wrap the same ring bytes mine garbage — the unroll is
    # what makes post-wrap mining exact
    raw = np.asarray(device_ngram_propose(hist, pos, k=3, g=2))
    assert raw[0].tolist() != [2, 3]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_pump_schedule_invariance(params, seed):
    """Greedy streams are SCHEDULE-INVARIANT: whatever interleaving of
    step / step_pump(n) / spec_pump(rounds, k) drains the batch —
    with staggered random submissions between operations — every
    request's tokens equal the plain per-token reference. The fuzz net
    over the whole pump surface."""
    rng = np.random.default_rng(seed)
    a = _twin(params)   # reference: plain steps only
    b = _twin(params)   # fuzzed: random pump schedule
    prompts = [
        _rep_prompt(int(rng.integers(4, 14)), 200 + seed * 10 + i,
                    period=int(rng.integers(2, 6)))
        for i in range(6)
    ]
    budgets = [int(rng.integers(2, 12)) for _ in prompts]
    ra, rb = [], []
    queue = list(zip(prompts, budgets))

    def submit_some(cb, rids, k):
        for _ in range(k):
            if len(rids) < len(prompts):
                p, n = queue[len(rids)]
                rid = cb.submit(p, n)
                if rid is None:
                    break
                rids.append(rid)

    submit_some(a, ra, 2)
    submit_some(b, rb, 2)
    while len(ra) < len(prompts) or any(
        a.result(r) is None for r in ra
    ):
        a.step()
        submit_some(a, ra, 1)
    ops = ("step", "pump", "spec")
    while len(rb) < len(prompts) or any(
        b.result(r) is None for r in rb
    ):
        op = ops[int(rng.integers(0, 3))]
        if op == "step":
            b.step()
        elif op == "pump":
            b.step_pump(int(rng.integers(1, 7)))
        else:
            b.spec_pump(rounds=int(rng.integers(1, 4)),
                        k=int(rng.integers(2, 5)),
                        ngram=int(rng.integers(1, 3)))
        submit_some(b, rb, int(rng.integers(0, 3)))
    assert _tokens(a, ra) == _tokens(b, rb)


@pytest.mark.parametrize("seed", [10, 11, 12])
def test_random_config_matrix_pump_equivalence(params, draft_params,
                                               seed):
    """Config-matrix fuzz: a random serving configuration (windowed ×
    int8 cache × pallas attention × draft model × per-request
    sampling) drained by pumps equals the SAME configuration drained
    per-token. Complements the explicit matrix tests with random
    combinations."""
    rng = np.random.default_rng(seed)
    kw = {}
    if rng.integers(0, 2):
        kw.update(windowed=True, max_len=32, prompt_len=16)
    else:
        kw.update(max_len=96, prompt_len=16)
    if rng.integers(0, 2):
        kw["cache_dtype"] = "int8"
    if rng.integers(0, 2):
        kw["attn_impl"] = "pallas"
    if rng.integers(0, 2):
        kw.update(draft_params=draft_params, draft_n_heads=N_HEADS)

    def mk():
        return ContinuousBatcher(params, N_HEADS, n_slots=2, **kw)

    a, b = mk(), mk()
    subs = []
    for i in range(3):
        p = _rep_prompt(int(rng.integers(4, 12)), 300 + seed * 7 + i,
                        period=int(rng.integers(2, 5)))
        s_kw = {}
        if rng.integers(0, 2):
            s_kw = dict(temperature=0.7, top_k=30, seed=int(i))
        subs.append((p, int(rng.integers(2, 9)), s_kw))
    # spec rounds on SAMPLING slots are distribution-exact, not
    # byte-identical (spec_accept keys per (seed, pos, draw)) — the
    # byte-equality fuzz may only use spec_pump on greedy workloads
    any_sampling = any(s for _, _, s in subs)
    ra = [a.submit(p, n, **s) for p, n, s in subs[:2]]
    rb = [b.submit(p, n, **s) for p, n, s in subs[:2]]
    while any(a.result(r) is None for r in ra):
        a.step()
    while any(b.result(r) is None for r in rb):
        if any_sampling or rng.integers(0, 2):
            b.step_pump(int(rng.integers(1, 6)))
        else:
            b.spec_pump(rounds=2, k=3, ngram=1)
    # late third submission joins a half-drained batch on both sides
    p, n, s_kw = subs[2]
    ra.append(a.submit(p, n, **s_kw))
    rb.append(b.submit(p, n, **s_kw))
    while any(a.result(r) is None for r in ra):
        a.step()
    while any(b.result(r) is None for r in rb):
        b.step_pump(3)
    assert _tokens(a, ra) == _tokens(b, rb)


# -- admission: one packed transfer, one launch (jit_nns_admit) -------------

_SLOT_STATE = ("_tok", "_pos", "_temp", "_topk", "_topp", "_keys", "_hist")
_PAGED = dict(kv_layout="paged", block_size=16)


def _slot_state(cb):
    return {k: np.asarray(getattr(cb, k)).copy() for k in _SLOT_STATE}


def _queue(cb, n):
    """Run the host side of admission until ``n`` rows wait in ``_pending``
    (the slot layout queues in submit; the paged one spends a budget read
    off the prefill queue per call)."""
    with cb._step_lock:
        while cb._paged and len(cb._pending) < n:
            cb._advance_prefill()
    assert len(cb._pending) == n
    return list(cb._pending)


def _apply(cb):
    """One ``_apply_pending``; -> (admit launches, requests admitted) it took."""
    st0 = cb.stats()
    with cb._step_lock:
        cb._apply_pending()
    st = cb.stats()
    return (st["admit_launches"] - st0["admit_launches"],
            st["admitted"] - st0["admitted"])


def _seven_writes(state, pending, finished=()):
    """The numpy model of admission: per queued row that joins the batch,
    the seven per-slot writes of the eager chain this program replaced."""
    want = {k: v.copy() for k, v in state.items()}
    for p in pending:
        if p.req.rid in finished:
            continue
        first = p.req.tokens[-1]  # the prefill's token, or a resume's
        row = p.hist_row.copy()
        row[p.fill] = first
        want["_tok"][p.slot] = first
        want["_pos"][p.slot] = p.fill
        want["_temp"][p.slot] = np.float32(p.req.temperature)
        want["_topk"][p.slot] = p.req.top_k
        want["_topp"][p.slot] = np.float32(p.req.top_p)
        want["_keys"][p.slot] = np.asarray(p.req.key, np.uint32)
        want["_hist"][p.slot] = row
    return want


def _assert_bitwise(cb, want):
    for k, v in _slot_state(cb).items():
        assert v.dtype == want[k].dtype, k
        assert v.tobytes() == want[k].tobytes(), k


@pytest.mark.parametrize("layout", ["slot", "paged"])
@pytest.mark.parametrize("rows", [1, 3, 4])
def test_admission_is_one_launch_of_the_seven_writes(params, layout, rows):
    """However many admissions a pump finds queued (1, 3, n_slots), it
    applies them with ONE launch of the admit program, and the per-slot
    device state after it is bit for bit what seven writes per row give."""
    b = _twin(params, **(_PAGED if layout == "paged" else {}))
    for s in range(rows):
        b.submit(_prompt(5 + 2 * s, 200 + s), 12, temperature=0.3 + 0.2 * s,
                 top_k=3 + s, top_p=0.95 - 0.1 * s, seed=50 + s)
    pending = _queue(b, rows)
    before = _slot_state(b)
    assert _apply(b) == (1, rows)
    _assert_bitwise(b, _seven_writes(before, pending))
    assert b._active.sum() == rows and not b._pending
    assert _apply(b) == (0, 0)  # nothing queued: no launch


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_admission_batch_with_resumed_and_finished_rows(params, layout):
    """One batch that holds a plain row, a row that ends on its first token
    (its stop id; paged also a budget of 1, which the slot layout finishes
    in submit) and, paged, a ``resumed`` row (re-prefill of a migrated
    request, first token a host int): one launch, the finished rows are not
    in it and leave their slots untouched, and the streams are exactly the
    per-token ones."""
    kw = _PAGED if layout == "paged" else {}
    a, b = _twin(params, **kw), _twin(params, **kw)
    p_plain, p_stop, p_one, p_mig = (_prompt(6 + s, 220 + s) for s in range(4))
    ref_plain, ref_stop = a.submit(p_plain, 9), a.submit(p_stop, 9)
    _drain_steps(a, [ref_plain, ref_stop])
    stop_id = a.result(ref_stop)[0]
    rids = [b.submit(p_plain, 9), b.submit(p_stop, 9, stop_token=stop_id)]
    finished = {rids[1]}
    want_tokens = [a.result(ref_plain), [stop_id]]
    if layout == "paged":
        rids.append(b.submit(p_one, 1))
        finished.add(rids[2])
        ref_one = a.submit(p_one, 1)
        ref_mig = a.submit(p_mig, 9)
        _drain_steps(a, [ref_one, ref_mig])
        c = _twin(params, **kw)
        mig = c.submit(p_mig, 9)
        while len(c.partials([mig]).get(mig, [])) < 3:
            c.step()
        rids.append(b.resume_from_span(c.extract_request(mig)))
        want_tokens += [a.result(ref_one), a.result(ref_mig)]
    pending = _queue(b, len(rids))
    assert [p.resumed for p in pending] == [False, False, False, True][
        : len(rids)]
    before = _slot_state(b)
    assert _apply(b) == (1, len(rids) - len(finished))
    _assert_bitwise(b, _seven_writes(before, pending, finished))
    assert [b.result(r) is not None for r in rids] == [
        r in finished for r in rids]
    _drain_steps(b, rids)
    assert _tokens(b, rids) == want_tokens


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_admission_compiles_once(params, layout):
    """Beside the no-new-compiles pins above: the admit program has ONE
    static shape per batcher and the first-token read launches nothing, so
    after the first admission, admissions of 1, 2 and n_slots rows compile
    nothing at all (a compile inside a measured window fails a benchmark
    run)."""
    import jax.monitoring as mon

    b = _twin(params, **(_PAGED if layout == "paged" else {}))

    def admit(n_rows, seed):
        rids = [b.submit(_prompt(5 + s, seed + s), 3) for s in range(n_rows)]
        _queue(b, n_rows)
        assert _apply(b) == (1, n_rows)
        return rids

    _drain_steps(b, admit(1, 300))  # warm: every program of the path,
    _drain_steps(b, admit(2, 305))  # and the one that queued prompts share
    compiled = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(secs)

    mon.register_event_duration_secs_listener(on_duration)
    try:
        for n_rows in (1, 2, 4):
            rids = admit(n_rows, 310 + 10 * n_rows)
            assert not compiled, f"{n_rows} rows compiled {len(compiled)}"
            _drain_steps(b, rids)
    finally:
        mon.unregister_event_duration_listener(on_duration)
    assert b._admit._cache_size() == 1


# -- admission keeps pace: the prefill budget follows the queue --------------

_ADMISSION_STATS = ("kv_prefill_chunks", "prefill_pumps", "admit_launches",
                    "admitted")


def _stat_deltas(cb, st0):
    st = cb.stats()
    return tuple(st[k] - st0[k] for k in _ADMISSION_STATS)


def _sampling_kw(s):
    """Every other request samples, on a seed of its own."""
    return dict(temperature=0.8, top_k=5, seed=900 + s) if s % 2 else {}


def _one_decoding_then_queue(cb, k, vocab=257):
    """One request live and decoding, then ``k`` more submitted (each a
    bucket-sized prompt) and waiting in the prefill queue."""
    first = cb.submit(_prompt(7, 500) % vocab, 24)
    cb.step_pump(2)
    assert cb._active.sum() == 1 and not cb._prefill_q
    rids = [cb.submit(_prompt(5 + 2 * s, 510 + s) % vocab, 6 + s,
                      **_sampling_kw(s)) for s in range(k)]
    assert cb.stats()["kv_prefill_queue"] == k
    return [first] + rids


@pytest.mark.parametrize("kind", ["paged", "paged-longcat"])
@pytest.mark.parametrize("k", [2, 3])
def test_pump_prefills_every_queued_job(params, kind, k):
    """With k jobs queued and a slot decoding, ONE pump launches k bucket
    programs and splices the k requests in with ONE launch of the admit
    program; every stream is byte for byte the reference's (the slot
    layout drained per token; for the latent family, which has no slot
    layout, a batcher capped at one bucket a pump)."""
    if kind == "paged-longcat":
        b, ref, vocab = _longcat_twin(), _longcat_twin(prefill_chunks=1), 97
    else:
        b, ref, vocab = _twin(params, **_PAGED), _twin(params), 257
    rids = _one_decoding_then_queue(b, k, vocab)
    st0 = b.stats()
    b.step_pump(2)
    assert _stat_deltas(b, st0) == (k, 1, 1, k)
    assert b._active.sum() == k + 1 and b.stats()["kv_prefill_queue"] == 0
    assert b.result(rids[0]) is None  # it decoded through that pump
    _drain_pump(b, rids, 2)
    want = [ref.submit(_prompt(7, 500) % vocab, 24)]
    want += [ref.submit(_prompt(5 + 2 * s, 510 + s) % vocab, 6 + s,
                        **_sampling_kw(s)) for s in range(k)]
    _drain_steps(ref, want)
    assert _tokens(b, rids) == _tokens(ref, want)


@pytest.mark.parametrize("cap", [1, 2])
def test_prefill_chunks_given_caps_a_pump(params, cap):
    """``prefill_chunks=N`` keeps its meaning: at most N bucket programs a
    pump while anything decodes, whatever is queued (N=1: one admission a
    pump, one admit launch each)."""
    b = _twin(params, prefill_chunks=cap, **_PAGED)
    rids = _one_decoding_then_queue(b, 3)
    st0 = b.stats()
    b.step_pump(2)
    assert _stat_deltas(b, st0) == (cap, 1, 1, cap)
    b.step_pump(2)
    assert _stat_deltas(b, st0) == ((3, 2, 2, 3) if cap == 2 else (2, 2, 2, 2))
    _drain_pump(b, rids, 2)
    assert _stat_deltas(b, st0)[0] == 3


def test_buckets_of_one_pump_run_one_at_a_time(params, monkeypatch):
    """Before every bucket but a span's first the host waits for what the
    last one's programs produce (the arena it landed in, its first token):
    k buckets in a pump hold one bucket's logits and stage on the device,
    not k, so peak memory does not grow with the queue."""
    from nnstreamer_tpu.models import serving

    b = _twin(params, **_PAGED)
    rids = _one_decoding_then_queue(b, 3)
    waited = []
    real = jax.block_until_ready

    def spy(x):
        waited.append(x)
        return real(x)

    monkeypatch.setattr(serving.jax, "block_until_ready", spy)
    with b._step_lock:
        b._advance_prefill()
    monkeypatch.undo()
    assert waited[0] is None and len(waited) == 3
    for cache, first in waited[1:]:
        assert isinstance(first, jax.Array)
        assert jax.tree_util.tree_leaves(cache)
    _drain_pump(b, rids, 2)


def test_lone_long_prompt_still_chunks_one_bucket_a_pump(params):
    """The budget is max(1, jobs queued): a long prompt ALONE in the queue
    advances one bucket a pump beside a decoding slot; with a second job
    behind it the pump spends two, front job first."""
    b = _twin(params, **_PAGED)
    live = b.submit(_prompt(7, 520), 40)
    b.step_pump(1)
    long_ = b.submit(_rep_prompt(60, 521), 3)  # 60 tokens = 4 buckets
    st0 = b.stats()
    b.step_pump(1)
    assert _stat_deltas(b, st0) == (1, 1, 0, 0)
    short = b.submit(_prompt(5, 522), 3)
    b.step_pump(1)
    assert _stat_deltas(b, st0) == (3, 2, 0, 0)  # both spent on the long job
    b.step_pump(1)  # its last bucket, then the short job's: both admitted
    assert _stat_deltas(b, st0) == (5, 3, 1, 2)
    _drain_pump(b, [live, long_, short], 4)


_SEEDS = [0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32 + 5, 2**40 + 3, 2**63 - 1,
          -1, -2**31 - 1, -2**40, -2**63, True, np.int32(-5),
          np.uint32(2**32 - 1), np.int64(2**40 + 3), np.uint64(2**63 + 5)]


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("seed", _SEEDS, ids=[repr(s) for s in _SEEDS])
def test_request_key_is_jax_prngkey_bit_for_bit(seed, x64):
    """The host-made key is the array ``jax.random.PRNGKey`` gives: the
    key's bytes are the contract every sampled stream rests on."""
    from nnstreamer_tpu.models.serving import request_key

    with jax.enable_x64(x64):
        want = np.asarray(jax.random.PRNGKey(seed))
        got = request_key(seed)
    assert got.dtype == want.dtype == np.uint32 and got.shape == (2,)
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("bad", [2**63, -2**63 - 1, 1.5, (1, 2)])
def test_request_key_refuses_what_jax_refuses(bad):
    from nnstreamer_tpu.models.serving import request_key

    with pytest.raises((OverflowError, TypeError)):
        jax.random.PRNGKey(bad)
    with pytest.raises((OverflowError, TypeError)):
        request_key(bad)


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_sampled_streams_do_not_change_with_host_keys(params, layout,
                                                      monkeypatch):
    """Sampled streams keyed on the host (seeds given, large and negative,
    and the rid where none is given) are the streams the device-made key
    gave."""
    from nnstreamer_tpu.models import serving

    kw = _PAGED if layout == "paged" else {}
    seeds = [None, 0, 2**40 + 3, -7]

    def streams():
        b = _twin(params, **kw)
        rids = [b.submit(_prompt(6 + s, 530 + s), 8, temperature=0.9,
                         top_k=7, top_p=0.9,
                         **({} if sd is None else {"seed": sd}))
                for s, sd in enumerate(seeds)]
        _drain_pump(b, rids, 3)
        return _tokens(b, rids)

    host = streams()
    monkeypatch.setattr(
        serving, "request_key",
        lambda seed: np.asarray(jax.random.PRNGKey(seed)))
    assert host == streams()
    assert len({tuple(t) for t in host}) == len(seeds)  # they do sample


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_submit_returns_while_a_pump_is_in_flight(params, layout,
                                                  monkeypatch):
    """``submit`` makes no key on the device (``jax.random.PRNGKey`` raises
    here) and does not wait for the decode launch in flight: it returns
    with its rid while the pump's program is still running."""
    import threading

    b = _twin(params, **(_PAGED if layout == "paged" else {}))
    first = b.submit(_prompt(6, 540), 12)
    b.step_pump(2)
    entered, release = threading.Event(), threading.Event()
    launch = b._pump_greedy

    def slow_launch(*a, **k):
        entered.set()
        assert release.wait(60)
        return launch(*a, **k)

    def no_device_key(*a, **k):
        raise AssertionError("submit made its key on the device")

    monkeypatch.setattr(b, "_pump_greedy", slow_launch)
    monkeypatch.setattr(jax.random, "PRNGKey", no_device_key)
    got = {}
    pump = threading.Thread(target=b.step_pump, args=(2,))
    pump.start()
    try:
        assert entered.wait(60)
        sub = threading.Thread(
            target=lambda: got.update(rid=b.submit(_prompt(5, 541), 4,
                                                   temperature=0.7, seed=3)))
        sub.start()
        sub.join(60)
        assert not sub.is_alive() and got["rid"] is not None
        assert pump.is_alive()  # the launch it did not wait for
    finally:
        release.set()
        pump.join(60)
    _drain_pump(b, [first, got["rid"]], 2)


def test_llm_config_default_follows_the_queue():
    from nnstreamer_tpu import config

    assert config.Config().get_int("llm", "prefill_chunks", 1) == 0


def _longcat_twin(**kw):
    import jax.numpy as jnp

    from nnstreamer_tpu.models import longcat as lc

    cfg = lc.LongcatConfig(
        d_model=64, n_heads=4, q_rank=16, kv_rank=8, nope=8, rope=8, v_dim=8,
        d_ff=128, d_expert=32, n_routed=16, n_zero=8, topk=3, n_layers=2,
        vocab=97, n_held=4, expert_offset=4,
    )
    return ContinuousBatcher(
        lc.init_params(cfg, 3, jnp.float32), cfg.n_heads, n_slots=4,
        max_len=96, prompt_len=16, kv_layout="paged",
        family=lc.LongcatFamily(cfg, jnp.float32), **kw,
    )


@pytest.mark.parametrize("kind", ["slot", "paged", "paged-longcat"])
def test_step_is_a_pump_of_one(params, kind):
    """``step()`` is ``step_pump(1)`` for every family and layout: the same
    tokens, from the same program — no batcher holds a second per-token
    decode program beside its pump."""
    def make():
        if kind == "paged-longcat":
            return _longcat_twin()
        return _twin(params, **(_PAGED if kind == "paged" else {}))

    vocab = 97 if kind == "paged-longcat" else 257
    prompts = [_prompt(5 + s, 400 + s) % vocab for s in range(3)]
    a, b = make(), make()
    ra = [a.submit(p, 7) for p in prompts]
    rb = [b.submit(p, 7) for p in prompts]
    while any(a.result(r) is None for r in ra):
        got = a.step()
        assert all(isinstance(t, int) for t in got.values())
    _drain_pump(b, rb, 1)
    assert _tokens(a, ra) == _tokens(b, rb)
    assert not hasattr(a, "_step_greedy") and not hasattr(a, "_step_sampling")
    # step() ran the pump, and only at n_steps=1
    assert a._pump_greedy.func._cache_size() == 1
    assert a.stats()["steps"] == b.stats()["steps"]


@pytest.mark.parametrize(
    "program,builder",
    [("_pump", "make_pump"), ("_spec_round", "make_spec_round"),
     ("_spec_pump", "make_spec_pump")],
)
def test_each_decode_program_has_one_definition(params, program, builder):
    """The slot and the paged batcher get each kind of decode program from
    the same module-level builder, over their layout: the greedy and the
    sampling variant of both are that builder's ``impl``, jitted with the
    carried cache and the history donated."""
    from nnstreamer_tpu.models import serving

    slot, paged = _twin(params), _twin(params, **_PAGED)
    assert type(slot._layout) is not type(paged._layout)
    code = getattr(serving, builder).__code__
    for cb in (slot, paged):
        for variant in ("_greedy", "_sampling"):
            fn = getattr(cb, program + variant).func
            impl = fn.__wrapped__
            assert impl.__name__ == "impl"
            assert impl.__code__ in code.co_consts, (program, variant)
