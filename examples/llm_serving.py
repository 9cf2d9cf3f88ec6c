"""Continuous-batching LLM serving demo (models/serving.py).

Three requests of different lengths arrive at different times; the
batcher multiplexes them onto one fixed slot batch — two compiled XLA
programs total (prefill, batched step) for the server's whole life.
Greedy outputs are identical to serving each request alone.

Run: python examples/llm_serving.py    (CPU or TPU; small model)
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import numpy as np

from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.models.serving import ContinuousBatcher

params = tfm.init_params(
    jax.random.PRNGKey(0), vocab=1024, d_model=128, n_heads=8, n_layers=2
)
cb = ContinuousBatcher(params, n_heads=8, n_slots=4, max_len=128,
                       prompt_len=32)
rng = np.random.default_rng(0)

print("submit A (prompt 20 tokens, want 12)")
ra = cb.submit(rng.integers(1, 1024, (20,)), 12)
steps = 0
for _ in range(4):
    cb.step()
    steps += 1
print("submit B mid-flight (prompt 7 tokens, want 8)")
rb = cb.submit(rng.integers(1, 1024, (7,)), 8)
print("submit C (prompt 30 tokens, want 5)")
rc = cb.submit(rng.integers(1, 1024, (30,)), 5)

while any(cb.result(r) is None for r in (ra, rb, rc)):
    emitted = cb.step()
    steps += 1
    print(f"  step {steps}: {len(emitted)} active slots emitted")

for name, rid in (("A", ra), ("B", rb), ("C", rc)):
    print(f"{name}: {cb.result(rid)}")
print(f"free slots at end: {cb.n_free}/4")

# ---- the pumped form: same streams, a fraction of the host traffic ----
# step() pays one dispatch + one [B] readback PER TOKEN; step_pump(n)
# scans n steps in one program with ONE [B, n] readback, and
# spec_pump(rounds, k) runs whole speculative rounds on device with
# proposals mined there (device_ngram_propose). Each saved readback is
# a host↔device sync.
cb2 = ContinuousBatcher(params, n_heads=8, n_slots=4, max_len=128,
                        prompt_len=32)
rng = np.random.default_rng(0)
r2a = cb2.submit(rng.integers(1, 1024, (20,)), 12)
cb2.step_pump(4)
r2b = cb2.submit(rng.integers(1, 1024, (7,)), 8)
r2c = cb2.submit(rng.integers(1, 1024, (30,)), 5)
pumps = 0
while any(cb2.result(r) is None for r in (r2a, r2b, r2c)):
    out = cb2.step_pump(8)   # or cb2.spec_pump(rounds=2, k=4)
    pumps += 1
    total = sum(len(v) for v in out.values())
    print(f"  pump {pumps}: {total} tokens in one readback")
assert cb2.result(r2a) == cb.result(ra)  # pumped == per-token streams
assert cb2.result(r2b) == cb.result(rb)
assert cb2.result(r2c) == cb.result(rc)
print(f"pumped streams identical; host reads: {steps} per-token vs "
      f"{pumps + 1} pumped")

print("\n-- prefix caching: shared system prompt, prefilled once --")
system = rng.integers(1, 1024, (24,))
pid = cb.register_prefix(system)
rd = cb.submit(rng.integers(1, 1024, (6,)), 6, prefix=pid)
re_ = cb.submit(rng.integers(1, 1024, (9,)), 6, prefix=pid,
                temperature=0.8, seed=42)  # sampled, deterministic per seed
while cb.result(rd) is None or cb.result(re_) is None:
    cb.step()
print(f"D (greedy, shared prefix): {cb.result(rd)}")
print(f"E (sampled t=0.8, shared prefix): {cb.result(re_)}")
cb.unregister_prefix(pid)

print("\n-- sliding window: 200 tokens through a 64-slot ring --")
ring = ContinuousBatcher(params, n_heads=8, n_slots=1, max_len=64,
                         prompt_len=32, windowed=True)
rf = ring.submit(rng.integers(1, 1024, (20,)), 200)
while ring.result(rf) is None:
    ring.step()
print(f"F: {len(ring.result(rf))} tokens decoded in a fixed 64-token cache")

print("\n-- token streaming: partials() while slots decode --")
sb = ContinuousBatcher(params, n_heads=8, n_slots=2, max_len=96,
                       prompt_len=32)
rg = sb.submit(rng.integers(1, 1024, (12,)), 10)
seen = 0
while sb.result(rg) is None:
    sb.step()
    toks = sb.partials([rg]).get(rg, [])
    if len(toks) > seen:
        print(f"  streamed: +{toks[seen:]}")
        seen = len(toks)
print(f"G: {seen} tokens streamed as they decoded")

print("\n-- windowed long prompt: 150-token prompt into a 64 ring --")
wp = ContinuousBatcher(params, n_heads=8, n_slots=1, max_len=64,
                       prompt_len=32, windowed=True)
rh = wp.submit(rng.integers(1, 1024, (150,)), 8)
while wp.result(rh) is None:
    wp.step()
print(f"H: prompt 150 > ring 64 — exact sliding-window prefill, "
      f"{len(wp.result(rh))} tokens out")

print("\n-- speculative rounds: prompt-lookup, then a draft model --")
pattern = np.tile(np.asarray([5, 9, 13], np.int32), 6)
sp = ContinuousBatcher(params, n_heads=8, n_slots=2, max_len=128,
                       prompt_len=32)
ri = sp.submit(pattern, 16)
rj = sp.submit(rng.integers(1, 1024, (8,)), 8, temperature=0.7, seed=7)
while sp.result(ri) is None or sp.result(rj) is None:
    sp.spec_step(k=4, ngram=1)  # greedy exact; sampled distribution-exact
st = sp.stats()
print(f"I/J: {st['tokens_emitted']} tokens in {st['spec_rounds']} "
      f"verify rounds ({st['spec_accepted_tokens']} speculated tokens "
      "accepted)")

draft = tfm.init_params(
    jax.random.PRNGKey(9), vocab=1024, d_model=64, n_heads=4, n_layers=1
)
ds = ContinuousBatcher(params, n_heads=8, n_slots=2, max_len=128,
                       prompt_len=32, draft_params=draft, draft_n_heads=4)
rk = ds.submit(rng.integers(1, 1024, (10,)), 12)
while ds.result(rk) is None:
    ds.spec_step(k=4)
st = ds.stats()
print(f"K (draft model proposes): {st['tokens_emitted']} tokens, "
      f"{st['spec_accepted_tokens']} draft proposals accepted")

# ---- paged KV: block tables, prefix sharing, SLOs (nns-kv) ----
# kv_layout="paged" carves the cache into 16-token blocks behind
# per-request block tables (docs/llm-serving.md): requests hold only
# the blocks their tokens occupy, identical prompts share physical
# blocks through a rolling prefix hash, long prompts prefill in chunks
# interleaved with decode, and pool pressure preempts-and-re-prefills
# instead of OOMing. Decode is block-native: attention reads ride the
# block tables straight off the arena, each token writes in place into
# its owning block. Streams are bitwise the slot layout's.
print("\n-- paged KV cache: 12 requests in a 6-request HBM budget --")
pg = ContinuousBatcher(params, n_heads=8, n_slots=16, max_len=128,
                       prompt_len=32, kv_layout="paged", block_size=16,
                       kv_blocks=48)  # 48 blocks = 6 x max_len of HBM
system = rng.integers(1, 1024, (32,))  # shared system prompt: 2 blocks
rids = []
for i in range(12):
    user = rng.integers(1, 1024, (8,))
    rids.append(pg.submit(np.concatenate([system, user]), 10,
                          deadline_s=30.0))
while any(pg.result(r) is None for r in rids):
    pg.step_pump(8)
st = pg.stats()
print(f"L: {len(rids)} requests served in a {st['kv_blocks']}-block "
      f"arena; prefix hits {st['kv_prefix_hits']} "
      f"({st['kv_prefix_hit_tokens']} tokens never re-prefilled), "
      f"peak blocks in use ≤ {st['kv_blocks']}")
slo = pg.requests()
done = [v for v in slo.values() if v["state"] == "done"]
print(f"   SLO ledger: {len(done)} done, sample TTFT "
      f"{done[0]['ttft_ms']:.1f} ms, TPOT {done[0]['tpot_ms']:.2f} ms"
      if done else "")
