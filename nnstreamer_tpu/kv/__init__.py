"""nns-kv: paged KV-cache management for continuous-batching LLM serving.

The slot-layout :class:`~nnstreamer_tpu.models.serving.ContinuousBatcher`
allocates one contiguous ``[L, B, max_len, KV, Dh]`` cache sized for the
worst-case request: HBM for short requests is wasted, shared system
prompts re-prefill per request, and a long prefill stalls every decoding
slot. This package is the paged alternative behind
``ContinuousBatcher(kv_layout="paged")`` (docs/llm-serving.md):

- :mod:`blocks` — BlockPool: fixed-size token blocks carved from one
  device-resident arena per layer, ref-counted with copy-on-write, and a
  rolling-prefix-hash index so requests sharing a token prefix share
  physical blocks;
- :mod:`block_attn` — the block-native decode/verify formulation:
  attention reads ride the block table straight off the arena, token
  writes land in place in their owning block — no contiguous view in
  either direction. The slot layout is the oracle: streams are bitwise
  identical to it (tests/test_kv_block_attn.py, tests/test_kv_paged.py);
- :mod:`gather` — the admission-path block ops (stage↔arena), the arena
  itself and the int8 cache entry;
- :mod:`sched` — chunked-prefill admission jobs, watermark block
  accounting with preemption-by-eviction, and the per-request SLO
  ledger (queue/prefill/TTFT/TPOT → nns-obs).
"""

from nnstreamer_tpu.kv.blocks import BlockPool, NoBlocksError
from nnstreamer_tpu.kv.sched import PrefillJob, SLOLedger, SLORecord

__all__ = [
    "BlockPool",
    "NoBlocksError",
    "PrefillJob",
    "SLOLedger",
    "SLORecord",
]
