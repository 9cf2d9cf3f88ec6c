"""The block-family seam of ``ContinuousBatcher``'s paged path.

The paged batcher (models/serving.py) owns slots, block tables, the pool,
admission, pumps and harvest; what differs between model families is what
one token leaves in the cache and how a step computes. A family answers
exactly that:

``arena(n_blocks, block_size, quantized, n_slots)``
    the zeroed arena: a pair of BLOCK leaves (or int8 ``(payload, scale)``
    pairs) shaped ``[cache layers, n_blocks + 1, block_size, ...]`` — block 0
    is scratch; ``kv.gather``'s staging ops and ``write_fresh_window`` treat
    whatever follows the first three dims as one token's entry — then the
    family's ``slot_leaves`` SLOT leaves ``[state layers, n_slots + 1, ...]``:
    what a layer keeps per slot and not per token (a recurrent state), lane b
    in row b, the last row scratch. The whole tuple is the decode programs'
    carried, donated cache.
``stage(length)``
    the contiguous staging cache of chunked prefill, ``[cache layers, 1,
    length, ...]`` per block leaf, then ``[state layers, 1, ...]`` per slot
    leaf: the state a prompt has reached, carried from bucket to bucket.
``slot_leaves``
    how many slot leaves follow the block leaves (0: none, and the family's
    programs are what they were). For a slot leaf the batcher does three
    things. Landing a finalized job writes the stage's row to the slot's row
    (``kv.gather.make_staging_ops``). Preemption keeps nothing: blocks are
    freed, the row is overwritten when the job, re-prefilled, lands again.
    And since the state at a block boundary is stored nowhere, a prefix
    cannot be adopted: such a family lists ``prefix sharing`` in
    ``unsupported`` and its pool indexes no blocks, so nothing ever matches.
``prefill(params, tokens)`` / ``chunk(params, tokens, cpos, stage, return_logits)``
    one prompt bucket from position 0 / one bucket at ``cpos`` against the
    stage -> ``(logits, stage leaves, pos)``.
``prefill_packed(params, tokens, positions, segment, last)`` (optional)
    several bucket-sized prompts in one bucket, each starting on a block
    boundary (``models/decode.prefill_packed``) -> ``(logits [K, V] of the
    K = prompt_len // block_size possible prompts' last rows, stage
    leaves)``. A family that has it lets a pump that finds two or more such
    prompts queued share programs between them; one that has not (experts
    dispatched over mixed prompts, a state to reset where a prompt starts)
    gives every prompt a bucket of its own, always.
``decode_step(params, tok, pos, active, arena, tables, attn_fn)``
    one token for every live slot straight off the arena ->
    ``(logits, arena, pos', aux)``; ``aux`` is an int32 vector of
    ``aux_names`` counters the pump sums over its steps and carries home in
    the readback it already makes (None where ``aux_names`` is empty);
    ``stats()`` reports their totals under ``aux_prefix + name``.
``decode_kernel`` / ``make_attention()``
    the registered name of the family's block-table decode kernel (None: the
    XLA formulation only) and its factory.
``pad_id``
    what pads a prompt bucket (a family whose layers must tell padding from
    tokens pads with -1).
``unsupported``
    the batcher features the family does not carry; asking for one refuses at
    construction by name.

``DenseFamily`` is the pre-norm RMSNorm / RoPE / GQA / SwiGLU block of
models/transformer.py: it calls the functions the paged path always called,
so its programs and numbers are what they were.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from nnstreamer_tpu.kv import block_attn as kvb
from nnstreamer_tpu.kv import gather as kvg
from nnstreamer_tpu.models import decode as dec
from nnstreamer_tpu.models import transformer as tfm


class DenseFamily:
    name = "dense"
    pad_id = 0
    slot_leaves = 0
    aux_names: Tuple[str, ...] = ()
    aux_prefix = ""
    unsupported: Tuple[str, ...] = ()
    decode_kernel = "paged_decode_attention"

    def __init__(self, params, n_heads: int, prompt_len: int, compute_dtype):
        self.n_heads = n_heads
        self.prompt_len = prompt_len
        self.compute_dtype = self.dtype = compute_dtype   # the arena's dtype
        self.n_layers, d = params["blocks"]["ln1"].shape
        self.head_dim = d // n_heads
        self.n_kv_heads = tfm.n_kv_heads_of(params["blocks"]["wqkv"], d, n_heads)

    def arena(self, n_blocks: int, block_size: int, quantized: bool = False,
              n_slots: int = 0):
        return kvg.init_arena(self.n_layers, n_blocks, block_size, self.n_kv_heads,
                              self.head_dim, quantized, self.compute_dtype)

    def stage(self, length: int):
        shape = (self.n_layers, 1, length, self.n_kv_heads, self.head_dim)
        return (jnp.zeros(shape, self.compute_dtype),
                jnp.zeros(shape, self.compute_dtype))

    def prefill(self, params, tokens):
        return dec.prefill(params, tokens, self.n_heads, self.prompt_len,
                           compute_dtype=self.compute_dtype)

    def prefill_packed(self, params, tokens, positions, segment, last):
        return dec.prefill_packed(params, tokens, positions, segment, last,
                                  self.n_heads,
                                  compute_dtype=self.compute_dtype)

    def chunk(self, params, tokens, cpos, stage, return_logits: bool = True):
        return dec.verify_chunk(params, tokens, cpos, stage, self.n_heads,
                                compute_dtype=self.compute_dtype,
                                return_logits=return_logits)

    def decode_step(self, params, tok, pos, active, arena, tables, attn_fn=None):
        return kvb.batched_decode_step_block(
            params, tok, pos, active, arena, tables, self.n_heads,
            self.compute_dtype, attn_fn=attn_fn) + (None,)

    def make_attention(self):
        from nnstreamer_tpu.ops.pallas.paged_attention import make_paged_attention

        return make_paged_attention()


def refuse_unsupported(family, asked: dict) -> None:
    """``asked``: feature name -> whether the caller asked for it. Raises
    ValueError naming the first one the family does not carry."""
    for feature, on in asked.items():
        if on and feature in family.unsupported:
            raise ValueError(
                f"the {family.name} block family does not support {feature} yet "
                f"(unsupported: {', '.join(family.unsupported)})"
            )
