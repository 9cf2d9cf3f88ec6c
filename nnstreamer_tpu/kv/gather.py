"""The paged cache's admission ops: what moves K/V between a contiguous
prefill stage and the block arena, and the arena itself.

- :func:`init_arena` — the zeroed arena tree ``[L, N + 1, bs, KV, Dh]``
  per leaf (block 0 is scratch), float or int8 ``(payload, scale)``;
- :func:`quantize_kv` / :func:`dequantize_kv` — the int8 cache entry
  (per-token-per-head symmetric scales), shared by every layout so slot
  and paged int8 payloads are bitwise identical;
- :func:`make_paged_ops` — one block at a time: stage→block write
  (quantizing when the arena is int8, exactly like the slot layout's
  insert_slot), block→stage read for prefix-seeded prefill, and the
  device side of copy-on-write;
- :func:`make_staging_ops` — the same two directions for a whole stage
  in one program each.

Decode never comes through here: it reads and writes the arena in place
through the block tables (:mod:`nnstreamer_tpu.kv.block_attn`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_kv(t):
    """[..., H, Dh] float → (int8 same shape, f32 scale [..., H]).
    Per-token-per-head symmetric scales keep the error tight without
    storing more than 1/Dh extra floats — the cache shrinks 4× vs f32
    (2× vs bf16), which is more live slots or longer contexts per chip."""
    m = jnp.maximum(jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1), 1e-8)
    scale = m / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale[..., None]


def make_paged_ops(quantized: bool, compute_dtype):
    """Admission-path jitted ops over one arena layout.

    Returns ``(write_block, read_block, copy_block)``:

    - ``write_block(arena, blk, ks, vs)`` — land one block of staged
      K/V (``[L, 1, bs, KV, Dh]`` compute dtype) at arena block ``blk``,
      quantizing per token per head when the arena is int8 (the same
      quantize_kv the slot layout's insert_slot applies, so paged and
      slot int8 payloads are bitwise identical);
    - ``read_block(arena, blk)`` — one block back as compute-dtype
      ``(ks, vs)`` (dequantized when int8): the prefix-seeded prefill
      stage source;
    - ``copy_block(arena, src, dst)`` — the device half of
      copy-on-write.
    """

    def write_block(arena, blk, ks, vs):
        if quantized:
            (ka, ksc), (va, vsc) = arena
            k8, ks_ = quantize_kv(ks)
            v8, vs_ = quantize_kv(vs)
            return (
                (ka.at[:, blk].set(k8[:, 0]), ksc.at[:, blk].set(ks_[:, 0])),
                (va.at[:, blk].set(v8[:, 0]), vsc.at[:, blk].set(vs_[:, 0])),
            )
        ka, va = arena
        return (
            ka.at[:, blk].set(ks[:, 0].astype(ka.dtype)),
            va.at[:, blk].set(vs[:, 0].astype(va.dtype)),
        )

    def read_block(arena, blk):
        if quantized:
            (ka, ksc), (va, vsc) = arena
            ks = dequantize_kv(ka[:, blk], ksc[:, blk])
            vs = dequantize_kv(va[:, blk], vsc[:, blk])
        else:
            ka, va = arena
            ks, vs = ka[:, blk], va[:, blk]
        return (
            ks.astype(compute_dtype)[:, None],
            vs.astype(compute_dtype)[:, None],
        )

    def copy_block(arena, src, dst):
        return jax.tree_util.tree_map(
            lambda a: a.at[:, dst].set(a[:, src]), arena
        )

    return (
        jax.jit(write_block, donate_argnums=0),
        jax.jit(read_block),
        jax.jit(copy_block, donate_argnums=0),
    )


def make_staging_ops(quantized: bool, compute_dtype):
    """Coalesced admission staging: ONE program per direction instead
    of one :func:`make_paged_ops` call per block.

    Returns ``(seed_stage, land_stage)`` over a chunked-prefill stage;
    the stage's block count rides in ``ids.shape[0]`` (the caller
    passes one id slot per stage block — a bucket-wide fast-path stage
    and the full chunked stage each compile once):

    - ``seed_stage(arena, stage, ids, n_seed)`` — read arena blocks
      ``ids[:n_seed]`` (dequantized when int8) into the stage's leading
      columns in one launch: the prefix-seeded prefill source
      (replaces a ``read_block`` + two dynamic-update launches per
      matched block);
    - ``land_stage(arena, stage, ids, valid, slot)`` — write every stage
      block ``i`` with ``valid[i]`` to arena block ``ids[i]``
      (quantizing when int8 — per token per head, so slicing per block
      first would change nothing) in one launch; invalid lanes route
      to scratch block 0 carrying its init values (zero payload, unit
      scales), so scratch stays pristine. Replaces a ``write_block``
      launch per landed block.

    Values are bitwise the per-block ops' — only the dispatch count
    changes (the paged admission path used to cost ~2 launches per
    block of prompt, a real tax on the `bench.py --pipeline llm`
    equal-occupancy cell).

    A family's SLOT leaves (models/family.py: per-slot state that no
    block holds) follow the two block leaves in both trees. They are not
    in any block: ``seed_stage`` hands the stage's back as they are, and
    ``land_stage`` takes the lane ``slot`` and writes each one's single
    staged row ``[layers, 1, ...]`` to row ``slot`` of its arena leaf
    (unused, and pruned from the program, where there are none)."""

    def seed_stage(arena, stage, ids, n_seed):
        S = ids.shape[0]
        if quantized:
            (ka, ksc), (va, vsc) = arena

            def taken(pay, sc):
                t = jnp.take(pay, ids, axis=1)   # [L, S, bs, KV, Dh]
                s = jnp.take(sc, ids, axis=1)    # [L, S, bs, KV]
                return dequantize_kv(t, s)
            tk, tv = taken(ka, ksc), taken(va, vsc)
        else:
            ka, va = arena[:2]
            tk = jnp.take(ka, ids, axis=1)
            tv = jnp.take(va, ids, axis=1)
        bs = tk.shape[2]

        def place(t, sleaf):
            flat = t.reshape(
                (t.shape[0], 1, S * bs) + t.shape[3:]
            ).astype(sleaf.dtype)
            cols = jnp.arange(S * bs, dtype=jnp.int32)
            keep = (cols < n_seed * bs).reshape(
                (1, 1, S * bs) + (1,) * (sleaf.ndim - 3)
            )
            return jnp.where(keep, flat, sleaf)

        return (place(tk, stage[0]), place(tv, stage[1])) + tuple(stage[2:])

    def land_stage(arena, stage, ids, valid, slot):
        S = ids.shape[0]
        ks, vs = stage[:2]  # [L, 1, S*bs, KV, Dh] compute dtype

        def rows_of(s):
            return s.reshape((s.shape[0], S, -1) + s.shape[3:])

        def put(a, rows, fill=0):
            keep = valid.reshape((1, S) + (1,) * (rows.ndim - 2))
            rows = jnp.where(keep, rows.astype(a.dtype),
                             jnp.asarray(fill, a.dtype))
            return a.at[:, ids].set(rows)

        if quantized:
            (ka, ksc), (va, vsc) = arena
            k8, ksn = quantize_kv(ks)
            v8, vsn = quantize_kv(vs)
            return (
                (put(ka, rows_of(k8)), put(ksc, rows_of(ksn), 1.0)),
                (put(va, rows_of(v8)), put(vsc, rows_of(vsn), 1.0)),
            )
        ka, va = arena[:2]
        return (put(ka, rows_of(ks)), put(va, rows_of(vs))) + tuple(
            a.at[:, slot].set(s[:, 0].astype(a.dtype))
            for a, s in zip(arena[2:], stage[2:])
        )

    return (
        jax.jit(seed_stage, donate_argnums=1),
        jax.jit(land_stage, donate_argnums=0),
    )


def init_arena(n_layers: int, n_blocks: int, block_size: int, kv: int,
               hd: int, quantized: bool, compute_dtype):
    """Zeroed arena tree (+1 scratch block at index 0), mirroring the
    slot cache's init values: int8 payloads zero with unit scales, fp
    zeros — so scratch/unwritten columns are finite and masked columns
    contribute exact zeros either way."""
    shape = (n_layers, n_blocks + 1, block_size, kv, hd)
    if quantized:
        sshape = shape[:-1]
        return (
            (jnp.zeros(shape, jnp.int8), jnp.ones(sshape, jnp.float32)),
            (jnp.zeros(shape, jnp.int8), jnp.ones(sshape, jnp.float32)),
        )
    return (
        jnp.zeros(shape, compute_dtype),
        jnp.zeros(shape, compute_dtype),
    )
