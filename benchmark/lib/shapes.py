"""Operations and bytes of the model a configuration states, from its
shapes alone. ``step_mfu_pct``, ``prefill_mfu_pct`` and
``decode_hbm_roofline_pct`` all read these functions, whatever implements
the step."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sizes_of(config: dict) -> dict:
    """The widths and depth the arithmetic and the reference need, under
    short names, from a configuration file's published keys."""
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    return {
        "d_model": d,
        "n_heads": heads,
        "n_kv_heads": config.get("num_key_value_heads", heads),
        "head_dim": d // heads,
        "n_layers": config["num_hidden_layers"],
        "d_ff": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "bytes_per_weight": config["served"]["bytes_per_weight"],
        "bytes_per_kv": config["served"]["bytes_per_kv"],
    }


def block_params(s: dict) -> int:
    """Matmul weights of one block (norm vectors left out: 2·d)."""
    d, kvd = s["d_model"], s["n_kv_heads"] * s["head_dim"]
    return d * (d + 2 * kvd) + d * d + 3 * d * s["d_ff"]


def head_params(s: dict) -> int:
    return s["d_model"] * s["vocab"]


def params_total(s: dict) -> int:
    """Every parameter held on the device: blocks, norms, the embedding
    table and the untied head."""
    d, L = s["d_model"], s["n_layers"]
    return L * (block_params(s) + 2 * d) + d + 2 * head_params(s)


def kv_bytes_per_token(s: dict) -> int:
    return 2 * s["n_kv_heads"] * s["head_dim"] * s["n_layers"] * s["bytes_per_kv"]


def flops_token(s: dict, context: float, with_head: bool) -> float:
    """Model FLOPs of one token that attends ``context`` positions: two per
    multiply-add of every block matmul, QK^T and PV over the context, and
    the output head where its logits are needed."""
    attn = 4.0 * s["n_heads"] * s["head_dim"] * context
    f = s["n_layers"] * (2.0 * block_params(s) + attn)
    return f + (2.0 * head_params(s) if with_head else 0.0)


def flops_prompt(s: dict, n: int) -> float:
    """A prompt of n tokens: causal, so token i attends i+1 positions; the
    head runs once, on the last position."""
    attn = 4.0 * s["n_heads"] * s["head_dim"] * (n * (n + 1) / 2.0)
    return s["n_layers"] * (2.0 * block_params(s) * n + attn) + 2.0 * head_params(s)


def decode_step_bytes(s: dict, live_kv_tokens: float) -> float:
    """Least bytes one decode step must read: every weight but the input
    embedding table (a row gather), once, and the keys and values of the
    tokens that are live, at the bytes each the configuration's ``served``
    block states for the storage they are streamed from."""
    weights = (s["n_layers"] * (block_params(s) + 2 * s["d_model"])
               + s["d_model"] + head_params(s)) * s["bytes_per_weight"]
    return weights + live_kv_tokens * kv_bytes_per_token(s)


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"benchmark/peaks.json has no entry for device kind {device_kind!r}; "
            "an unknown device is an error, not a default"
        )
    return table[device_kind]
