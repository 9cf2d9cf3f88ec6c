"""Operations and bytes of the LongCat-Flash configuration (latent attention,
the shortcut-connected double layer, one chip's share of the routed experts),
from its shapes and the router's counters alone, whatever implements the step.
The ``*.moe`` readers and the two kernel rooflines read these functions;
``shapes.py`` counts the dense block and is not used for this configuration.

``s`` is the configuration's file reduced by ``shape_of`` (the file under
``benchmark/configs/`` that names the reference ``longcat_flash`` and has the
run's sizes).
"""

from __future__ import annotations


def shape_of(sizes: dict):
    """The configuration as its reference reads it (``reference/longcat_flash.py``
    ``shape_of``: the file that names that reference and has these sizes) with
    the bytes the run's ``sizes`` state, or None where no such file exists."""
    from benchmark.lib import check

    try:
        shape = check.load_reference("longcat_flash").shape_of(sizes)
    except SystemExit:
        return None
    return {**shape, "n_out": shape["n_routed"] + shape["n_zero"],
            "bytes_per_weight": sizes["bytes_per_weight"],
            "bytes_per_kv": sizes["bytes_per_kv"]}


def attention_params(s: dict) -> int:
    """One latent attention: W_qa, W_qb, W_kva, W_kvb, W_o."""
    h, d = s["heads"], s["d"]
    return (d * s["q_rank"] + s["q_rank"] * h * (s["nope"] + s["rope"])
            + d * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * h * (s["nope"] + s["v_dim"]) + h * s["v_dim"] * d)


def dense_ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["d_ff"]


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["d_expert"]


def layer_params_outside_experts(s: dict) -> int:
    """Two attentions, two dense FFNs and the router: what every token meets."""
    return 2 * attention_params(s) + 2 * dense_ffn_params(s) + s["d"] * s["n_out"]


def head_params(s: dict) -> int:
    return s["d"] * s["vocab"]


def latent_values_per_token(s: dict) -> int:
    """Cached values of one token: (c, k_r) in each of the 2L attentions."""
    return 2 * s["n_layers"] * (s["kv_rank"] + s["rope"])


def decode_attention_flops_per_cached_token(s: dict) -> float:
    """One query token against one cached token, one attention, absorbed:
    the score over (kv_rank + rope) and the weighted sum over kv_rank, for
    every head, two FLOPs a multiply-add."""
    return 2.0 * s["heads"] * (2 * s["kv_rank"] + s["rope"])


def token_matmul_flops(s: dict, pairs_per_token_layer: float, with_head: bool) -> float:
    """Matmul FLOPs of one token here: the parameters it meets outside the
    experts in every layer, its local expert pairs, and the head where its
    logits are needed."""
    f = 2.0 * s["n_layers"] * (layer_params_outside_experts(s)
                               + pairs_per_token_layer * expert_params(s))
    return f + (2.0 * head_params(s) if with_head else 0.0)


def window_flops(s: dict, prompt_tokens: float, n_prompts_heads: float,
                 out_tokens: float, pairs_per_token_layer: float,
                 cached_token_reads: float) -> float:
    """Model FLOPs of a window: every prompt and output token's matmuls, the
    head once per output token and once per prompt (``n_prompts_heads``), and
    the decode attention over ``cached_token_reads`` = sum over decode steps
    of the live cached tokens. Prefill attention (under 2 % of a prompt
    token's FLOPs at 512) is left out, so the share reads low, never high."""
    body = token_matmul_flops(s, pairs_per_token_layer, False)
    return ((prompt_tokens + out_tokens) * body
            + (out_tokens + n_prompts_heads) * 2.0 * head_params(s)
            + 2 * s["n_layers"] * decode_attention_flops_per_cached_token(s)
            * cached_token_reads)


def decode_step_bytes(s: dict, live_kv_tokens: float, experts_hit_per_step: float) -> float:
    """Least bytes one decode step must read: attention, dense FFN and router
    weights of every layer and the head, once; the experts that were HIT this
    step (summed over layers); the latent cache of the live tokens."""
    weights = (s["n_layers"] * layer_params_outside_experts(s) + head_params(s)
               + experts_hit_per_step * expert_params(s)) * s["bytes_per_weight"]
    return weights + live_kv_tokens * latent_values_per_token(s) * s["bytes_per_kv"]


def mla_decode_attention_cost(s: dict, live_kv_tokens: float) -> tuple:
    """(FLOPs, bytes) of the decode attention reads of ONE step over all 2L
    attentions: every live cached token once."""
    flops = (2 * s["n_layers"] * decode_attention_flops_per_cached_token(s)
             * live_kv_tokens)
    return flops, live_kv_tokens * latent_values_per_token(s) * s["bytes_per_kv"]


def routing(ctx: dict):
    """The ``nns.moe.routing`` instants of the traced window summed, or None
    where the program writes none (another family, or the parent commit)."""
    from benchmark.lib import host_spans

    events = host_spans.spans(ctx, ("nns.moe.routing",))
    if not events:
        return None
    keys = ("tokens", "local_pairs", "experts_hit", "zero_picks", "picks")
    out = {k: float(sum(e["stats"].get(k, 0) for e in events)) for k in keys}
    out["pumps"] = len(events)
    return out
