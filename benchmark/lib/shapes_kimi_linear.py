"""Operations and bytes of the Kimi-Linear configuration (Kimi Delta Attention
layers with a per-slot recurrent state beside latent attention layers, a
leading dense layer, one chip's share of sigmoid-routed experts and a shared
expert), from its shapes and the program's counters alone, whatever implements
the step. The ``*.hybrid`` readers and ``kda_*`` read these functions;
``shapes.py`` counts the dense block and is not used for this configuration.

``s`` is the configuration's file reduced by ``shape_of`` (the file under
``benchmark/configs/`` that names the reference ``kimi_linear`` and has the
run's sizes).
"""

from __future__ import annotations

STATE_BYTES = 4  # the recurrent state is float32 in every precision


def shape_of(sizes: dict):
    """The configuration as its reference reads it (``reference/kimi_linear.py``
    ``shape_of``) with the bytes the run's ``sizes`` state, or None where no
    such file exists (another configuration's run)."""
    from benchmark.lib import check

    try:
        shape = check.load_reference("kimi_linear").shape_of(sizes)
    except SystemExit:
        return None
    n_mla = len(shape["mla_layers"])
    return {**shape, "n_mla": n_mla, "n_kda": shape["n_layers"] - n_mla,
            "n_expert_layers": shape["n_layers"] - shape["n_dense"],
            "bytes_per_weight": sizes["bytes_per_weight"],
            "bytes_per_kv": sizes["bytes_per_kv"]}


def kda_matmul_params(s: dict) -> int:
    """One KDA attention's matrices: W_q, W_k, W_v, W_o, the two low-rank
    gates, W_beta."""
    d, c, r = s["d"], s["kda_heads"] * s["kda_dim"], s["gate_rank"]
    return 3 * d * c + c * d + 2 * (d * r + r * c) + d * s["kda_heads"]


def kda_params(s: dict) -> int:
    """... and its three depthwise convolutions, A_log, dt_bias, the head norm."""
    c = s["kda_heads"] * s["kda_dim"]
    return kda_matmul_params(s) + 3 * s["conv"] * c + s["kda_heads"] + c + s["kda_dim"]


def mla_matmul_params(s: dict) -> int:
    """One latent attention: W_q, W_kva, W_kvb, W_o."""
    h, d = s["heads"], s["d"]
    return (d * h * (s["nope"] + s["rope"]) + d * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * h * (s["nope"] + s["v_dim"]) + h * s["v_dim"] * d)


def dense_ffn_params(s: dict) -> int:
    return 3 * s["d"] * s["d_ff"]


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["d_expert"]


def head_params(s: dict) -> int:
    return s["d"] * s["vocab"]


def matmul_params_outside_routed_experts(s: dict) -> int:
    """What every token meets in the layers here: every attention, the dense
    FFNs, and in each expert layer the router and the shared expert."""
    return (s["n_kda"] * kda_matmul_params(s) + s["n_mla"] * mla_matmul_params(s)
            + s["n_dense"] * dense_ffn_params(s)
            + s["n_expert_layers"] * (s["d"] * s["n_routed"] + expert_params(s)))


def params_total(s: dict) -> int:
    """Every parameter held on the device, the layer norms left out (2 d a
    layer and d at the end): what ISSUE 33 counts."""
    return (s["n_kda"] * kda_params(s)
            + s["n_mla"] * (mla_matmul_params(s) + s["kv_rank"])
            + s["n_dense"] * dense_ffn_params(s)
            + s["n_expert_layers"] * (s["d"] * s["n_routed"]
                                      + (s["n_held"] + 1) * expert_params(s))
            + 2 * head_params(s))


def state_values_per_slot_layer(s: dict) -> int:
    """The recurrent state of one slot in one KDA layer: H matrices d_k x d_v."""
    return s["kda_heads"] * s["kda_dim"] * s["kda_dim"]


def latent_values_per_token(s: dict) -> int:
    """Cached values of one token: (c, k_r) in each MLA layer."""
    return s["n_mla"] * (s["kv_rank"] + s["rope"])


def recurrence_flops_per_update(s: dict) -> float:
    """One token through one KDA layer's recurrence: per state element the
    decay, k^T S, the rank-1 update and S^T q (1 + 2 + 2 + 2)."""
    return 7.0 * state_values_per_slot_layer(s)


def decode_attention_flops_per_cached_token(s: dict) -> float:
    """One query token against one cached token, one MLA layer, absorbed: the
    score over (kv_rank + rope) and the weighted sum over kv_rank, every head."""
    return 2.0 * s["heads"] * (2 * s["kv_rank"] + s["rope"])


def window_flops(s: dict, prompt_tokens: float, out_tokens: float,
                 pairs_per_token_layer: float, cached_token_reads: float) -> float:
    """Model FLOPs of a window: every prompt and output token's matmuls (the
    local expert pairs from the counter) and recurrence, the head once per
    output token, and the decode attention over ``cached_token_reads`` = sum
    over decode steps of the live cached tokens. Prefill attention in the MLA
    layers and a prompt's one head are left out, so the share reads low, never
    high."""
    per_token = (2.0 * (matmul_params_outside_routed_experts(s)
                        + s["n_expert_layers"] * pairs_per_token_layer * expert_params(s))
                 + s["n_kda"] * recurrence_flops_per_update(s))
    return ((prompt_tokens + out_tokens) * per_token
            + out_tokens * 2.0 * head_params(s)
            + s["n_mla"] * decode_attention_flops_per_cached_token(s) * cached_token_reads)


def decode_step_bytes(s: dict, live_kv_tokens: float, experts_hit_per_step: float,
                      state_updates_per_step: float) -> dict:
    """Least bytes one decode step must move, by kind: the weights outside the
    routed experts and the head once, the experts that were HIT (summed over
    layers), the latents of the live tokens, and the state of the live lanes
    once read and once written."""
    return {
        "weights": (matmul_params_outside_routed_experts(s) + head_params(s)
                    + experts_hit_per_step * expert_params(s)) * s["bytes_per_weight"],
        "latents": live_kv_tokens * latent_values_per_token(s) * s["bytes_per_kv"],
        "state": (state_updates_per_step * 2 * state_values_per_slot_layer(s)
                  * STATE_BYTES),
    }


def kda_decode_cost(s: dict, state_updates_per_step: float) -> tuple:
    """(FLOPs, bytes) of one step's recurrence over all KDA layers."""
    return (state_updates_per_step * recurrence_flops_per_update(s),
            state_updates_per_step * 2 * state_values_per_slot_layer(s) * STATE_BYTES)


def counters(ctx: dict):
    """The ``nns.moe.routing`` and ``nns.state.update`` instants of the traced
    window summed, or None where the program writes none (another family, or
    the parent commit)."""
    from benchmark.lib import host_spans

    routing = host_spans.spans(ctx, ("nns.moe.routing",))
    updates = host_spans.spans(ctx, ("nns.state.update",))
    if not routing or not updates:
        return None
    out = {k: float(sum(e["stats"].get(k, 0) for e in routing))
           for k in ("tokens", "local_pairs", "experts_hit", "picks")}
    out["state_updates"] = float(sum(e["stats"].get("slot_layers", 0) for e in updates))
    out["steps"] = len(routing) * ctx["pump"]
    return out


def step_bytes(ctx: dict):
    """``decode_step_bytes`` of the traced window's mean step, or None."""
    s = shape_of(ctx["sizes"])
    c = counters(ctx) if s else None
    if not c or not c["steps"]:
        return None
    return decode_step_bytes(s, ctx["live_kv_tokens"], c["experts_hit"] / c["steps"],
                             c["state_updates"] / c["steps"])
