"""Operations and bytes of the Granite-4.0-H configuration (Mamba-2 layers with
a per-slot state beside grouped-query attention layers, every layer followed
by one chip's share of softmax-routed experts and a shared MLP, a tied head),
from its shapes and the program's counters alone, whatever implements the
step. The ``*.ssm`` readers and ``ssm_*`` read these functions; ``shapes.py``
counts the dense block and is not used for this configuration.

``s`` is the configuration's file reduced by ``shape_of`` (the file under
``benchmark/configs/`` that names the reference ``granite_hybrid`` and has the
run's sizes).
"""

from __future__ import annotations

# the ``nns.moe.routing`` / ``nns.state.update`` instants of the traced window
# summed (None where the program writes none): the same instants, the same sum
from benchmark.lib.shapes_kimi_linear import counters  # noqa: F401

STATE_BYTES = 4  # the state is float32 in every precision


def shape_of(sizes: dict):
    """The configuration as its reference reads it
    (``reference/granite_hybrid.py`` ``shape_of``) with the bytes the run's
    ``sizes`` state, or None where no such file exists (another
    configuration's run)."""
    from benchmark.lib import check

    try:
        shape = check.load_reference("granite_hybrid").shape_of(sizes)
    except SystemExit:
        return None
    n_attn = len(shape["attn_layers"])
    return {**shape, "n_attn": n_attn, "n_ssm": shape["n_layers"] - n_attn,
            "bytes_per_weight": sizes["bytes_per_weight"],
            "bytes_per_kv": sizes["bytes_per_kv"]}


def ssm_inner(s: dict) -> int:
    return s["ssm_heads"] * s["ssm_head_dim"]


def conv_width(s: dict) -> int:
    """Channels of the convolution: x~, B and C side by side (one group)."""
    return ssm_inner(s) + 2 * s["ssm_state"]


def ssm_matmul_params(s: dict) -> int:
    """One Mamba-2 mixer's matrices: W_in (z, xBC, dt) and W_out."""
    return s["d"] * (ssm_inner(s) + conv_width(s) + s["ssm_heads"]) + ssm_inner(s) * s["d"]


def ssm_params(s: dict) -> int:
    """... and its convolution with bias, A_log, dt_bias, D, the gated norm."""
    return (ssm_matmul_params(s) + (s["conv"] + 1) * conv_width(s)
            + 3 * s["ssm_heads"] + ssm_inner(s))


def attn_matmul_params(s: dict) -> int:
    """One attention: W_q, W_k, W_v, W_o."""
    return 2 * s["d"] * s["head_dim"] * (s["heads"] + s["kv_heads"])


def shared_params(s: dict) -> int:
    return 3 * s["d"] * s["d_shared"]


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["d_expert"]


def head_params(s: dict) -> int:
    """The tied embedding, read once a step as the head."""
    return s["d"] * s["vocab"]


def matmul_params_outside_routed_experts(s: dict) -> int:
    """What every token meets in the layers here: every mixer, and in each
    layer the router and the shared MLP."""
    return (s["n_ssm"] * ssm_matmul_params(s) + s["n_attn"] * attn_matmul_params(s)
            + s["n_layers"] * (s["d"] * s["n_routed"] + shared_params(s)))


def params_total(s: dict) -> int:
    """Every parameter held on the device: what ISSUE 36 counts (two norms a
    layer and the final one included; the embedding, which is the head, once)."""
    return (s["n_ssm"] * ssm_params(s) + s["n_attn"] * attn_matmul_params(s)
            + s["n_layers"] * (s["d"] * s["n_routed"] + shared_params(s)
                               + s["n_held"] * expert_params(s) + 2 * s["d"])
            + head_params(s) + s["d"])


def state_values_per_slot_layer(s: dict) -> int:
    """The state of one slot in one Mamba-2 layer: H matrices P x N."""
    return s["ssm_heads"] * s["ssm_head_dim"] * s["ssm_state"]


def kv_values_per_token(s: dict) -> int:
    """Cached values of one token: k and v in each attention layer."""
    return s["n_attn"] * 2 * s["kv_heads"] * s["head_dim"]


def scan_flops_per_update(s: dict) -> float:
    """One token through one Mamba-2 layer's scan: per state element the
    decay, the rank-1 update and S C (1 + 2 + 2); the convolution's taps."""
    return 5.0 * state_values_per_slot_layer(s) + 2.0 * s["conv"] * conv_width(s)


def decode_attention_flops_per_cached_token(s: dict) -> float:
    """One query token against one cached token, one attention layer: the
    score and the weighted sum over head_dim, every query head."""
    return 4.0 * s["heads"] * s["head_dim"]


def window_flops(s: dict, prompt_tokens: float, out_tokens: float,
                 pairs_per_token_layer: float, cached_token_reads: float) -> float:
    """Model FLOPs of a window: every prompt and output token's matmuls (the
    local expert pairs from the counter) and scan, the head once per output
    token, and the decode attention over ``cached_token_reads`` = sum over
    decode steps of the live cached tokens. Prefill attention in the one
    attention layer and a prompt's one head are left out, so the share reads
    low, never high."""
    per_token = (2.0 * (matmul_params_outside_routed_experts(s)
                        + s["n_layers"] * pairs_per_token_layer * expert_params(s))
                 + s["n_ssm"] * scan_flops_per_update(s))
    return ((prompt_tokens + out_tokens) * per_token
            + out_tokens * 2.0 * head_params(s)
            + s["n_attn"] * decode_attention_flops_per_cached_token(s) * cached_token_reads)


def decode_step_bytes(s: dict, live_kv_tokens: float, experts_hit_per_step: float,
                      state_updates_per_step: float) -> dict:
    """Least bytes one decode step must move, by kind: the weights outside the
    routed experts and the head once, the experts that were HIT (summed over
    layers), the keys and values of the live tokens, and the state of the
    live lanes once read and once written."""
    return {
        "weights": (matmul_params_outside_routed_experts(s) + head_params(s)
                    + experts_hit_per_step * expert_params(s)) * s["bytes_per_weight"],
        "kv": live_kv_tokens * kv_values_per_token(s) * s["bytes_per_kv"],
        "state": (state_updates_per_step * 2 * state_values_per_slot_layer(s)
                  * STATE_BYTES),
    }


def ssm_decode_cost(s: dict, state_updates_per_step: float) -> tuple:
    """(FLOPs, bytes) of one step's scan over all Mamba-2 layers."""
    values = state_values_per_slot_layer(s)
    return (state_updates_per_step * 5.0 * values,
            state_updates_per_step * 2 * values * STATE_BYTES)


def step_bytes(ctx: dict):
    """``decode_step_bytes`` of the traced window's mean step, or None."""
    s = shape_of(ctx["sizes"])
    c = counters(ctx) if s else None
    if not c or not c["steps"]:
        return None
    return decode_step_bytes(s, ctx["live_kv_tokens"], c["experts_hit"] / c["steps"],
                             c["state_updates"] / c["steps"])
