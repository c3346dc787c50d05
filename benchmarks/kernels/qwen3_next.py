"""Operations and bytes the layers of a Qwen3-Next model NEED, from
shapes alone, for its config.json keys (`hf`): the Gated DeltaNet
operator with the float32 matrix a value head its sequences carry,
gated grouped-query attention at `head_dim` in the layers that hold
K/V, and a HELD share of the routed experts beside the gated shared
expert. `kernels/shapes.py` refuses this block's keys, `kernels/lfm2.py`
and `kernels/mla.py` read other families'.
"""

from typing import Any, Dict


def layer_counts(hf: Dict[str, Any]) -> Dict[str, int]:
    """Layers by what they hold: `linear_attention` (matrices and
    carried inputs in a slot), `attention` (K/V in pages), `routed`
    (experts: every layer) of the configuration as run."""
    n, every = hf["num_hidden_layers"], hf["full_attention_interval"]
    types = hf.get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else "linear_attention"
        for i in range(n)]
    return {"linear_attention": types.count("linear_attention"),
            "attention": types.count("full_attention"), "routed": n}


def conv_channels(hf: Dict[str, Any]) -> int:
    """Channels the DeltaNet's convolution runs over: [q; k; v]."""
    return (2 * hf["linear_num_key_heads"] * hf["linear_key_head_dim"]
            + hf["linear_num_value_heads"] * hf["linear_value_head_dim"])


def matrix_bytes_per_sequence_per_layer(hf: Dict[str, Any]) -> int:
    """The float32 matrices one sequence carries in ONE DeltaNet layer:
    2,097,152 B at the published widths (32 heads of 128 x 128)."""
    return (hf["linear_num_value_heads"] * hf["linear_key_head_dim"]
            * hf["linear_value_head_dim"] * 4)


def state_bytes_per_sequence_per_layer(hf: Dict[str, Any],
                                       dtype_bytes: int = 2) -> int:
    """Everything one sequence carries in ONE DeltaNet layer: the
    matrices and the convolution's last K - 1 inputs; 2,146,304 B."""
    return (matrix_bytes_per_sequence_per_layer(hf)
            + (hf["linear_conv_kernel_dim"] - 1) * conv_channels(hf)
            * dtype_bytes)


def kv_bytes_per_token_per_layer(hf: Dict[str, Any],
                                 dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer: 2,048 B in bf16 at
    the published widths (2 KV heads of 256)."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * dtype_bytes


def delta_rule_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                               n_sequences: float) -> Dict[str, float]:
    """The delta rule alone (the kernel under scope `gdn_state`) in one
    DeltaNet layer over `n_tokens` rows of `n_sequences` sequences.
    Needed bytes: each sequence's matrices read once and written once
    (rows of one run share them), plus the rows' q, k, v in float32 and
    the output. Needed operations a row a head: the decay (Dk Dv), S^T k
    and S^T q (2 Dk Dv each) and the rank-one write (2 Dk Dv)."""
    Hv = hf["linear_num_value_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    return {"flops": 7.0 * Hv * Dk * Dv * n_tokens,
            "bytes": 2.0 * n_sequences * matrix_bytes_per_sequence_per_layer(hf)
            + 4.0 * n_tokens * Hv * (2 * Dk + 2 * Dv)}


def delta_net_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                              n_sequences: float,
                              dtype_bytes: int = 2) -> Dict[str, float]:
    """One DeltaNet layer's whole operator (scope `linear_attention`):
    the delta rule plus the projections (E -> [q; k; v; z] and [b; a],
    Hv Dv -> E: 2 flops a weight a row), their weights once, the rows
    in and out and each sequence's carried inputs read and written."""
    E, Hv = hf["hidden_size"], hf["linear_num_value_heads"]
    C, Dv = conv_channels(hf), hf["linear_value_head_dim"]
    K = hf["linear_conv_kernel_dim"]
    weights = E * (C + Hv * Dv) + E * 2 * Hv + C * K + Hv * Dv * E
    rule = delta_rule_flops_and_bytes(hf, n_tokens, n_sequences)
    return {"flops": rule["flops"] + 2.0 * (weights - C * K) * n_tokens,
            "bytes": rule["bytes"] + (
                weights + 2.0 * n_tokens * E
                + 2.0 * n_sequences * (K - 1) * C) * dtype_bytes}


def attention_flops_and_bytes(hf: Dict[str, Any], table_tokens: float,
                              row_tokens: float,
                              dtype_bytes: int = 2) -> Dict[str, float]:
    """One attention layer's walk over the cache in one iteration.
    table_tokens: cached tokens summed over the iteration's TABLES
    (each sequence's K/V read once, however many rows it has);
    row_tokens: summed over the ROWS. Needed bytes: every live token's
    K and V once. Needed operations: each row's scores and values over
    its cached tokens, 2 flops x 2 x H x D a pair."""
    H, D = hf["num_attention_heads"], hf["head_dim"]
    return {"flops": 2.0 * 2 * H * D * row_tokens,
            "bytes": float(table_tokens)
            * kv_bytes_per_token_per_layer(hf, dtype_bytes)}


def held_experts_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                                 held_pairs: float,
                                 dtype_bytes: int = 2) -> Dict[str, float]:
    """One layer's expert work on a chip that HOLDS `num_experts` of
    the routed experts and the shared one: `held_pairs` (token, expert)
    pairs reach a held expert, every token passes the shared expert.
    Needed operations: 2 flops x 3 matrices x E x F for each held pair,
    x E x Fs for each token. Needed bytes: each held expert a pair
    reached (at most all held) and the shared expert streamed once,
    plus the tokens in and out."""
    E, F = hf["hidden_size"], hf["moe_intermediate_size"]
    Fs = hf.get("shared_expert_intermediate_size", 0)
    reached = min(float(hf["num_experts"]), held_pairs)
    return {"flops": 2.0 * 3 * E * (F * held_pairs + Fs * n_tokens),
            "bytes": (3 * E * (reached * F + Fs)
                      + 2 * float(n_tokens) * E) * dtype_bytes}
