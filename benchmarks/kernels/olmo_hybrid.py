"""Operations and bytes the layers of an Olmo-Hybrid model NEED, from
shapes alone, for its config.json keys (`hf`), whatever implements
them: the Gated DeltaNet operator with the float32 matrix a head its
sequences carry (96 x 192 at the published widths: 192 values a head,
never a padded 256), multi-head attention with no positions over K/V of
`num_key_value_heads` heads of hidden_size / num_attention_heads in the
layers that hold K/V, and the dense SwiGLU every layer ends in.
`kernels/qwen3_next.py` reads the other DeltaNet family's keys
(`head_dim`, `full_attention_interval`, a held share of experts).
"""

from typing import Any, Dict


def layer_counts(hf: Dict[str, Any]) -> Dict[str, int]:
    """Layers by what they hold: `linear_attention` (matrices and
    carried inputs in a slot), `attention` (K/V in pages) of the
    configuration as run."""
    types = hf["layer_types"]
    return {"linear_attention": types.count("linear_attention"),
            "attention": types.count("full_attention")}


def head_dim(hf: Dict[str, Any]) -> int:
    return hf["hidden_size"] // hf["num_attention_heads"]


def conv_channels(hf: Dict[str, Any]) -> int:
    """Channels the DeltaNet's convolution runs over: [q; k; v]."""
    return (2 * hf["linear_num_key_heads"] * hf["linear_key_head_dim"]
            + hf["linear_num_value_heads"] * hf["linear_value_head_dim"])


def matrix_bytes_per_sequence_per_layer(hf: Dict[str, Any]) -> int:
    """The float32 matrices one sequence carries in ONE DeltaNet layer:
    2,211,840 B at the published widths (30 heads of 96 x 192)."""
    return (hf["linear_num_value_heads"] * hf["linear_key_head_dim"]
            * hf["linear_value_head_dim"] * 4)


def state_bytes_per_sequence_per_layer(hf: Dict[str, Any],
                                       dtype_bytes: int = 2) -> int:
    """Everything one sequence carries in ONE DeltaNet layer: the
    matrices and the convolution's last K - 1 inputs; 2,280,960 B."""
    return (matrix_bytes_per_sequence_per_layer(hf)
            + (hf["linear_conv_kernel_dim"] - 1) * conv_channels(hf)
            * dtype_bytes)


def kv_bytes_per_token_per_layer(hf: Dict[str, Any],
                                 dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer: 15,360 B in bf16 at
    the published widths (30 KV heads of 128)."""
    return 2 * hf["num_key_value_heads"] * head_dim(hf) * dtype_bytes


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


def delta_net_parameters(hf: Dict[str, Any]) -> int:
    """One DeltaNet layer's operator: 88,750,332 at the published
    widths."""
    E, Hv = hf["hidden_size"], hf["linear_num_value_heads"]
    C, Dv = conv_channels(hf), hf["linear_value_head_dim"]
    return (E * (C + Hv * Dv) + E * 2 * Hv + C * hf["linear_conv_kernel_dim"]
            + 2 * Hv + Dv + Hv * Dv * E)


def delta_net_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                              n_sequences: float,
                              dtype_bytes: int = 2) -> Dict[str, float]:
    """One DeltaNet layer's whole operator (scope `linear_attention`):
    the delta rule plus the projections (E -> [q; k; v; z] and [b; a],
    Hv Dv -> E: 2 flops a weight a row), their weights once, the rows
    in and out and each sequence's carried inputs read and written."""
    E, Hv = hf["hidden_size"], hf["linear_num_value_heads"]
    C, K = conv_channels(hf), hf["linear_conv_kernel_dim"]
    weights = delta_net_parameters(hf) - 2 * Hv - hf["linear_value_head_dim"]
    rule = delta_rule_flops_and_bytes(hf, n_tokens, n_sequences)
    return {"flops": rule["flops"] + 2.0 * (weights - C * K) * n_tokens,
            "bytes": rule["bytes"] + (
                weights + 2.0 * n_tokens * E
                + 2.0 * n_sequences * (K - 1) * C) * dtype_bytes}


def attention_parameters(hf: Dict[str, Any]) -> int:
    """One attention layer's operator: q, k, v, o and the two
    hidden-wide QK-norm scales; 58,990,080."""
    E, H, KV, D = (hf["hidden_size"], hf["num_attention_heads"],
                   hf["num_key_value_heads"], head_dim(hf))
    return 2 * E * H * D + 2 * E * KV * D + H * D + KV * D


def attention_flops_and_bytes(hf: Dict[str, Any], table_tokens: float,
                              row_tokens: float,
                              dtype_bytes: int = 2) -> Dict[str, float]:
    """One attention layer's walk over the cache in one iteration.
    table_tokens: cached tokens summed over the iteration's TABLES
    (each sequence's K/V read once, however many rows it has);
    row_tokens: summed over the ROWS. Needed bytes: every live token's
    K and V once. Needed operations: each row's scores and values over
    its cached tokens, 2 flops x 2 x H x D a pair."""
    H, D = hf["num_attention_heads"], head_dim(hf)
    return {"flops": 2.0 * 2 * H * D * row_tokens,
            "bytes": float(table_tokens)
            * kv_bytes_per_token_per_layer(hf, dtype_bytes)}


def ffn_parameters(hf: Dict[str, Any]) -> int:
    """One layer's SwiGLU: 126,812,160."""
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def parameters(hf: Dict[str, Any]) -> int:
    """Every parameter of the configuration as run: the layers by their
    kind (operator, SwiGLU, two output norms of E), the untied embedding
    and head, the final norm. 3,268,268,508 at 12 layers."""
    E = hf["hidden_size"]
    n = layer_counts(hf)
    layer = ffn_parameters(hf) + 2 * E
    return (n["linear_attention"] * (delta_net_parameters(hf) + layer)
            + n["attention"] * (attention_parameters(hf) + layer)
            + 2 * hf["vocab_size"] * E + E)
