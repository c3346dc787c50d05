"""Operations and bytes the layers of an LFM2-MoE model NEED, from
shapes alone, for its config.json keys (`hf`): the gated short
convolution with its per-sequence state, grouped-query attention at
head dim 64 in the layers that hold K/V, and the routed experts of
`moe_intermediate_size`. `kernels/shapes.py` refuses this block's keys
(`layer_types`, `conv_L_cache`, `use_expert_bias`,
`moe_intermediate_size`), and `kernels/moe.py` reads OLMoE's.
"""

from typing import Any, Dict


def layer_counts(hf: Dict[str, Any]) -> Dict[str, int]:
    """Layers by what they hold: `conv` (state in a slot), `attention`
    (K/V in pages), `routed` (experts) of the configuration as run."""
    types = hf["layer_types"]
    return {"conv": types.count("conv"),
            "attention": types.count("full_attention"),
            "routed": hf["num_hidden_layers"] - hf.get("num_dense_layers", 0)}


def kv_bytes_per_token_per_layer(hf: Dict[str, Any],
                                 dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer: 2,048 B in bf16 at
    the published widths (8 KV heads of 64). A padded or packed pool is
    the program's business: this is the need."""
    D = hf["hidden_size"] // hf["num_attention_heads"]
    return 2 * hf["num_key_value_heads"] * D * dtype_bytes


def short_conv_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                               n_sequences: float,
                               dtype_bytes: int = 2) -> Dict[str, float]:
    """One conv layer's operator in one iteration over `n_tokens` rows
    of `n_sequences` sequences. Needed operations: the two projections,
    2 flops x (3 E^2 + E^2) a row (the taps' K multiplies a channel are
    a thousandth of that). Needed bytes: W_in, the taps and W_out once,
    the rows in and out, and each sequence's K - 1 carried inputs read
    and written."""
    E, K = hf["hidden_size"], hf["conv_L_cache"]
    weights = 3 * E * E + E * K + E * E
    return {"flops": 2.0 * 4 * E * E * n_tokens,
            "bytes": (weights + 2.0 * n_tokens * E
                      + 2.0 * n_sequences * (K - 1) * E) * dtype_bytes}


def attention_flops_and_bytes(hf: Dict[str, Any], table_tokens: float,
                              row_tokens: float,
                              dtype_bytes: int = 2) -> Dict[str, float]:
    """One attention layer's walk over the cache in one iteration.
    table_tokens: cached tokens summed over the iteration's TABLES
    (each sequence's K/V read once, however many rows it has);
    row_tokens: summed over the ROWS. Needed bytes: every live token's
    K and V once. Needed operations: each row's scores and values over
    its cached tokens, 2 flops x 2 x H x D a pair."""
    H = hf["num_attention_heads"]
    D = hf["hidden_size"] // H
    return {"flops": 2.0 * 2 * H * D * row_tokens,
            "bytes": float(table_tokens)
            * kv_bytes_per_token_per_layer(hf, dtype_bytes)}


def experts_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                            dtype_bytes: int = 2) -> Dict[str, float]:
    """One routed layer's experts (gate, up, down of
    `moe_intermediate_size`) over `n_tokens` tokens, each routed to
    `num_experts_per_tok`. Needed operations: 2 flops x 3 x E x F a
    pair (an implementation that multiplies every token by every expert
    does num_experts / num_experts_per_tok times that: not credited).
    Needed bytes: every expert a pair reached, once, plus the tokens in
    and out."""
    E, F = hf["hidden_size"], hf["moe_intermediate_size"]
    X, k = hf["num_experts"], hf["num_experts_per_tok"]
    pairs = float(n_tokens) * k
    return {"flops": 2.0 * 3 * E * F * pairs,
            "bytes": (min(float(X), pairs) * 3 * E * F
                      + 2.0 * n_tokens * E) * dtype_bytes}
