"""Operations and bytes a Mellum 2 block NEEDS, from the configuration
file alone (never from the program): the two kinds of K/V layer and
the routed block whose experts' width is `moe_intermediate_size`
(`kernels/moe.py` reads OLMoE's `intermediate_size`, which here is a
dense entry's SwiGLU).

All functions take the configuration's keys (`hf`): the published
config.json's, cut as `reduced` says.
"""

from typing import Any, Dict


def layer_counts(hf: Dict[str, Any]) -> Dict[str, int]:
    """Layers by what they hold and compute, as the file's
    `layer_types` / `mlp_layer_types` name them."""
    types, mlps = hf["layer_types"], hf["mlp_layer_types"]
    return {"window": types.count("sliding_attention"),
            "full": types.count("full_attention"),
            "routed": mlps.count("sparse"), "dense": mlps.count("dense")}


def kv_bytes_per_token_per_layer(hf: Dict[str, Any],
                                 dtype_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * dtype_bytes


def ring_blocks(hf: Dict[str, Any]) -> int:
    """R: blocks a sequence holds in a windowed layer at any length,
    ceil((window + block - 1) / block) + 1 (a chunk of at most one
    block of rows is written before it attends; neither end aligned)."""
    bs = hf["serve"]["engine"]["kv_block_size"]
    return -(-(hf["sliding_window"] + bs - 1) // bs) + 1


def pool_bytes(hf: Dict[str, Any], dtype_bytes: int = 2) -> Dict[str, int]:
    """What the two pools hold, pad blocks left out: the full layers'
    pages and the windowed layers' rings."""
    eng, n = hf["serve"]["engine"], layer_counts(hf)
    block = eng["kv_block_size"] * kv_bytes_per_token_per_layer(hf, dtype_bytes)
    return {"full": eng["num_kv_blocks"] * n["full"] * block,
            "window": eng["num_kv_rings"] * ring_blocks(hf) * n["window"] * block}


def walk_bytes(hf: Dict[str, Any], full_tokens: float,
               window_tokens: float, dtype_bytes: int = 2) -> float:
    """Cached bytes the shared-table walk NEEDS for one step over all
    layers: each dispatched sequence's context once a full layer
    (`full_tokens`, the scheduler's kv_full_tokens a step) and its
    context inside the window once a windowed layer (`window_tokens`,
    kv_window_tokens), however many rows it has."""
    n = layer_counts(hf)
    return (full_tokens * n["full"] + window_tokens * n["window"]) \
        * kv_bytes_per_token_per_layer(hf, dtype_bytes)


def expert_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                           dtype_bytes: int = 2) -> Dict[str, float]:
    """One routed layer's gated experts over `n_tokens` tokens, each
    routed to `num_experts_per_tok` of `num_experts` experts of
    `moe_intermediate_size`: 2 flops x 3 matrices x E x F a (token,
    expert) pair; the weights of every expert a token reached read once
    (at most one a pair, at most all) plus the tokens in and out. The
    router's matmul is not in it (scope `moe_route`)."""
    E, F = hf["hidden_size"], hf["moe_intermediate_size"]
    pairs = float(n_tokens) * hf["num_experts_per_tok"]
    reached = min(float(hf["num_experts"]), pairs)
    return {"flops": 2.0 * 3 * E * F * pairs,
            "bytes": (reached * 3 * E * F + 2 * float(n_tokens) * E)
            * dtype_bytes}
