"""Parameters, bytes and operations an SDAR-MoE stage NEEDS, from the
configuration file alone (never from the program): what the cell's
sizes are recounted from and what `sdar_experts_roofline` sets the
expert pass's device time against.

All functions take the configuration's keys (`hf`): the published
config.json's, cut as `reduced` says.
"""

from typing import Any, Dict


def layer_parameters(hf: Dict[str, Any]) -> Dict[str, int]:
    """One layer, by part."""
    E, D = hf["hidden_size"], hf["head_dim"]
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    X, F = hf["num_experts"], hf["moe_intermediate_size"]
    return {"attention": 2 * E * H * D + 2 * E * KV * D,
            "norms": 2 * E + 2 * D,
            "router": E * X,
            "experts": X * 3 * E * F}


def parameters(hf: Dict[str, Any]) -> int:
    """The stage as cut: its layers, the embedding, the untied head and
    the final norm."""
    E, V = hf["hidden_size"], hf["vocab_size"]
    return (hf["num_hidden_layers"] * sum(layer_parameters(hf).values())
            + 2 * V * E + E)


def kv_bytes_per_token(hf: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V of one token over the stage's layers."""
    return (hf["num_hidden_layers"] * 2 * hf["num_key_value_heads"]
            * hf["head_dim"] * dtype_bytes)


def expert_pass(hf: Dict[str, Any], rows: float,
                dtype_bytes: int = 2) -> Dict[str, float]:
    """One layer's pass over EVERY expert at `rows` rows (the streamed
    pass multiplies each expert's tile by all the rows and masks by the
    combine weights): the bytes the PUBLISHED weights need, read once,
    plus the rows in and out; the operations the pass multiplies, 2 x 3
    matrices x E x F a (row, expert); and those a pass over each row's
    OWN experts alone would (`needed_flops`: num_experts_per_tok of
    num_experts)."""
    E, F, X = hf["hidden_size"], hf["moe_intermediate_size"], hf["num_experts"]
    return {"bytes": (X * 3 * E * F + 2 * float(rows) * E) * dtype_bytes,
            "flops": 2.0 * 3 * E * F * X * float(rows),
            "needed_flops": 2.0 * 3 * E * F * hf["num_experts_per_tok"]
            * float(rows)}
