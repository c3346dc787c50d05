"""Operations and bytes latent attention (MLA) and a held share of
routed experts NEED, from shapes alone, for openPangu-Ultra-MoE's
config.json keys (`hf`); `kernels/moe.py` reads OLMoE's keys.

The absorbed latent walk attends `H` query heads over ONE cached row a
token: `kv_lora_rank + qk_rope_head_dim` values, of which the first
`kv_lora_rank` are also the value.
"""

from typing import Any, Dict


def latent_row_bytes(hf: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """What one token's cached row NEEDS in one layer: 1,152 B in bf16
    at the published widths (the pool pads it to whole lanes, 1,280 B:
    the padding is the program's cost, not the algorithm's need)."""
    return (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * dtype_bytes


def latent_walk_flops_and_bytes(hf: Dict[str, Any], table_tokens: float,
                                row_tokens: float,
                                dtype_bytes: int = 2) -> Dict[str, float]:
    """One layer's absorbed latent attention in one iteration.

    table_tokens: cached tokens summed over the TABLES of the iteration
    (each sequence's cache read once, however many rows it has).
    row_tokens: cached tokens summed over the ROWS (a chunk's rows each
    attend over their table).
    Needed bytes: every live cached row once. Needed operations: for
    each row and cached token, all heads' score (2 flops x (Rkv + Dr))
    and value (2 flops x Rkv) products. The absorption of W_uk / W_uv
    and the projections are other scopes' work."""
    H, Rkv, Dr = (hf["num_attention_heads"], hf["kv_lora_rank"],
                  hf["qk_rope_head_dim"])
    return {"flops": 2.0 * row_tokens * H * ((Rkv + Dr) + Rkv),
            "bytes": float(table_tokens) * latent_row_bytes(hf, dtype_bytes)}


def held_experts_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                                 held_pairs: float,
                                 dtype_bytes: int = 2) -> Dict[str, float]:
    """One routed layer's expert work on a chip that HOLDS
    `n_routed_experts` of the routed experts and the shared ones:
    `held_pairs` (token, expert) pairs reach a held expert, every one
    of `n_tokens` tokens passes the shared expert(s).

    Needed operations: 2 flops x 3 matrices x E x F for each held pair
    and each (token, shared expert). Needed bytes: each held expert a
    pair reached (at most all held) and each shared expert streamed
    once, plus the tokens in and out."""
    E, F = hf["hidden_size"], hf["moe_intermediate_size"]
    held, shared = hf["n_routed_experts"], hf.get("n_shared_experts", 0)
    reached = min(float(held), held_pairs) + shared
    return {"flops": 2.0 * 3 * E * F * (held_pairs + n_tokens * shared),
            "bytes": (reached * 3 * E * F + 2 * float(n_tokens) * E) * dtype_bytes}
