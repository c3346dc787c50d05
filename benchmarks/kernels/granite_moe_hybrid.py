"""Operations and bytes the layers of a Granite 4.0-H model NEED, from
shapes alone, for its config.json keys (`hf`): the Mamba-2 mixer with
the float32 matrix a head its sequences carry, grouped-query attention
without positions in the layers that hold K/V, and a HELD share of the
routed experts beside the ungated shared expert. Whatever implements
the step: the needs are the algorithm's. `kernels/shapes.py` refuses
this block's keys, `kernels/qwen3_next.py`, `kernels/lfm2.py` and
`kernels/mla.py` read other families'.
"""

from typing import Any, Dict


def layer_counts(hf: Dict[str, Any]) -> Dict[str, int]:
    """Layers by what they hold: `state_space` (matrices and carried
    inputs in a slot), `attention` (K/V in pages), `routed` (experts:
    every layer) of the configuration as run."""
    types = hf["layer_types"]
    return {"state_space": types.count("mamba"),
            "attention": types.count("attention"), "routed": len(types)}


def inner(hf: Dict[str, Any]) -> int:
    """Values all the heads hold a token: x, z and y (8,192)."""
    return hf["mamba_n_heads"] * hf["mamba_d_head"]


def conv_channels(hf: Dict[str, Any]) -> int:
    """Channels the mixer's convolution runs over: [x; B; C] (8,448)."""
    return inner(hf) + 2 * hf["mamba_n_groups"] * hf["mamba_d_state"]


def matrix_bytes_per_sequence_per_layer(hf: Dict[str, Any]) -> int:
    """The float32 matrices one sequence carries in ONE Mamba-2 layer:
    4,194,304 B at the published widths (128 heads of 64 x 128)."""
    return inner(hf) * hf["mamba_d_state"] * 4


def state_bytes_per_sequence_per_layer(hf: Dict[str, Any],
                                       dtype_bytes: int = 2) -> int:
    """Everything one sequence carries in ONE Mamba-2 layer: the
    matrices and the convolution's last K - 1 inputs; 4,244,992 B."""
    return (matrix_bytes_per_sequence_per_layer(hf)
            + (hf["mamba_d_conv"] - 1) * conv_channels(hf) * dtype_bytes)


def slot_bytes_per_sequence_per_layer(hf: Dict[str, Any],
                                      dtype_bytes: int = 2) -> int:
    """What a sequence's SLOT holds in one Mamba-2 layer (the
    scheduler's `state_bytes_moved` counts by it): the matrices, and
    the carried inputs with their channels padded to whole (8, 128)
    tiles, 8,448 in 9,216; 4,249,600 B."""
    C = conv_channels(hf)
    padded = -(-C // 1024) * 1024 if C % 128 == 0 else C
    return (matrix_bytes_per_sequence_per_layer(hf)
            + (hf["mamba_d_conv"] - 1) * padded * dtype_bytes)


def kv_bytes_per_token_per_layer(hf: Dict[str, Any],
                                 dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE attention layer: 4,096 B in bf16 at
    the published widths (8 KV heads of 128)."""
    D = hf["hidden_size"] // hf["num_attention_heads"]
    return 2 * hf["num_key_value_heads"] * D * dtype_bytes


def ssm_step_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                             n_sequences: float) -> Dict[str, float]:
    """The state-space step alone (scope `ssm_state`) in one Mamba-2
    layer over `n_tokens` rows of `n_sequences` sequences. Needed
    bytes: each sequence's matrices read once and written once (rows of
    one run share them), plus the rows' x, dt, B, C in float32 and the
    output. Needed operations a row a head: the decay (P N), the
    rank-one write (2 P N) and the read against C (2 P N)."""
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    return {"flops": 5.0 * H * P * N * n_tokens,
            "bytes": 2.0 * n_sequences * matrix_bytes_per_sequence_per_layer(hf)
            + 4.0 * n_tokens * (2 * H * P + H + 2 * N)}


def mixer_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                          n_sequences: float,
                          dtype_bytes: int = 2) -> Dict[str, float]:
    """One Mamba-2 layer's whole operator (scope `state_space`): the
    step plus the projections (E -> [z; x; B; C; dt], H P -> E: 2 flops
    a weight a row), the mixer's weights once, the rows in and out and
    each sequence's carried inputs read and written."""
    E, H, I = hf["hidden_size"], hf["mamba_n_heads"], inner(hf)
    C, K = conv_channels(hf), hf["mamba_d_conv"]
    matmuls = E * (I + C + H) + I * E
    weights = matmuls + C * (K + 1) + 3 * H + I
    step = ssm_step_flops_and_bytes(hf, n_tokens, n_sequences)
    return {"flops": step["flops"] + 2.0 * matmuls * n_tokens,
            "bytes": step["bytes"] + (
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
    return {"flops": 2.0 * 2 * hf["hidden_size"] * row_tokens,
            "bytes": float(table_tokens)
            * kv_bytes_per_token_per_layer(hf, dtype_bytes)}


def held_experts_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                                 held_pairs: float,
                                 dtype_bytes: int = 2) -> Dict[str, float]:
    """One layer's expert work on a chip that HOLDS `num_local_experts`
    of the routed experts and the shared one: `held_pairs` (token,
    expert) pairs reach a held expert, every token passes the shared
    expert. Needed operations: 2 flops x 3 matrices x E x F for each
    held pair, x E x Fs for each token. Needed bytes: each held expert
    a pair reached (at most all held) and the shared expert streamed
    once, plus the tokens in and out."""
    E, F = hf["hidden_size"], hf["intermediate_size"]
    Fs = hf.get("shared_intermediate_size", 0)
    reached = min(float(hf["num_local_experts"]), held_pairs)
    return {"flops": 2.0 * 3 * E * (F * held_pairs + Fs * n_tokens),
            "bytes": (3 * E * (reached * F + Fs)
                      + 2 * float(n_tokens) * E) * dtype_bytes}
