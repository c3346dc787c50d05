"""Operations and bytes the layers of a Nemotron-H model NEED, from
shapes alone, for its config.json keys (`hf`): layers that are ONE
mixer each (`hybrid_override_pattern`), the Mamba-2 mixer whose B and C
come in `n_groups` groups with the float32 matrix a head its sequences
carry, grouped-query attention without positions, and a HELD share of
ungated experts (two matrices each) beside the ungated shared expert.
Whatever implements the step: the needs are the algorithm's, at the
PUBLISHED widths (an expert is 2 x 2688 x 1,856 whatever the program
pads it to). `kernels/shapes.py` refuses this block's keys; the other
files here read other families'.
"""

from typing import Any, Dict


def layer_counts(hf: Dict[str, Any]) -> Dict[str, int]:
    """Layers by what they hold: `state_space` (`M`: matrices and
    carried inputs in a slot), `attention` (`*`: K/V in pages),
    `routed` (`E`: experts, and nothing a sequence) of the
    configuration as run."""
    pattern = hf["hybrid_override_pattern"]
    return {"state_space": pattern.count("M"),
            "attention": pattern.count("*"), "routed": pattern.count("E")}


def inner(hf: Dict[str, Any]) -> int:
    """Values all the heads hold a token: x, z and y (4,096: heads x
    head_dim; `expand` is not read)."""
    return hf["mamba_num_heads"] * hf["mamba_head_dim"]


def conv_channels(hf: Dict[str, Any]) -> int:
    """Channels the mixer's convolution runs over: [x; B; C], B and C a
    group (6,144)."""
    return inner(hf) + 2 * hf["n_groups"] * hf["ssm_state_size"]


def matrix_bytes_per_sequence_per_layer(hf: Dict[str, Any]) -> int:
    """The float32 matrices one sequence carries in ONE Mamba-2 layer:
    2,097,152 B at the published widths (64 heads of 64 x 128)."""
    return inner(hf) * hf["ssm_state_size"] * 4


def slot_bytes_per_sequence_per_layer(hf: Dict[str, Any],
                                      dtype_bytes: int = 2) -> int:
    """What a sequence's SLOT holds in one Mamba-2 layer (the
    scheduler's `state_bytes_moved` counts by it): the matrices, and
    the carried inputs with their channels padded to whole (8, 128)
    tiles where they are more than one (6,144 are six whole tiles);
    2,134,016 B."""
    C = conv_channels(hf)
    padded = -(-C // 1024) * 1024 if C % 128 == 0 and C > 1024 else C
    return (matrix_bytes_per_sequence_per_layer(hf)
            + (hf["conv_kernel"] - 1) * padded * dtype_bytes)


def grouped_ssm_step_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                                     n_sequences: float) -> Dict[str, float]:
    """The state-space step alone (scope `ssm_state`) in one Mamba-2
    layer over `n_tokens` rows of `n_sequences` sequences. Needed
    bytes: each sequence's matrices read once and written once (rows of
    one run share them), plus the rows' x, dt and G groups of B and C
    in float32 and the output. Needed operations a row a head: the
    decay (P N), the rank-one write (2 P N) and the read against C
    (2 P N)."""
    H, P = hf["mamba_num_heads"], hf["mamba_head_dim"]
    N, G = hf["ssm_state_size"], hf["n_groups"]
    return {"flops": 5.0 * H * P * N * n_tokens,
            "bytes": 2.0 * n_sequences * matrix_bytes_per_sequence_per_layer(hf)
            + 4.0 * n_tokens * (2 * H * P + H + 2 * G * N)}


def held(hf: Dict[str, Any]) -> int:
    """Routed experts this chip holds a layer (32 of 128)."""
    return int(hf["n_routed_experts"])


def ungated_held_experts_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                                         held_pairs: float,
                                         dtype_bytes: int = 2
                                         ) -> Dict[str, float]:
    """One routed layer's HELD experts alone (the kernel of the ungated
    pass; the shared expert is scope `moe_shared`'s): `held_pairs`
    (token, expert) pairs reach a held expert. Needed operations: 2
    flops x 2 matrices x E x F for each held pair. Needed bytes: each
    held expert a pair reached (at most all held), two matrices of the
    PUBLISHED E x F, streamed once, plus the tokens in and out."""
    E, F = hf["hidden_size"], hf["moe_intermediate_size"]
    reached = min(float(held(hf)), held_pairs)
    return {"flops": 2.0 * 2 * E * F * held_pairs,
            "bytes": (2 * E * F * reached + 2 * float(n_tokens) * E)
            * dtype_bytes}
