"""Operations and bytes the Trinity-class (`afmoe`) TRAINING step needs,
from shapes alone: the arithmetic `kernels/shapes.py` refuses to guess
(a routed block with a shared expert and a held share, windowed and
full attention in one stack, a leading dense layer, a gate in
attention). All functions take the configuration file's keys (`hf`):
`num_experts` is what this chip HOLDS, `reduced.num_experts.published`
the router's width. No recomputation is counted anywhere.
"""

from typing import Any, Dict


def _dims(hf):
    E, H, KV = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    return E, H, KV, hf["head_dim"]


def router_width(hf) -> int:
    return int((hf.get("reduced") or {}).get("num_experts", {}).get(
        "published", hf["num_experts"]))


def attention_matmul_params(hf) -> int:
    """Wq, the gate's Wg and Wo (E x H D each), Wk and Wv (E x KV D)."""
    E, H, KV, D = _dims(hf)
    return 3 * E * H * D + 2 * E * KV * D


def attention_params(hf) -> int:
    """... and the two per-head QK-norm scales of D."""
    return attention_matmul_params(hf) + 2 * hf["head_dim"]


def expert_params(hf) -> int:
    """One routed (or one shared) expert: a SwiGLU of moe_intermediate_size."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def expected_held_pairs_per_token(hf) -> float:
    """k x held / n: the pairs of a token that land on an expert held
    here when the router spreads its choices evenly."""
    return hf["num_experts_per_tok"] * hf["num_experts"] / router_width(hf)


def layer_params(hf, dense: bool) -> int:
    """Parameters one layer HOLDS on this chip, its four norms included.
    Trinity-Mini, 16 of 128 experts: dense 65,020,160, routed
    134,488,448."""
    E = hf["hidden_size"]
    base = attention_params(hf) + 4 * E
    if dense:
        return base + 3 * E * hf["intermediate_size"]
    X = router_width(hf)
    return (base + E * X + X  # the router and its bias span every expert
            + (hf["num_shared_experts"] + hf["num_experts"]) * expert_params(hf))


def model_params(hf: Dict[str, Any]) -> int:
    """All parameters HELD: layers, embedding, final norm, untied head.
    trinity-mini-train-l5-ep8: 705,474,304."""
    nd, L = hf["num_dense_layers"], hf["num_hidden_layers"]
    E, V = hf["hidden_size"], hf["vocab_size"]
    return (nd * layer_params(hf, True) + (L - nd) * layer_params(hf, False)
            + 2 * E * V + E)


def matmul_params_per_token(hf) -> float:
    """Matrix parameters ONE token is multiplied by: every layer's
    attention, the dense layers' SwiGLU, in a routed layer the router,
    the shared expert and the EXPECTED held pairs' experts, and the
    head. The embedding is a gather. trinity-mini-train-l5-ep8:
    276,692,992."""
    nd, L = hf["num_dense_layers"], hf["num_hidden_layers"]
    E = hf["hidden_size"]
    routed = (E * router_width(hf)
              + (hf["num_shared_experts"] + expected_held_pairs_per_token(hf))
              * expert_params(hf))
    return (L * attention_matmul_params(hf)
            + nd * 3 * E * hf["intermediate_size"] + (L - nd) * routed
            + E * hf["vocab_size"])


def visible_pairs(seq_len: int, window) -> int:
    """(query, key) pairs one sequence's causal attention holds: key j
    visible to query i iff 0 <= i - j < window (None: iff j <= i)."""
    S = seq_len
    if not window or window >= S:
        return S * (S + 1) // 2
    return window * (window + 1) // 2 + (S - window) * window


def windows(hf):
    """Each model layer's window (None: full), in order."""
    return [hf["sliding_window"] if t == "sliding_attention" else None
            for t in hf["layer_types"]]


def attention_flops_per_token(hf, seq_len: int) -> float:
    """Forward + backward attention a token: QK^T and PV (2 flops each)
    over its visible keys in the mean, x3 for forward + backward, by
    layer type. At 8,192: full 201.3 M, a window of 2,048 88.1 M."""
    _, H, _, D = _dims(hf)
    return sum(12.0 * H * D * visible_pairs(seq_len, w) / seq_len
               for w in windows(hf))


def train_flops_per_token(hf, seq_len: int) -> float:
    """6 x matmul parameters + attention: what `train_mfu` divides by.
    trinity-mini-train-l5-ep8 at 8,192: 2.21 G."""
    return 6.0 * matmul_params_per_token(hf) \
        + attention_flops_per_token(hf, seq_len)


def flash_flops_and_bytes(hf, batch: int, seq_len: int, window,
                          dtype_bytes: int = 2) -> Dict[str, float]:
    """One layer's flash attention, forward + backward, for `batch`
    sequences under `window` (None: causal): kernels/shapes.py's seven
    needed matmuls, each 2 flops a visible pair a head dim, and the same
    bytes (q, k, v, o read or written twice and four times)."""
    _, H, KV, D = _dims(hf)
    q = float(batch) * seq_len * H * D * dtype_bytes
    kv = float(batch) * seq_len * KV * D * dtype_bytes
    return {"flops": 7.0 * 2 * batch * H * D * visible_pairs(seq_len, window),
            "bytes": (2 * q + 2 * kv) + (4 * q + 4 * kv)}


def held_experts_flops_and_bytes(hf, pairs_held: float,
                                 dtype_bytes: int = 2) -> Dict[str, float]:
    """The held experts' grouped products of one step over all routed
    layers, forward + backward: 3 matrices x 2 flops x 3 (forward, the
    rows' gradient, the weights' gradient) = 18 E F a held pair; every
    held expert's weights read three times (once a pass) whatever rows
    it got."""
    E, F = hf["hidden_size"], hf["moe_intermediate_size"]
    routed_layers = hf["num_hidden_layers"] - hf["num_dense_layers"]
    return {"flops": 18.0 * E * F * pairs_held,
            "bytes": 3.0 * routed_layers * hf["num_experts"]
            * expert_params(hf) * dtype_bytes}
