"""Operations and bytes the algorithms NEED, from shapes alone — the
yardstick's arithmetic, kept apart from the program's own
(`TransformerConfig.flops_per_token` counts the embedding gather as a
matmul; XLA's cost analysis counts recomputed operations).

All functions take the published config.json keys (`hf`).
"""

import re
from typing import Any, Dict

# keys that change what a block holds; one this file does not know is
# an error, never a dense count
_BLOCK_KEY = re.compile(r"expert|^moe_|_lora_rank$|_head_dim$|^ssm_|^mamba_"
                        r"|^conv_|dense_replace|^layer_types$|_bias$")
_EXPERT_COUNT = ("num_local_experts", "num_experts")
_KNOWN_BLOCK_KEYS = _EXPERT_COUNT + ("num_experts_per_tok",)


def _experts(hf: Dict[str, Any]):
    """(experts a layer holds, experts a token is routed to), or (0, 0)
    for a dense block. Refuses block keys it cannot count."""
    unknown = sorted(k for k, v in hf.items() if _BLOCK_KEY.search(k)
                     and k not in _KNOWN_BLOCK_KEYS
                     and not (k.endswith("_bias") and not v))
    if unknown:
        raise ValueError(
            f"kernels/shapes.py cannot count a block with the keys {unknown}: "
            f"a dense count would be wrong; add the arithmetic (a new "
            f"function beside these) with the configuration")
    held = [hf[k] for k in _EXPERT_COUNT if hf.get(k)]
    if not held:
        if hf.get("num_experts_per_tok"):
            raise ValueError("num_experts_per_tok without a count of experts")
        return 0, 0
    if len(held) > 1 or not hf.get("num_experts_per_tok"):
        raise ValueError("a routed block states one count of experts "
                         f"({_EXPERT_COUNT}) and num_experts_per_tok")
    return int(held[0]), int(hf["num_experts_per_tok"])


def layer_params(hf: Dict[str, Any]) -> int:
    """Parameters one decoder layer HOLDS, its two norms included (a
    family's further norms, as a QK-norm's 2 x H x D, are not in
    config.json and not here). Mistral-7B: 218,112,000."""
    return layer_matmul_params(hf) + 2 * hf["hidden_size"]


def layer_matmul_params(hf: Dict[str, Any], active: bool = False) -> int:
    """Matrix parameters of one decoder layer: those it HOLDS, or with
    `active` those one token is MULTIPLIED by. They differ in a routed
    block only: N experts of 3 x E x F held (`intermediate_size` is one
    expert's width, as the catalog reads it), k of them and the E x N
    router multiplied by. OLMoE-1B-7B: 419,561,472 held, 67,239,936
    active."""
    E, F = hf["hidden_size"], hf["intermediate_size"]
    D = hf.get("head_dim") or E // hf["num_attention_heads"]
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    attention = E * H * D + 2 * E * KV * D + H * D * E
    n_experts, top_k = _experts(hf)
    if not n_experts:
        return attention + 3 * E * F
    return attention + E * n_experts + (top_k if active else n_experts) * 3 * E * F


def model_params(hf: Dict[str, Any], n_layers: int = None) -> int:
    """All parameters HELD: layers, embedding, final norm, untied head.
    Mistral-7B whole: 7,241,732,096; OLMoE-1B-7B: 6,919,096,320."""
    L = hf["num_hidden_layers"] if n_layers is None else n_layers
    E, V = hf["hidden_size"], hf["vocab_size"]
    head = 0 if hf.get("tie_word_embeddings") else E * V
    return L * layer_params(hf) + E * V + E + head


def matmul_params(hf: Dict[str, Any], n_layers: int = None) -> int:
    """Parameters a token is MULTIPLIED by: the layers' matrices and
    the output head (of a routed block, the router and the k experts a
    token reaches). The embedding is a gather and the norms are
    elementwise: neither is a matmul. OLMoE-1B-7B: 1,178,861,568 (its
    model card's 1.3 B counts the embedding gather too)."""
    L = hf["num_hidden_layers"] if n_layers is None else n_layers
    return L * layer_matmul_params(hf, active=True) \
        + hf["hidden_size"] * hf["vocab_size"]


def train_flops_per_token(hf: Dict[str, Any], seq_len: int,
                          n_layers: int = None) -> float:
    """Forward + backward operations one trained token requires:
    6 x matmul parameters, plus causal attention 6 * L * min(S, window)
    * (H * D) (QK^T and PV, 2 flops each, over S/2 visible keys on
    average, x3 for forward + backward). No recomputation counted."""
    L = hf["num_hidden_layers"] if n_layers is None else n_layers
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    span = min(seq_len, hf.get("sliding_window") or seq_len)
    return 6.0 * matmul_params(hf, L) + 6.0 * L * span * hf["num_attention_heads"] * D


def flash_flops_and_bytes(hf: Dict[str, Any], batch: int, seq_len: int,
                          dtype_bytes: int = 2) -> Dict[str, float]:
    """One layer's causal flash attention, forward + backward, for
    `batch` sequences. Needed matmuls: forward QK^T, PV; backward the
    recomputed QK^T, dV, dP, dQ, dK — seven, each B*H*S^2*D operations
    under the causal mask (2 flops x S^2/2 pairs). A split backward
    that recomputes QK^T and dP twice does nine; the surplus is not
    credited. Bytes: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv (lse is small and left out)."""
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // H
    unit = float(batch) * H * seq_len * seq_len * D
    q = float(batch) * seq_len * H * D * dtype_bytes
    kv = float(batch) * seq_len * KV * D * dtype_bytes
    return {"flops": 7.0 * unit,
            "bytes": (2 * q + 2 * kv) + (4 * q + 4 * kv)}


def kv_bytes_per_token(hf: Dict[str, Any], n_layers: int,
                       dtype_bytes: int = 2) -> int:
    """K and V of one token over all layers. Mistral-7B at 16 layers,
    bf16: 65,536."""
    D = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return 2 * n_layers * hf["num_key_value_heads"] * D * dtype_bytes
