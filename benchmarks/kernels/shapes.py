"""Operations and bytes the algorithms NEED, from shapes alone — the
yardstick's arithmetic, kept apart from the program's own
(`TransformerConfig.flops_per_token` counts the embedding gather as a
matmul; XLA's cost analysis counts recomputed operations).

All functions take the published config.json keys (`hf`).
"""

from typing import Any, Dict


def layer_params(hf: Dict[str, Any]) -> int:
    """Parameters of one decoder layer, norms included.
    Mistral-7B: 218,112,000."""
    return layer_matmul_params(hf) + 2 * hf["hidden_size"]


def layer_matmul_params(hf: Dict[str, Any]) -> int:
    E, F = hf["hidden_size"], hf["intermediate_size"]
    D = hf.get("head_dim") or E // hf["num_attention_heads"]
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    return E * H * D + 2 * E * KV * D + H * D * E + 3 * E * F


def model_params(hf: Dict[str, Any], n_layers: int = None) -> int:
    """All parameters: layers, embedding, final norm, untied head.
    Mistral-7B whole: 7,241,732,096."""
    L = hf["num_hidden_layers"] if n_layers is None else n_layers
    E, V = hf["hidden_size"], hf["vocab_size"]
    head = 0 if hf.get("tie_word_embeddings") else E * V
    return L * layer_params(hf) + E * V + E + head


def matmul_params(hf: Dict[str, Any], n_layers: int = None) -> int:
    """Parameters a token is MULTIPLIED by: the layers' matrices and
    the output head. The embedding is a gather and the norms are
    elementwise: neither is a matmul."""
    L = hf["num_hidden_layers"] if n_layers is None else n_layers
    return L * layer_matmul_params(hf) + hf["hidden_size"] * hf["vocab_size"]


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
