"""Operations and bytes the layers of a Phi-4-mini-flash model NEED,
from shapes alone, for its config.json keys (`hf`), whatever implements
them: the Mamba-1 selective scan with the float32 state [2E, 16] its
sequences carry, differential attention over K/V of
`num_key_value_heads` heads of hidden_size / num_attention_heads in the
layers that OWN K/V (windowed: a ring; one full: pages), the seven
cross-attention layers that walk the full layer's pages again, the
gated memory units that hold nothing, and the SwiGLU every layer ends
in. The Mamba sizes are the family's defaults where the file has no key
(`reference/phi4flash.py mamba_sizes` states the same rule).
"""

import math
from typing import Any, Dict


def layer_counts(hf: Dict[str, Any]) -> Dict[str, int]:
    """Layers by what they hold or read, by the publisher's rule of
    `mb_per_layer` 2 and the depth: `selective_scan` (a state slot),
    `window` (a ring), `full` (pages), `gated_memory` (nothing; reads
    the last scan), `cross` (nothing; walks the full layer's pages)."""
    L, half = hf["num_hidden_layers"], hf["num_hidden_layers"] // 2
    even = range(0, L, 2)
    odd = range(1, L, 2)
    return {"selective_scan": sum(l <= half for l in even),
            "gated_memory": sum(l > half for l in even),
            "window": sum(l < half for l in odd),
            "full": sum(l == half + 1 for l in odd),
            "cross": sum(l > half + 1 for l in odd)}


def mamba_sizes(hf: Dict[str, Any]):
    """(channels I, state N, taps K, step rank R)."""
    E = hf["hidden_size"]
    rank = hf.get("mamba_dt_rank", "auto")
    return (int(hf.get("mamba_expand", 2)) * E, int(hf.get("mamba_d_state", 16)),
            int(hf.get("mamba_d_conv", 4)),
            math.ceil(E / 16) if rank == "auto" else int(rank))


def head_dim(hf: Dict[str, Any]) -> int:
    return hf["hidden_size"] // hf["num_attention_heads"]


def state_bytes_per_sequence_per_layer(hf: Dict[str, Any]) -> int:
    """The float32 state one sequence carries in ONE scan layer:
    327,680 B at the published widths (5,120 x 16)."""
    I, N, _, _ = mamba_sizes(hf)
    return I * N * 4


def slot_bytes_per_sequence_per_layer(hf: Dict[str, Any],
                                      dtype_bytes: int = 2) -> int:
    """Everything one sequence carries in ONE scan layer: the state and
    the convolution's last K - 1 inputs; 358,400 B."""
    I, _, K, _ = mamba_sizes(hf)
    return state_bytes_per_sequence_per_layer(hf) + (K - 1) * I * dtype_bytes


def kv_bytes_per_token_per_layer(hf: Dict[str, Any],
                                 dtype_bytes: int = 2) -> int:
    """K and V of one token in ONE layer that owns K/V: 5,120 B in bf16
    at the published widths (20 KV heads of 64)."""
    return 2 * hf["num_key_value_heads"] * head_dim(hf) * dtype_bytes


def scan_step_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                              n_sequences: float) -> Dict[str, float]:
    """The recurrence alone (the kernel `sscan_state`) in one scan layer
    over `n_tokens` rows of `n_sequences` sequences. Needed bytes: each
    sequence's state read once and written once (rows of one run share
    it), plus the rows' dt, dt x, output (I float32 each) and B, C.
    Needed operations a row a (channel, state) pair: the decay's
    product and exponential, the decay, the write and the read (6)."""
    I, N, _, _ = mamba_sizes(hf)
    return {"flops": 6.0 * I * N * n_tokens,
            "bytes": 2.0 * n_sequences * state_bytes_per_sequence_per_layer(hf)
            + 4.0 * n_tokens * (3 * I + 2 * N)}


def walk_bytes(hf: Dict[str, Any], full_tokens: float, shared_tokens: float,
               window_tokens: float, dtype_bytes: int = 2) -> float:
    """Cached bytes the walks of one iteration NEED: `full_tokens` the
    contexts' tokens once for the layer that owns the pages,
    `shared_tokens` the same contexts once a layer that reads them
    (already times those layers), `window_tokens` the contexts clipped
    to the window, once a windowed layer."""
    per = kv_bytes_per_token_per_layer(hf, dtype_bytes)
    return per * (full_tokens + shared_tokens
                  + layer_counts(hf)["window"] * window_tokens)


def scan_parameters(hf: Dict[str, Any]) -> int:
    """One selective-scan mixer: 41,241,600 at the published widths."""
    E = hf["hidden_size"]
    I, N, K, R = mamba_sizes(hf)
    return (E * 2 * I + I * K + I + I * (R + 2 * N) + R * I + I + I * N + I
            + I * E)


def _differential_leaves(hf: Dict[str, Any]) -> int:
    """The four vectors of lam and the pair norm's scale: 384."""
    return 4 * head_dim(hf) + 2 * head_dim(hf)


def attention_parameters(hf: Dict[str, Any]) -> int:
    """One attention mixer that owns K/V, biases and the differential
    leaves included: 19,668,864."""
    E, H, KV, D = (hf["hidden_size"], hf["num_attention_heads"],
                   hf["num_key_value_heads"], head_dim(hf))
    return (2 * E * H * D + 2 * E * KV * D + H * D + 2 * KV * D + E
            + _differential_leaves(hf))


def cross_parameters(hf: Dict[str, Any]) -> int:
    """One cross-attention mixer (W_q, W_o, their biases): 13,112,704."""
    E, H, D = hf["hidden_size"], hf["num_attention_heads"], head_dim(hf)
    return 2 * E * H * D + H * D + E + _differential_leaves(hf)


def gated_memory_parameters(hf: Dict[str, Any]) -> int:
    """One gated memory unit: 26,214,400."""
    return 2 * hf["hidden_size"] * mamba_sizes(hf)[0]


def ffn_parameters(hf: Dict[str, Any]) -> int:
    """One layer's SwiGLU: 78,643,200."""
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def parameters(hf: Dict[str, Any]) -> int:
    """Every parameter of the configuration as run: the layers by their
    kind (mixer, SwiGLU, two LayerNorms with a bias), the tied
    embedding, the final norm. 3,852,562,944 at 32 layers."""
    E = hf["hidden_size"]
    n = layer_counts(hf)
    return (n["selective_scan"] * scan_parameters(hf)
            + (n["window"] + n["full"]) * attention_parameters(hf)
            + n["gated_memory"] * gated_memory_parameters(hf)
            + n["cross"] * cross_parameters(hf)
            + hf["num_hidden_layers"] * (ffn_parameters(hf) + 4 * E)
            + hf["vocab_size"] * E * (1 if hf.get("tie_word_embeddings") else 2)
            + 2 * E)
