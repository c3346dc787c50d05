"""Operations and bytes a routed expert block NEEDS, from shapes alone
(`kernels/shapes.py` counts parameters; this file counts one
application of the block's experts to a batch of tokens).

All functions take the published config.json keys (`hf`), with the
catalog's reading that `intermediate_size` is ONE expert's width.
"""

from typing import Any, Dict


def expert_flops_and_bytes(hf: Dict[str, Any], n_tokens: float,
                           dtype_bytes: int = 2) -> Dict[str, float]:
    """One layer's gated expert MLPs (gate, up, down) over `n_tokens`
    tokens, each routed to `num_experts_per_tok` experts.

    Needed operations: 2 flops x 3 matrices x E x F for each
    (token, expert) pair; an implementation that multiplies every token
    by every expert does num_experts / num_experts_per_tok times that,
    and the surplus is not credited. Needed bytes: the weights of every
    expert a token reached, read once (at most one expert a pair, and
    at most all of them; with 16 pairs an expert under any routing that
    is not degenerate, all of them), plus the tokens in and out. The
    router's E x X matmul is not in it (its scope is `moe_route`)."""
    E, F = hf["hidden_size"], hf["intermediate_size"]
    X, k = hf["num_experts"], hf["num_experts_per_tok"]
    pairs = float(n_tokens) * k
    reached = min(float(X), pairs)
    return {"flops": 2.0 * 3 * E * F * pairs,
            "bytes": (reached * 3 * E * F + 2 * float(n_tokens) * E) * dtype_bytes}
