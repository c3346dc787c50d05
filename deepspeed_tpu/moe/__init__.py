from .dropless import (  # noqa: F401
    DroplessOut,
    chosen_scores,
    dropless_apply,
    dropless_moe_ffn,
    dropless_topk_gating,
    expert_counts,
    grouped_mm,
    router_z_loss,
    rows_of,
    sigmoid_topk_gating,
    sort_by_expert,
    sort_pairs,
    sum_to_tokens,
    token_order,
)
from .sharded_moe import (  # noqa: F401
    compute_capacity,
    moe_ffn,
    top1_gating,
    top2_gating,
    topk_gating,
)
