"""Rows an expert's matmuls see in one iteration, on average: the
scheduler's count of (token, expert) pairs over the window
(`counters["moe_token_expert_pairs"]`, one layer's) / steps / experts.
What inference/model.py `expert_path` chooses from."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or not d.get("moe_token_expert_pairs"):
        return None
    return d["moe_token_expert_pairs"] / d["steps"] / obs["hf"]["num_experts"]
