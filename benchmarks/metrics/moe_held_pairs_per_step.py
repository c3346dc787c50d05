"""(Token, expert) pairs that landed on the experts THIS chip holds, a
train step, all routed layers: the step's own count (`moe_pairs_held`,
read back with the loss from the census), not an expectation. Of
tokens x k x routed layers routed in all (`moe_pairs_routed`). None
where the program counts none."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or "moe_pairs_held" not in d:
        return None
    return d["moe_pairs_held"] / d["steps"]
