"""(Token, expert) pairs EXPECTED at the experts this chip holds in one
iteration of one routed layer: the scheduler's `moe_token_expert_pairs`
/ steps, times the configuration's held share (`experts_held`: count /
of). An expectation under a router that spreads its pairs evenly, NOT a
count: which pairs really arrive is known on the device alone, and the
program hands no routing result back. 32 of 1,024 at 128 rows, top-8,
8 of 256 held. Nothing for a configuration that holds every expert."""


def expected(obs):
    """Held pairs an iteration by the uniform prior, or None."""
    d = obs.get("counters_delta") or {}
    held = (obs.get("hf") or {}).get("experts_held")
    if not held or not d.get("steps") or not d.get("moe_token_expert_pairs"):
        return None
    return d["moe_token_expert_pairs"] / d["steps"] * held["count"] / held["of"]


def read(obs):
    return expected(obs)
