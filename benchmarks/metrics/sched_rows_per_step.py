"""Rows (tokens) the scheduler batched per iteration over the window:
counters["batched_tokens"] / counters["steps"], deltas."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps"):
        return None
    return d["batched_tokens"] / d["steps"]
