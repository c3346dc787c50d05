"""Cached tokens the rows of one iteration attended over in one latent
layer, on average: the scheduler's sum over dispatched rows of their
context (`counters["mla_cache_tokens"]`) / steps. What the latent
walk's operations follow; a chunk's rows each count their table."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or not d.get("mla_cache_tokens"):
        return None
    return d["mla_cache_tokens"] / d["steps"]
