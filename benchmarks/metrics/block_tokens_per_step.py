"""Tokens a block-diffusion model committed per scheduler iteration:
counters["block_tokens"] / counters["steps"], deltas over the window
(rows an iteration / `block_row_passes_per_token`, less the prompt
chunks' rows). None on a program without the counter or a model that
generates no blocks."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or not d.get("block_tokens"):
        return None
    return d["block_tokens"] / d["steps"]
