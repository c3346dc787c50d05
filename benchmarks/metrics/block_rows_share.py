"""Share of the rows the scheduler batched that were a block's, being
denoised or committed: counters["block_rows"] / counters["batched_tokens"]
in percent, deltas over the window; the rest are prompt chunks' rows.
None on a program without the counter or a model that generates no
blocks."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("batched_tokens") or not d.get("block_rows"):
        return None
    return 100.0 * d["block_rows"] / d["batched_tokens"]
