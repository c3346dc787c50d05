"""Rows a block-diffusion model was fed for each token it committed:
counters["block_rows"] / counters["block_tokens"], deltas over the
window. T denoising passes and one commit pass of B rows for B tokens
read T + 1 (5.0 at block_length 4 in 4 passes), a little more where a
first block held a prompt's remainder or a last one was cut at the
output budget; a dynamic reveal, or a commit pass merged with the next
block's first pass, lowers it. None on a program without the counters
(a parent commit) or a model that generates no blocks."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("block_tokens") or not d.get("block_rows"):
        return None
    return d["block_rows"] / d["block_tokens"]
