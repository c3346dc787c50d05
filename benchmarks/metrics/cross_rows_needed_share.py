"""Of the token rows that went through the layers that read another
layer's K/V (`cross_rows_run`), the share whose logits were read
(`cross_rows_needed`), in percent: what is left over is what running
the cross-decoder on the read rows alone would save (a prompt chunk's
other rows need the layers up to the donor alone). None where the
scheduler counts no such rows (every other model, a parent)."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("cross_rows_run"):
        return None
    return 100.0 * d.get("cross_rows_needed", 0) / d["cross_rows_run"]
