"""Rings of the windowed layers' pool held by tracked sequences in one
iteration, on average: the scheduler's sum over dispatched steps
(`counters["kv_rings_live"]`) / steps. How much of the windowed pool
the mix fills (a ring is 15.7 MB at the published widths), and what an
admission that waits on that pool waits for. None for a model without
rings."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or not d.get("kv_rings_live"):
        return None
    return d["kv_rings_live"] / d["steps"]
