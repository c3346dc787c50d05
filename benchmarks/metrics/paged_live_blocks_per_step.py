"""KV cache blocks the rows of one iteration had to read, on average:
the scheduler's sum over dispatched rows of ceil(ctx / block size) over
the window (`counters["kv_live_blocks"]`) / steps. What
`paged_grid_ms_per_step` is divided by to see whether the paged kernel
is paid per live block; the table holds rows x blocks_per_seq slots."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or not d.get("kv_live_blocks"):
        return None
    return d["kv_live_blocks"] / d["steps"]
