"""Mean wait between submit() and the first admission, over the
requests admitted inside the window: counters["queue_wait_s"] /
counters["admitted"], deltas. Above the knee it grows with the queue,
as `sat_ttft_p50_ms` does; the two together say how much of the time
to the first token is queue."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("admitted") or "queue_wait_s" not in d:
        return None
    return 1e3 * d["queue_wait_s"] / d["admitted"]
