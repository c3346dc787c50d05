"""Share of the window lost to stalled iterations, in percent:
counters["stall_s"] (the sum over the window's stalls of what each
took beyond a typical iteration) over the window's seconds. The number
to hold beside a run's tokens per second: a run that reads 4 here lost
4% of its window in pieces the steady loop does not explain. A program
without the counter gives nothing."""


def read(obs):
    d = obs.get("counters_delta") or {}
    window_s = obs.get("window_s")
    if "stall_s" not in d or not window_s:
        return None
    return 100.0 * d["stall_s"] / window_s
