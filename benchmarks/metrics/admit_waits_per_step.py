"""Admission passes an iteration that left a request waiting because a
pool of a model of mixed windows was short: the scheduler's
`admit_waits_full_pool` + `admit_waits_window_pool` over steps (it
prints each). Above capacity the queue never empties, so this reads how
often the POOLS, not the row budget, held a request back: 0 where the
rows are always taken first. None where the scheduler counts no such
waits (a parent commit) or the model has no rings."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or "admit_waits_window_pool" not in d \
            or not d.get("kv_rings_live"):
        return None
    full, window = d["admit_waits_full_pool"], d["admit_waits_window_pool"]
    print(f"[bench] admissions that waited a step: {full / d['steps']:.3f} "
          f"on the full layers' pool, {window / d['steps']:.3f} on the "
          f"windowed layers' rings", flush=True)
    return (full + window) / d["steps"]
