"""State slots held by tracked sequences in one iteration, on average:
the scheduler's sum over dispatched steps of the sequences it tracks
(`counters["state_slots_live"]`) / steps. What the convolution's slot
traffic follows (a slot is 8 KB a conv layer at the published widths),
and how much of the slot table an operator's row budget fills. None
for a model without recurrent state."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or not d.get("state_slots_live"):
        return None
    return d["state_slots_live"] / d["steps"]
