"""Sequences that (re)started at position 0 in a state slot, per
iteration: the scheduler's `counters["state_slot_resets"]` (admissions,
and re-admissions after a flush-and-recompute preemption) / steps. The
slot's turnover: above the admissions the traffic brings, it is
preemption recomputing prompts. None for a model without recurrent
state."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or "state_slot_resets" not in d \
            or not d.get("state_slots_live"):
        return None
    return d["state_slot_resets"] / d["steps"]
