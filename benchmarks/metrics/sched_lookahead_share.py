"""Share of the window's scheduler steps that were launched BEFORE the
previous step's tokens were read back (`ServingScheduler.run`'s
look-ahead): counters["lookahead_steps"] / counters["steps"], in
percent. At 100 the device never waits for the host between two steps;
what is missing are the iterations that fell back to readback-then-
dispatch (`lookahead_fallbacks`, speculation, a mesh, wave or fused
parts). A program without the counter gives nothing."""


def read(obs):
    d = obs.get("counters_delta") or {}
    if not d.get("steps") or "lookahead_steps" not in d:
        return None
    return 100.0 * d["lookahead_steps"] / d["steps"]
