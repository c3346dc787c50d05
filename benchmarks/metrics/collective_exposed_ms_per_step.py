"""The part of the collectives' time on device 0 in which no other
operation runs there, per traced step."""

from benchmarks.trace import reduce as R


def read(obs):
    td = obs.get("trace")
    if td is None:
        return None
    _, exposed = R.collective_seconds(td)
    return 1e3 * exposed / obs["traced_steps"]
