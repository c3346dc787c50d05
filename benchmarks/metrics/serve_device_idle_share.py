"""Share of the traced window in which no operation ran on device 0
(1 - union of device-op intervals / window)."""

from benchmarks.trace import reduce as R


def read(obs):
    td = obs.get("trace")
    return None if td is None else 100.0 * R.idle_share(td)
