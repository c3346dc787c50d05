"""Device time in which a collective operation (all-gather,
reduce-scatter, all-reduce, collective-permute, all-to-all) runs on
device 0, per traced step."""

from benchmarks.trace import reduce as R


def read(obs):
    td = obs.get("trace")
    if td is None:
        return None
    total, _ = R.collective_seconds(td)
    return 1e3 * total / obs["traced_steps"]
