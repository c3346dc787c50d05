"""Share of the device's idle time in the traced window whose gap a
LEAF span of the program names (`sched.build`, `sched.launch`, ...,
not merely `sched.iteration`): how much of the idle time the program's
own spans attribute to a layer boundary."""

from benchmarks.trace import program_spans as PS


def read(obs):
    ps = PS.load(obs)
    if ps is None:
        return None
    share = PS.named_share(ps["gaps"])
    return None if share is None else 100.0 * share
