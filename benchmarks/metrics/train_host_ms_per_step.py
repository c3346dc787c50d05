"""Host milliseconds per traced train step outside the readback wait:
the self time of `train.prepare` (curriculum, `_reshape_gas`,
`shard_batch`, the shape key), `train.launch` and `train.post`
(heartbeat, log, monitor), over the `train.batch` spans recorded."""

from benchmarks.trace import program_spans as PS


def read(obs):
    PS.load(obs)  # for its log: the step's idle gaps by program span
    return PS.train_phase_ms(("train.prepare", "train.launch", "train.post"))
