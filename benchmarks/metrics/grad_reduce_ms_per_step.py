"""Device time on device 0, per traced step, booked to the scope
`grad_reduce` (runtime/engine.py, runtime/overlap.py: the accumulation
add, the zero-init of the accumulator, the unscale, the constraint of
a gradient to its ZeRO layout). At `gradient_accumulation_steps` 1 in
bf16 the add into zeros and the multiply by 1.0 fold away and the
constraint leaves no instruction of its own (the partitioner names the
all-reduce it induces after the matmul whose output it shards: see
`collective_in_fusion_ms_per_step`), so there is nothing to read; a
step that accumulates or unscales has it."""

from benchmarks.metrics.train_step_named_share import booked_ms_per_step


def read(obs):
    return booked_ms_per_step(obs, "grad_reduce")
