"""Device time on device 0 of the streamed pass over a block of UNGATED
experts (the kernel `expert_stream_ungated`: act(h W_in) W_out, two
stacks an expert, under a combine column), all routed layers, per
shared-table program of the traced window. Read by the kernel's name,
which no gated pass carries (`expert_stream`, `expert_stream_grouped`
are theirs: both names END where this one goes on, so a reader of
either must not match by prefix). None on a program without it."""

from benchmarks.trace import reduce as R

KERNEL = "expert_stream_ungated"


def kernel_ms(obs):
    td = obs.get("trace")
    if td is None:
        return None
    s = R.kernel_seconds(td, (KERNEL,))
    n = len(R.modules_with(td, "paged_decode_grid"))
    if s is None or not n:
        return None
    return 1e3 * s / n


def read(obs):
    return kernel_ms(obs)
