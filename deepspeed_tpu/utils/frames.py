"""Deep, call-heavy host work (tracing a program, lowering it) on ONE chunk
of the interpreter's frame stack.

CPython keeps a thread's Python frames in chunks of 16 KiB, allocates the
next chunk when a call does not fit, and FREES it the moment the frame at
its base returns (pystate.c `push_chunk`, `_PyThreadState_PopFrame`). Code
that calls and returns across the edge of a full chunk maps and unmaps
16 KiB a CALL: 6-11 us on this repo's sandbox and 94 us on the
benchmark's host, where a call costs 0.06 us (PERF.md section 5, PR 49).
Tracing a serving step is ten million calls at depths that follow the
model's code, so some loop of it always straddles an edge: one warm-up
took 12.6 s or 4.1 s by the depth `warmup` was called from, and 3.1 s
from inside the frame below.

A new chunk is sized to the frame that did not fit: 16 KiB doubled until
it does. A frame of 33,000 locals (264 KB) gets a chunk of 512 KiB, and
the ~31,000 slots it leaves free hold the next several hundred frames, so
no call above it meets an edge until those are used up (then it is as
before, never worse)."""

import functools

# just over half of 512 KiB of 8-byte slots: the chunk's other half is free
_LOCALS = 33_000


@functools.cache
def _big_frame():
    names = "=".join(f"_{i}" for i in range(_LOCALS))
    scope: dict = {}
    exec("def big_frame(call, *args, **kwargs):\n"
         f"    {names} = None\n"
         "    return call(*args, **kwargs)\n", scope)
    return scope["big_frame"]


def on_one_chunk(call, *args, **kwargs):
    """call(*args, **kwargs) from a frame so large that the interpreter
    gives it, and the few hundred frames above it, one chunk of their
    own."""
    return _big_frame()(call, *args, **kwargs)
