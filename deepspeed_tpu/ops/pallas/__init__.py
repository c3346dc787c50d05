"""Pallas (Mosaic) kernels, and THE switch for how they execute.

A kernel in this package compiles with Mosaic and runs on the TPU.
Anywhere else it can only run through the Pallas interpreter, which is
a debugging aid orders of magnitude slower than the chip — so interpret
mode is never inferred from the backend: it is OFF unless a caller asks
for it by name (`interpret_kernels()`; the CPU test lane's
`pallas_interpret` fixture, the CPU gate builders that pin a Pallas
program, `chip_smoke.run(tiny=True)` off-chip). A kernel called on a
non-TPU backend without that request fails in `pallas_call` instead of
quietly running interpreted.

`kernels_runnable()` is the one predicate the path selectors
(ops/attention.causal_attention, parallel/ring_attention, the serving
engine's decode_impl='auto') consult: kernels run where a TPU compiles
them or where interpret mode was requested; otherwise the jnp reference
runs and the caller's result says so (InferenceEngine.resolved_impl).
"""

import contextlib
import contextvars
import functools

import jax

_interpret = contextvars.ContextVar("ds_pallas_interpret", default=False)
_kernel_traces = 0


def interpret() -> bool:
    """Value every `pallas_call(..., interpret=)` in the package takes.
    Read at TRACE time: a jitted program keeps the mode it was traced
    under."""
    return _interpret.get()


@contextlib.contextmanager
def interpret_kernels(on: bool = True):
    """Explicitly run this package's kernels through the Pallas
    interpreter (CPU rehearsal / CPU tests) for the duration."""
    token = _interpret.set(on)
    try:
        yield
    finally:
        _interpret.reset(token)


def kernel_jit(*static_argnums: int):
    """`jax.jit` for the body that builds a kernel's `pallas_call`: THE
    boundary of every kernel a step program calls once a layer. Un-jitted,
    each call traces the kernel and builds and serializes its Mosaic
    module again, sixteen times for sixteen layers, which no compile
    cache saves (the cache keys on the lowered text). Behind a jit of its
    own, calls of equal shapes and statics are ONE jaxpr and ONE lowered
    function that the step calls a layer; XLA inlines the calls, so the
    compiled program is the same. The public entry works out what is
    static (tile, window, scale, activation) and passes interpret() AT
    ITS CALLER'S TRACE TIME as a static flag too. No donation here: the
    outer program's donation and the kernel's input_output_aliases keep
    a pool in place. Each trace of a body is counted (kernel_traces)."""

    def deco(body):
        @functools.wraps(body)
        def counted(*args):
            global _kernel_traces
            _kernel_traces += 1  # at trace time, never on the device
            return body(*args)

        return jax.jit(counted, static_argnums=static_argnums)

    return deco


def kernel_traces() -> int:
    """How many times this process has traced a kernel_jit body: a
    program that calls a kernel in n layers adds 1 a distinct signature,
    or 0 where an earlier program traced it (InferenceEngine.warmup
    puts a program's delta on its span as `kernel_traces`)."""
    return _kernel_traces


def kernels_runnable() -> bool:
    return _interpret.get() or jax.default_backend() == "tpu"
