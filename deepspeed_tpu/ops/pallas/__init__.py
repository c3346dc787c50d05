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

import jax

_interpret = contextvars.ContextVar("ds_pallas_interpret", default=False)


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


def kernels_runnable() -> bool:
    return _interpret.get() or jax.default_backend() == "tpu"
