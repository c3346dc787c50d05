"""Pallas streamed expert MLP: one pipelined pass over a layer's expert
stack (TPU).

Serving's scan path multiplies every token by every expert so as to
stream each expert's weights once; as a `lax.scan` that is one loop
trip and three separately started dots an expert, each starting its
weight stream cold and draining it before the next begins (64 experts
of 2048 x 1024 on a v5e: 56% of the chip's bandwidth). Here the whole
stack is ONE grid over (expert, F tile): the BlockSpec pipeline
double-buffers the three weight tiles ACROSS expert boundaries, so the
DMA engine never drains between experts and the matmuls of step n run
under the DMA of step n + 1. The tokens and a float32 [T, E]
accumulator stay in VMEM for the whole layer.

The stacks are read as serving's prepare() lays them out ([X, E, F],
[X, E, F], [X, F, E]): no re-layout, no second copy.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret

# scoped VMEM the pass may ask Mosaic for, what its buffers may take of
# that, and what the double-buffered weight tiles may take of those:
# ~6 MB of weights a grid step read best at both widths timed on a v5e
# (routed block at 128 tokens, ms by F tile; PERF.md section 6, PR 34):
# 64 x [2048, 1024], 8 layers: 256: 9.42, 512: 8.77, 1024: 8.80 (the
# stream alone needs 7.87); 8 x [7680, 2048], 4 layers: 128: 4.15,
# 256: 4.32, 512: 4.21 (needs 3.69)
_STREAM_VMEM_LIMIT = 64 << 20
_STREAM_VMEM_BUDGET = 56 << 20
_STREAM_WEIGHT_BYTES = 16 << 20


def _pad_rows(n: int) -> int:
    return -(-n // 16) * 16  # the sublane tile of a 16-bit type


def stream_f_tile(n_tokens: int, w_gate, w_in, w_out):
    """The F tile the streamed pass would take for `n_tokens` rows over
    these stacks (arrays or shapes: [X, E, F], [X, E, F], [X, F, E]),
    or None where it cannot take them: stacks that are not plain arrays
    of ONE 16-bit float type, E or F off the 128-lane tile, or tokens
    whose resident buffers do not fit beside the weight tiles. The
    widest tile whose double-buffered weights fit their share (the
    narrowest, one lane tile, may exceed it)."""
    stacks = (w_gate, w_in, w_out)
    if not all(hasattr(w, "dtype") and hasattr(w, "shape") for w in stacks):
        return None  # a QuantizedWeight stack: codes + scales
    dtype = jnp.dtype(w_gate.dtype)
    if (any(jnp.dtype(w.dtype) != dtype for w in stacks) or dtype.itemsize != 2
            or not jnp.issubdtype(dtype, jnp.floating)):
        return None
    X, E, F = w_gate.shape
    if w_in.shape != (X, E, F) or w_out.shape != (X, F, E) or E % 128 or F % 128:
        return None
    Tp = _pad_rows(n_tokens)
    resident = (4 * Tp * E * 2                    # tokens, result: x 2 buffers
                + 2 * Tp * E * 4                  # accumulator, a dot's result
                + 2 * Tp * -(-X // 128) * 128 * 4)  # the combine weights
    for tf in range(F, 0, -128):
        weights = 2 * 3 * E * tf * 2
        if F % tf or (weights > _STREAM_WEIGHT_BYTES and tf > 128):
            continue
        # + gate, up and their product in float32
        if weights + resident + 3 * Tp * tf * 4 <= _STREAM_VMEM_BUDGET:
            return tf
    return None


def _stream_kernel(c_ref, h_ref, wg_ref, wi_ref, wo_ref, o_ref, acc_ref, *,
                   act):
    x, f = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(x == 0, f == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...]
    gate = jnp.dot(h, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(h, wi_ref[...], preferred_element_type=jnp.float32)
    # this expert's combine column out of the resident [T, X] matrix: a
    # masked lane reduction (a [1, T] row would have to be transposed)
    c = c_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    col = jnp.sum(jnp.where(lane == x, c, 0.0), axis=1, keepdims=True)
    inner = (act(gate) * up * col).astype(h.dtype)
    acc_ref[...] += jnp.dot(inner, wo_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(x == pl.num_programs(0) - 1,
                             f == pl.num_programs(1) - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def expert_stream_mlp(h, w_gate, w_in, w_out, wcols, act=jax.nn.silu):
    """sum_x wcols[x][:, None] * ((act(h @ w_gate[x]) * (h @ w_in[x]))
    @ w_out[x]) over h [T, E], stacks [X, E, F], [X, E, F], [X, F, E]
    and combine columns wcols [X, T] (zero where a token did not choose
    the expert) -> [T, E] in h's dtype. Operands in the stacks' 16-bit
    type, float32 inside every dot and across experts. Every expert is
    streamed and multiplied whatever the columns hold. The caller asks
    stream_f_tile first."""
    T, E = h.shape
    X, _, F = w_gate.shape
    tf = stream_f_tile(T, w_gate, w_in, w_out)
    assert tf is not None, (h.shape, w_gate.shape, w_gate.dtype)
    Tp = _pad_rows(T)
    hp = jnp.pad(h.astype(w_gate.dtype), ((0, Tp - T), (0, 0)))
    cols = jnp.pad(wcols.astype(jnp.float32).T, ((0, Tp - T), (0, 0)))
    whole = lambda x, f: (0, 0)
    out = pl.pallas_call(
        functools.partial(_stream_kernel, act=act),
        grid=(X, F // tf),
        in_specs=[
            pl.BlockSpec((Tp, X), whole),
            pl.BlockSpec((Tp, E), whole),
            pl.BlockSpec((None, E, tf), lambda x, f: (x, 0, f)),
            pl.BlockSpec((None, E, tf), lambda x, f: (x, 0, f)),
            pl.BlockSpec((None, tf, E), lambda x, f: (x, f, 0)),
        ],
        out_specs=pl.BlockSpec((Tp, E), whole),
        out_shape=jax.ShapeDtypeStruct((Tp, E), h.dtype),
        scratch_shapes=[pltpu.VMEM((Tp, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STREAM_VMEM_LIMIT),
        interpret=interpret(),
        name="expert_stream",
    )(cols, hp, w_gate, w_in, w_out)
    return out[:T]
