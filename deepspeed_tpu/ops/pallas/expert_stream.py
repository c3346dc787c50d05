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

The pass has two entries, which share the grid (expert, F tile), the
stacks as they lie, the three BlockSpecs of the weight tiles and so
the pipeline that never drains, the tile rule (_widest_f_tile), bf16
operands with float32 inside every dot, and that EVERY expert's
weights are streamed exactly once, reached or not. They differ in the
rows a grid step multiplies. The all-expert entry (expert_stream_mlp)
multiplies every token by every expert under a combine column: X / k
times the needed operations, free while the weight stream binds (to
~240 rows on a v5e). The grouped entry (expert_grouped_mlp), for
programs past that ridge, multiplies an expert's tile by that expert's
OWN rows: the (token, expert) pairs arrive in the order a stable sort
by expert gives (group_rows), each expert's run a window of one
resident buffer handed to the kernel as scalar-prefetched offsets, and
a grid step's matmuls walk that window alone. Which is taken is
inference/model.py expert_path's to say.

A block WITHOUT a gate (act(h W_in) W_out: two stacks an expert, not
three) takes the all-expert entry's twin, expert_stream_ungated_mlp:
the same grid, pipeline and combine column over two weight tiles, under
a kernel name of its own (`expert_stream_ungated`), so that a trace
tells the two apart.

The stacks are read as serving's prepare() lays them out ([X, E, F],
[X, E, F], [X, F, E]): no re-layout, no second copy.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret, kernel_jit

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
# the grouped entry holds every pair's row twice, in (bf16) and out
# (float32), so its buffers ask for more of the chip's 128 MiB
_GROUPED_VMEM_LIMIT = 100 << 20
_GROUPED_VMEM_BUDGET = 92 << 20
# rows of an expert's window one matmul takes (the row block). Timed on
# a v5e, routed block at 512 tokens, ms by block (PERF.md section 6,
# PR 37): 32 x [2048, 1792] top-4, 12 layers: 32: 12.26, 64: 12.27,
# 128: 12.28, 256: 13.61; 64 x [2048, 1024] top-8, 8 layers: 32: 11.92,
# 64: 11.88, 128: 11.94, 256: 12.01
_GROUP_ROW_TILE = 64


def _pad_rows(n: int) -> int:
    return -(-n // 16) * 16  # the sublane tile of a 16-bit type


def _stack_dims(w_gate, w_in, w_out):
    """(X, E, F) of stacks ([X, E, F], [X, E, F], [X, F, E], arrays or
    shapes; w_gate None: a block without a gate) the passes can take,
    or None: stacks that are not plain arrays of ONE 16-bit float type,
    or E or F off the 128-lane tile."""
    stacks = tuple(w for w in (w_gate, w_in, w_out) if w is not None)
    if not all(hasattr(w, "dtype") and hasattr(w, "shape") for w in stacks):
        return None  # a QuantizedWeight stack: codes + scales
    dtype = jnp.dtype(w_in.dtype)
    if (any(jnp.dtype(w.dtype) != dtype for w in stacks) or dtype.itemsize != 2
            or not jnp.issubdtype(dtype, jnp.floating)):
        return None
    X, E, F = w_in.shape
    if (w_gate is not None and w_gate.shape != (X, E, F)
            or w_out.shape != (X, F, E) or E % 128 or F % 128):
        return None
    return X, E, F


def _widest_f_tile(E: int, F: int, resident: int, rows: int, budget: int,
                   stacks: int = 3):
    """The widest F tile whose double-buffered weights (`stacks` tiles
    an expert) fit their share (the narrowest, one lane tile, may
    exceed it) and, beside `resident` bytes and the float32 products of
    `rows` rows (gate, up and theirs; or up and its activation),
    `budget`; None where none does."""
    for tf in range(F, 0, -128):
        weights = 2 * stacks * E * tf * 2
        if F % tf or (weights > _STREAM_WEIGHT_BYTES and tf > 128):
            continue
        if weights + resident + stacks * rows * tf * 4 <= budget:
            return tf
    return None


def stream_f_tile(n_tokens: int, w_gate, w_in, w_out):
    """The F tile the all-expert pass would take for `n_tokens` rows
    over these stacks (arrays or shapes: [X, E, F], [X, E, F],
    [X, F, E]; w_gate None: the ungated entry's two), or None where it
    cannot take them: stacks _stack_dims refuses, or tokens whose
    resident buffers do not fit beside the weight tiles."""
    dims = _stack_dims(w_gate, w_in, w_out)
    if dims is None:
        return None
    X, E, F = dims
    Tp = _pad_rows(n_tokens)
    resident = (4 * Tp * E * 2                    # tokens, result: x 2 buffers
                + 2 * Tp * E * 4                  # accumulator, a dot's result
                + 2 * Tp * -(-X // 128) * 128 * 4)  # the combine weights
    return _widest_f_tile(E, F, resident, Tp, _STREAM_VMEM_BUDGET,
                          stacks=2 if w_gate is None else 3)


def grouped_rows(n_tokens: int, top_k: int, n_experts: int) -> int:
    """Rows of the grouped pass's buffer, static and capacity-free:
    every expert's group starts on the 16-row tile, so any routing of
    T x k pairs over X experts (an expert holding anything from 0 to T
    of them) ends under round16(T x k) + 16 X; one row tile more, for
    the last group's last tile to run over into."""
    return _pad_rows(n_tokens * top_k) + 16 * n_experts + _GROUP_ROW_TILE


def grouped_f_tile(n_tokens: int, top_k: int, w_gate, w_in, w_out):
    """The F tile the grouped pass would take for `n_tokens` tokens of
    `top_k` experts each over these stacks, or None where it cannot
    take them: stacks _stack_dims refuses, or a buffer of pairs that
    does not fit VMEM (bf16 rows in, float32 rows out, one copy each)
    beside the weight tiles."""
    dims = _stack_dims(w_gate, w_in, w_out)
    if dims is None:
        return None
    X, E, F = dims
    return _grouped_tile(grouped_rows(n_tokens, top_k, X), E, F)


def _grouped_tile(R: int, E: int, F: int):
    """grouped_f_tile for a buffer of R rows: bf16 in and float32 out,
    one copy each, and a row tile's dot result."""
    resident = R * E * (2 + 4) + _GROUP_ROW_TILE * E * 4
    return _widest_f_tile(E, F, resident, _GROUP_ROW_TILE,
                          _GROUPED_VMEM_BUDGET)


# jitted beside the grouped pass: the routed layers' sorts are one
# lowered function, as their kernels are (no kernel: not counted)
@functools.partial(jax.jit, static_argnums=(1,))
def group_rows(idx, n_experts: int):
    """Where the grouped pass's buffer holds each (token, expert) pair
    of idx [T, k]: the pairs in the order a stable sort by expert gives
    (moe.dropless.sort_by_expert), each expert's run moved up to start
    on the 16-row tile. Returns (row_token [R] int32: the token a
    buffer row holds, 0 in the padding; pair_row [T, k] int32: the row
    that holds the pair; starts [X], counts [X] int32: an expert's
    first row and its pairs).

    No sort runs: a pair's place in its expert's run is the count of
    EARLIER pairs of that expert, a one-hot matrix against a lower
    triangle, 128 pairs at a time, on the MXU (0/1 operands, float32
    sums: exact), which on a v5e saves 75 us a layer at 2,048 pairs and
    143 at 4,096 against the sort and the scatter that inverts it
    (PERF.md section 6, PR 37)."""
    T, k = idx.shape
    A, C = T * k, 128
    flat = jnp.pad(idx.reshape(-1), (0, -A % C), constant_values=n_experts)
    experts = jnp.arange(n_experts, dtype=flat.dtype)
    onehot = (flat[:, None] == experts).astype(jnp.bfloat16)
    chunks = onehot.reshape(-1, C, n_experts)
    lower = jnp.tril(jnp.ones((C, C), jnp.bfloat16), -1)
    within = jnp.einsum("ij,cjx->cix", lower, chunks,
                        preferred_element_type=jnp.float32)
    totals = jnp.sum(chunks, axis=1, dtype=jnp.float32)       # [A / C, X]
    before = within + (jnp.cumsum(totals, axis=0) - totals)[:, None, :]
    counts = jnp.sum(totals, axis=0).astype(jnp.int32)
    padded = _pad_rows(counts)
    starts = jnp.cumsum(padded) - padded
    pos = jnp.sum(chunks.astype(jnp.float32) * (before + starts), axis=-1
                  ).astype(jnp.int32).reshape(-1)[:A]
    row_token = jnp.zeros((grouped_rows(T, k, n_experts),), jnp.int32).at[
        pos].set(jnp.arange(A, dtype=jnp.int32) // k, unique_indices=True)
    return row_token, pos.reshape(T, k), starts, counts


def _stream_kernel(c_ref, h_ref, *refs, act):
    """refs: the weight tiles (gate, up, down; or up, down of a block
    without a gate), the output and the accumulator."""
    *w_refs, wo_ref, o_ref, acc_ref = refs
    x, f = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(x == 0, f == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...]
    # [gate, up] of a gated block, [up] of one without a gate
    first, *up = (jnp.dot(h, w[...], preferred_element_type=jnp.float32)
                  for w in w_refs)
    # this expert's combine column out of the resident [T, X] matrix: a
    # masked lane reduction (a [1, T] row would have to be transposed)
    c = c_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    col = jnp.sum(jnp.where(lane == x, c, 0.0), axis=1, keepdims=True)
    inner = ((act(first) * up[0] if up else act(first)) * col).astype(h.dtype)
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
    tf = stream_f_tile(h.shape[0], w_gate, w_in, w_out)
    assert tf is not None, (h.shape, w_gate.shape, w_gate.dtype)
    return _stream_mlp(h, w_gate, w_in, w_out, wcols, act, tf, interpret())


@kernel_jit(5, 6, 7)
def _stream_mlp(h, w_gate, w_in, w_out, wcols, act, tf: int,
                interpreted: bool):
    return _stream_call("expert_stream", h, (w_gate, w_in), w_out, wcols,
                        act, tf, interpreted)


def _stream_call(name: str, h, w_ins, w_out, wcols, act, tf: int,
                 interpreted: bool):
    """The all-expert pass over the stacks `w_ins` ([X, E, F] each: gate
    and up, or up alone) and w_out [X, F, E], as the kernel `name`."""
    T, E = h.shape
    X, F, _ = w_out.shape
    Tp = _pad_rows(T)
    hp = jnp.pad(h.astype(w_out.dtype), ((0, Tp - T), (0, 0)))
    cols = jnp.pad(wcols.astype(jnp.float32).T, ((0, Tp - T), (0, 0)))
    whole = lambda x, f: (0, 0)
    out = pl.pallas_call(
        functools.partial(_stream_kernel, act=act),
        grid=(X, F // tf),
        in_specs=[
            pl.BlockSpec((Tp, X), whole),
            pl.BlockSpec((Tp, E), whole),
            *(pl.BlockSpec((None, E, tf), lambda x, f: (x, 0, f))
              for _ in w_ins),
            pl.BlockSpec((None, tf, E), lambda x, f: (x, f, 0)),
        ],
        out_specs=pl.BlockSpec((Tp, E), whole),
        out_shape=jax.ShapeDtypeStruct((Tp, E), h.dtype),
        scratch_shapes=[pltpu.VMEM((Tp, E), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STREAM_VMEM_LIMIT),
        interpret=interpreted,
        name=name,
    )(cols, hp, *w_ins, w_out)
    return out[:T]


def expert_stream_ungated_mlp(h, w_in, w_out, wcols, act):
    """expert_stream_mlp for a block without a gate: sum_x
    wcols[x][:, None] * (act(h @ w_in[x]) @ w_out[x]), two stacks
    streamed an expert. The caller asks stream_f_tile (w_gate None)
    first."""
    tf = stream_f_tile(h.shape[0], None, w_in, w_out)
    assert tf is not None, (h.shape, w_in.shape, w_in.dtype)
    return _stream_ungated_mlp(h, w_in, w_out, wcols, act, tf, interpret())


@kernel_jit(4, 5, 6)
def _stream_ungated_mlp(h, w_in, w_out, wcols, act, tf: int,
                        interpreted: bool):
    return _stream_call("expert_stream_ungated", h, (w_in,), w_out, wcols,
                        act, tf, interpreted)


def _grouped_kernel(start_ref, count_ref, xs_ref, wg_ref, wi_ref, wo_ref,
                    o_ref, *, act, rows):
    x, f = pl.program_id(0), pl.program_id(1)
    start = start_ref[x]

    def tile(i, carry):
        at = pl.ds(pl.multiple_of(start + i * rows, 16), rows)
        h = xs_ref[at, :]
        gate = jnp.dot(h, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(h, wi_ref[...], preferred_element_type=jnp.float32)
        inner = (act(gate) * up).astype(h.dtype)
        y = jnp.dot(inner, wo_ref[...], preferred_element_type=jnp.float32)

        # the last tile runs over into rows of LATER experts (or the
        # buffer's tail): their own first F tile, which comes after
        # every F tile of this expert, overwrites what lands there
        @pl.when(f == 0)
        def _first():
            o_ref[at, :] = y

        @pl.when(f != 0)
        def _rest():
            o_ref[at, :] += y

        return carry

    jax.lax.fori_loop(0, pl.cdiv(count_ref[x], rows), tile, 0)


def expert_grouped_mlp(xs, starts, counts, w_gate, w_in, w_out,
                       act=jax.nn.silu):
    """(act(xs[r] @ w_gate[x]) * (xs[r] @ w_in[x])) @ w_out[x] for
    every row r of expert x's window [starts[x], starts[x] + counts[x])
    of xs [R, E] (group_rows' layout, R = grouped_rows) over stacks
    [X, E, F], [X, E, F], [X, F, E] -> [R, E] float32, unweighted.
    Rows outside every window hold nothing meaningful. Operands in the
    stacks' 16-bit type, float32 inside every dot and out. Every
    expert's weights are streamed once whatever its count; its matmuls
    run over its own window alone. The caller asks grouped_f_tile
    first."""
    dims = _stack_dims(w_gate, w_in, w_out)
    assert dims is not None, (xs.shape, w_gate.shape, w_gate.dtype)
    tf = _grouped_tile(xs.shape[0], *dims[1:])
    assert tf is not None, (xs.shape, w_gate.shape)
    return _grouped_mlp(xs, starts, counts, w_gate, w_in, w_out, act, tf,
                        interpret())


@kernel_jit(6, 7, 8)
def _grouped_mlp(xs, starts, counts, w_gate, w_in, w_out, act, tf: int,
                 interpreted: bool):
    (R, E), (X, _, F) = xs.shape, w_gate.shape
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_grouped_kernel, act=act, rows=_GROUP_ROW_TILE),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(X, F // tf),
            in_specs=[
                whole,
                pl.BlockSpec((None, E, tf), lambda x, f, s, c: (x, 0, f)),
                pl.BlockSpec((None, E, tf), lambda x, f, s, c: (x, 0, f)),
                pl.BlockSpec((None, tf, E), lambda x, f, s, c: (x, f, 0)),
            ],
            out_specs=whole),
        out_shape=jax.ShapeDtypeStruct((R, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM_LIMIT),
        interpret=interpreted,
        name="expert_stream_grouped",
    )(starts, counts, xs.astype(w_gate.dtype), w_gate, w_in, w_out)
