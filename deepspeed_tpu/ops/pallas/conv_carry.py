"""The short causal convolution of a state layer over one serving
step's ragged rows: each row's K - 1 predecessors found, the taps
summed, and each sequence's last K - 1 inputs left in its state slot,
in one pass.

Row r is the token at positions[r] of the sequence holding slot
slots[r] (-1: batch padding); rows of one sequence are adjacent and in
order (a RUN: a decode row is a run of one, a prefill chunk a run of
several). The input k places before row r is row r - k where that is
the same sequence's token k places back; else an earlier step left it
in the sequence's slot; before the sequence's first token it is zero,
whatever the slot holds. `carry_facts` says which, `conv_carry` is the
Pallas kernel, and inference/model.py `_carry_rows` + `_depthwise` are
the same thing in XLA (decode_impl 'xla', the CPU, what `carry_fits`
refuses) and the oracle the kernel is tested against.

The pool is [slots, K - 1, E / 128, 128]: a slot is whole (sublane,
lane) tiles, so one copy moves it. (A row of a [slots, (K - 1) E]
array is no such thing on the chip: eight slots share every 2 KB tile
of it, a bf16 row interleaved with its neighbour's in 4-byte words,
and Mosaic refuses to slice one out.) The kernel keeps the pool in
HBM, aliased in and out, and moves live slots alone: a copy in for a
row some of whose predecessors lie before the step, a copy out for a
run's last row, none for a pad row, none in for a run that starts at
position 0. A grid step is a TILE of rows: the next tile's slots are
fetched while this one's rows are summed, and this one's slots are
written while the next one's are. The step's inputs are resident, so a
predecessor in the step is a row index away, across tiles too.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret, kernel_jit

F32 = jnp.float32
LANES = 128
_VMEM_LIMIT = 48 << 20
# what a tile's buffers may take: the fetched and the written slots and
# the output block, two of each
_TILE_BYTES = 8 << 20
_SCALAR_BYTES = 256 << 10


def carry_facts(slots, positions, k1: int):
    """(run, last) [S] of a step's ragged rows: how many rows before
    row r in this step are its own sequence's previous tokens (up to
    k1 = K - 1), and whether row r is its sequence's last of the step,
    the one that leaves the slot."""
    S = slots.shape[0]
    row = jnp.arange(S)
    same = [(jnp.roll(slots, k) == slots)
            & (jnp.roll(positions, k) + k == positions) & (row >= k)
            for k in range(1, k1 + 1)]
    run = jnp.sum(jnp.cumprod(jnp.stack(same).astype(jnp.int32), axis=0),
                  axis=0)
    last = jnp.roll(slots, -1) != slots
    return run, last.at[S - 1].set(True)


def _row_bytes(width: int, k1: int, itemsize: int) -> int:
    """VMEM a row of a tile takes: its slot fetched and written, its
    float32 output; two of each."""
    return 4 * k1 * width * itemsize + 2 * width * 4


def _tile_rows(n_rows: int, width: int, k1: int, itemsize: int) -> int:
    """Rows a grid step: the most that divide the step and whose
    buffers fit _TILE_BYTES."""
    cap = max(1, _TILE_BYTES // _row_bytes(width, k1, itemsize))
    return next(r for r in range(min(cap, n_rows), 0, -1) if n_rows % r == 0)


def carry_fits(n_rows: int, dtype, pool) -> bool:
    """Whether the kernel takes a step of n_rows rows of `dtype` inputs
    over this pool: whole lanes a slot, its lane rows whole (8, 128)
    tiles where they are more than one (Mosaic refuses the slot's copy
    otherwise: `TransformerConfig.state_shapes` pads them), slots in
    the inputs' own dtype (a slot's rows are copied, never converted),
    the step's inputs and a tile's buffers inside the kernel's VMEM,
    the rows' facts in scalar memory."""
    if pool.ndim != 4 or pool.shape[-1] != LANES or pool.dtype != dtype:
        return False
    _, k1, C, _ = pool.shape
    if C > 8 and C % 8:
        return False
    width, itemsize = C * LANES, jnp.dtype(dtype).itemsize
    # the inputs and the taps, double-buffered though fetched once
    resident = 2 * n_rows * width * itemsize + 2 * (k1 + 1) * width * 4
    tile = _tile_rows(n_rows, width, k1, itemsize) * _row_bytes(
        width, k1, itemsize)
    # 4 MB left to the compiler's own temporaries
    return (resident + tile <= _VMEM_LIMIT - (4 << 20)
            and 4 * n_rows * 4 <= _SCALAR_BYTES)


def _kernel(slot_ref, run_ref, pos_ref, move_ref, u_ref, taps_ref, pool_in,
            out_ref, pool_out, held, left, rsem, wsem, *, rows: int, k1: int):
    """One tile of rows. held / left [2, rows, k1, C, 128]: the slots
    fetched for a tile's rows and the slots its rows leave, by the
    tile's parity; move_ref: 1 = the row's slot is read, 2 = written."""
    i, n = pl.program_id(0), pl.num_programs(0)
    p = i % 2

    def copies(tile, par, bit, wait=False):
        """Start (or wait for) the slot copies of a tile's rows: bit 1
        into `held`, bit 2 out of `left`."""
        def one(rl, c):
            r = tile * rows + rl

            @pl.when(move_ref[r] & bit != 0)
            def _():
                slot = slot_ref[r]
                cp = (pltpu.make_async_copy(
                    pool_in.at[slot], held.at[par, rl], rsem.at[par])
                    if bit == 1 else pltpu.make_async_copy(
                        left.at[par, rl], pool_out.at[slot], wsem.at[par]))
                cp.wait() if wait else cp.start()
            return c
        jax.lax.fori_loop(0, rows, one, 0)

    fetch = functools.partial(copies, bit=1)
    leave = functools.partial(copies, bit=2)

    pl.when(i == 0)(lambda: fetch(0, 0))
    pl.when(i + 1 < n)(lambda: fetch(i + 1, 1 - p))
    fetch(i, p, wait=True)

    def row(rl, c):
        r = i * rows + rl
        run, pos = run_ref[r], pos_ref[r]
        acc = None
        for j in range(k1):  # oldest first: the input k places back
            k = k1 - j
            x = jnp.where(run >= k, u_ref[jnp.maximum(r - k, 0)],
                          held[p, rl, jnp.minimum(j + run, k1 - 1)])
            x = jnp.where(pos >= k, x, jnp.zeros_like(x))
            if j:
                left[p, rl, j - 1] = x
            term = x.astype(F32) * taps_ref[j]
            acc = term if acc is None else acc + term
        x = u_ref[r]
        left[p, rl, k1 - 1] = x
        out_ref[rl] = acc + x.astype(F32) * taps_ref[k1]
        return c

    jax.lax.fori_loop(0, rows, row, 0)
    leave(i, p)
    # the tile before wrote while this one was summed; its buffer is
    # the next tile's
    pl.when(i > 0)(lambda: leave(i - 1, 1 - p, wait=True))
    pl.when(i == n - 1)(lambda: leave(i, p, wait=True))


def conv_carry(u, taps, pool, slots, positions):
    """u [S, E] the step's inputs; taps [E, K], oldest first; pool
    [slots, K - 1, E / 128, 128] in u's dtype; slots, positions [S]
    int32 -> (sum_j taps[:, j] * input_{t-(K-1)+j} [S, E] float32,
    products and sum in float32 in that order; the pool with each
    run's last K - 1 inputs in its sequence's slot). A pad row's
    predecessors are zeros and it writes nothing."""
    rows = _tile_rows(*u.shape, pool.shape[1], u.dtype.itemsize)
    return _conv_carry(u, taps, pool, slots, positions, rows, interpret())


# nine or ten state layers call it with the same shapes
@kernel_jit(5, 6)
def _conv_carry(u, taps, pool, slots, positions, rows: int, interpreted: bool):
    S, E = u.shape
    _, k1, C, L = pool.shape
    run, last = carry_facts(slots, positions, k1)
    live = slots >= 0
    pos = jnp.where(live, jnp.minimum(positions, k1), 0)
    # a slot is read where some predecessor lies before the step's rows
    move = (live & (run < k1) & (pos > run)) + 2 * (live & last)
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S // rows,),
        in_specs=[whole(S, C, L), whole(k1 + 1, C, L),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((rows, C, L), lambda i, *_: (i, 0, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((2, rows, k1, C, L), pool.dtype),
                        pltpu.VMEM((2, rows, k1, C, L), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    i32 = lambda a: a.astype(jnp.int32)
    out, pool = pl.pallas_call(
        functools.partial(_kernel, rows=rows, k1=k1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, C, L), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (after the four prefetched scalars, u, taps): the pool
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpreted,
        name="conv_carry",
    )(i32(jnp.maximum(slots, 0)), i32(run), i32(pos), i32(move),
      u.reshape(S, C, L), taps.astype(F32).T.reshape(k1 + 1, C, L), pool)
    return out.reshape(S, E), pool
