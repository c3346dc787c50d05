"""Rows summed to their tokens with no scatter: the combine of the held
routed wire (moe/dropless.py sum_to_tokens), as a banded one-hot product
on the MXU.

The rows arrive IN TOKEN ORDER (`ids` [C] ascending; an id >= n_tokens
is a dead row, and those sort behind every token), so the rows of token
tile j (TOKEN_TILE tokens) are one contiguous range, and

    out[tile j] = sum over the row blocks b that touch it of
                  onehot(ids[b] - j TOKEN_TILE) [TB x RB] @ rows[b] [RB x E]

float32 across the row blocks, ONE rounding to the rows' dtype at the
end (on a v5e XLA's bf16 scatter-add reads the same bits: PERF.md
section 6, PR 61). A token tile and the row blocks that hold its rows
make at most C / RB + T / TB - 1 (block, tile) pairs (`pairs_bound`: consecutive tiles share at most the
block their edge lies in, and a tile with no row is visited once, so a
token with no row reads zero): the grouped-matmul pattern, one grid step
a pair from a scalar-prefetched schedule (`_schedule`: the block, the
tile, and whether the step is the tile's first, its last, or holds no
row), every block aligned, no copy of the kernel's own. The schedule
comes from the sorted ids by comparisons (how many ids lie under each
tile's edge), never by a gather over the rows. Consecutive steps of one
block do not fetch it again, so every live row is read once.

A dead row holds whatever a grouped product left there (dropless.py
_held_wire): it is SELECTED to zero on the VMEM tile, by its position
(the dead sort last), since a one-hot weight of 0 would turn its Inf
into a NaN of every token of the tile.

`token_tile_sum` runs the kernel where kernels run
(ops.pallas.kernels_runnable); elsewhere the sum is XLA's own sorted
segment sum, the kernel's oracle (tests/test_dropless.py). On a v5e at
[32,768 x 2,048] bf16 -> [16,384 x 2,048]: PERF.md section 6, PR 61.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret, kernel_jit, kernels_runnable

ROW_BLOCK = 256    # rows of a block the product contracts over
TOKEN_TILE = 256   # tokens of an output tile
_E_TILE = 2048     # widest slab of E a step holds (rows, out, f32 sum)
_VMEM_LIMIT = 48 << 20

_FIRST, _LAST, _ROWS = 1, 2, 4  # a step's flags


def pairs_bound(n_rows: int, n_tokens: int, row_block: int = ROW_BLOCK,
                token_tile: int = TOKEN_TILE) -> int:
    """(row block, token tile) pairs a sum visits at most: the grid."""
    return pl.cdiv(n_rows, row_block) + pl.cdiv(n_tokens, token_tile) - 1


def _schedule(ids, n_tokens: int, nb: int, nt: int, rb: int, tb: int):
    """The walk over (block, tile) pairs of sorted `ids` [nb rb]: blk,
    tile, flags [nb + nt - 1] and the live rows [1]. Tile j's rows are
    [lo_j, hi_j), the ids under its two edges; it visits the blocks they
    lie in, or, with no row, the one block its edge lies in (nothing is
    multiplied there). Steps past the last pair repeat it with no flag:
    nothing is fetched, nothing written."""
    i32 = jnp.int32
    edges = jnp.minimum(jnp.arange(nt + 1, dtype=i32) * tb, n_tokens)
    under = (ids[:, None] < edges).sum(0, dtype=i32)  # [nt + 1]
    lo, hi = under[:-1], under[1:]
    first = jnp.minimum(lo // rb, nb - 1)
    last = jnp.where(hi > lo, (hi - 1) // rb, first)
    visits = last - first + 1
    ends = jnp.cumsum(visits)
    i = jnp.arange(nb + nt - 1, dtype=i32)
    tile = jnp.minimum((i[:, None] >= ends).sum(1, dtype=i32), nt - 1)
    hit = tile[:, None] == jnp.arange(nt, dtype=i32)
    at = lambda v: jnp.where(hit, v, 0).sum(1, dtype=i32)  # v[tile]
    step = i - at(ends - visits)
    blk = jnp.minimum(at(first) + step, at(last))
    flags = jnp.where(
        i < ends[-1],
        _FIRST * (step == 0) + _LAST * (step == at(visits) - 1)
        + _ROWS * (at(hi) > at(lo)), 0).astype(i32)
    return blk, tile, flags, under[-1:]


def _onehot_sum(ids, rows, first_row, first_token, n_live, tb: int):
    """What the rows of one block add to one tile: ids [1, RB], rows
    [RB, E] -> [TB, E] float32."""
    rb = rows.shape[0]
    at = first_row + jax.lax.broadcasted_iota(jnp.int32, (rb, 1), 0)
    rows = jnp.where(at < n_live, rows, 0)
    token = first_token + jax.lax.broadcasted_iota(jnp.int32, (tb, rb), 0)
    return jnp.dot(
        (ids == token).astype(rows.dtype), rows,
        preferred_element_type=jnp.float32,
        # the one-hot is exact in any type; float32 rows must not be
        # rounded to the MXU's bf16 on their way through
        precision=(jax.lax.Precision.HIGHEST
                   if rows.dtype == jnp.float32 else None))


def _kernel(blk_ref, tile_ref, flag_ref, live_ref, ids_ref, rows_ref, o_ref,
            acc_ref, *, rb: int, tb: int):
    i = pl.program_id(1)
    flags = flag_ref[i]

    @pl.when(flags & _FIRST != 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(flags & _ROWS != 0)
    def _add():
        acc_ref[...] += _onehot_sum(ids_ref[...], rows_ref[...],
                                    blk_ref[i] * rb, tile_ref[i] * tb,
                                    live_ref[0], tb)

    @pl.when(flags & _LAST != 0)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _e_tile(E: int) -> int:
    """The slab of E a step holds: E, or its widest divisor of whole
    lane tiles under _E_TILE."""
    if E <= _E_TILE:
        return E
    return max((t for t in range(128, _E_TILE + 1, 128) if E % t == 0),
               default=E)


def _padded(rows_t, ids_t, n_tokens: int, rb: int, tb: int):
    C = rows_t.shape[0]
    nb, nt = pl.cdiv(C, rb), pl.cdiv(n_tokens, tb)
    rows = jnp.pad(rows_t, ((0, nb * rb - C), (0, 0)))
    ids = jnp.pad(ids_t.astype(jnp.int32), (0, nb * rb - C),
                  constant_values=n_tokens)
    return rows, ids, nb, nt


def token_tile_sum(rows_t, ids_t, n_tokens: int):
    """out[t] = the sum of the rows r with ids_t[r] == t, rows_t [C, E]
    in token order (ids_t [C] int32 ascending, >= n_tokens a dead row,
    whatever it holds) -> [n_tokens, E] in the rows' dtype, float32
    inside."""
    if kernels_runnable():
        return _tile_sum(rows_t, ids_t, n_tokens, ROW_BLOCK, TOKEN_TILE,
                         interpret())
    return jax.ops.segment_sum(
        jnp.where((ids_t < n_tokens)[:, None], rows_t, 0).astype(jnp.float32),
        ids_t, num_segments=n_tokens,
        indices_are_sorted=True).astype(rows_t.dtype)


@kernel_jit(2, 3, 4, 5)
def _tile_sum(rows_t, ids_t, n_tokens: int, rb: int, tb: int,
              interpreted: bool):
    E = rows_t.shape[1]
    rows, ids, nb, nt = _padded(rows_t, ids_t, n_tokens, rb, tb)
    te = _e_tile(E)
    out = pl.pallas_call(
        functools.partial(_kernel, rb=rb, tb=tb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(E // te, nb + nt - 1),
            in_specs=[
                pl.BlockSpec((None, 1, rb),
                             lambda e, i, blk, tile, flag, live:
                             (blk[i], 0, 0)),
                pl.BlockSpec((rb, te),
                             lambda e, i, blk, tile, flag, live:
                             (blk[i], e)),
            ],
            out_specs=pl.BlockSpec(
                (tb, te), lambda e, i, blk, tile, flag, live: (tile[i], e)),
            scratch_shapes=[pltpu.VMEM((tb, te), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nt * tb, E), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpreted,
        name="token_tile_sum",
    )(*_schedule(ids, n_tokens, nb, nt, rb, tb), ids.reshape(nb, 1, rb),
      rows)
    return out[:n_tokens]
