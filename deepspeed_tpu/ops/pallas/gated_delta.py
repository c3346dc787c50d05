"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464):
the state a linear-attention head carries and the two ways serving
advances it.

A value head carries S in R^{Dk x Dv}, float32. For each token, with
its head's q and k (L2-normalised, q scaled by Dk^-0.5), v, the log
decay g <= 0 and the write strength beta in (0, 1), or in (0, 2) where
the model lets the state's eigenvalues go negative (beta = 2 sigmoid:
I - beta k k^T then reflects along k):

    S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

- `gated_delta_step`: one serving step over ragged rows, the Pallas
  kernel `gdn_state`. Row t is one token; rows of one sequence are
  adjacent and in order (a RUN: a decode row is a run of one, a prefill
  chunk a run of several), so a step is a segmented recurrence. A
  run's state is fetched from its sequence's slot of the pool
  [slots + 1, H, Dk, Dv] (heads of Dv that are no whole lane tiles
  stand side by side, `pack_heads`: [slots + 1, H / 2, 96, 384] for
  heads of 96 x 192, so that a slot is whole tiles and moves its own
  bytes and no padding; zeros at position 0, whatever the slot holds), updated in place in ONE VMEM buffer by the run's rows, and
  written to the slot once, after its last row. The pool stays in HBM,
  aliased in and out, and the kernel issues the copies itself
  (`state_step_call`, the walk this kernel shares with ssm_state.py's):
  what moves is each live sequence's 4 x H x Dk x Dv bytes in and out,
  never the pool. The pool's LAST slot belongs to no sequence: it is
  the pad rows', which neither read nor write it.
- `gated_delta_chunked`: a whole prompt, the chunked form: algebra on
  the recurrence (the paper's WY representation), C tokens at a time as
  matmuls. With G_i = g_1 + ... + g_i inside the chunk, D_ij =
  exp(G_i - G_j) for i >= j (never above 1) and S_0 the state before
  it: A = -strict_tril((diag(beta) K) K^T . D); T = (I - A)^-1;
  U = T diag(beta) V; W = T diag(beta) (K . exp(G)); V' = U - W S_0;
  O = (Q . exp(G)) S_0 + tril((Q K^T) . D) V';
  S_C = exp(G_C) S_0 + (K . exp(G_C - G))^T V'. A is strictly lower
  triangular, so U and W are ONE triangular solve (forward
  substitution) of I - A: stable whatever the keys, where the product
  (I + A)(I + A^2)(I + A^4)... passes through powers of A that
  near-parallel keys at beta near 2 take to 1e27 before they cancel.
- `gated_delta_recurrent`: the recurrence itself as a `lax.scan`, the
  oracle both are tested against; `gated_delta_step_xla` is the step
  over rows without a kernel (decode_impl 'xla', the CPU).

Everything here is float32: the state is what a sequence IS in such a
layer, and every token rewrites all of it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret, kernel_jit

F32 = jnp.float32
# the published chunk of the family's kernels
CHUNK = 64


def gated_delta_recurrent(q, k, v, g, beta, state=None):
    """q, k [B, T, H, Dk], v [B, T, H, Dv], g, beta [B, T, H] float32,
    state [B, H, Dk, Dv] or None (zeros) -> (o [B, T, H, Dv], the state
    after the last token). Token by token."""
    B, _, H, Dk = q.shape
    if state is None:
        state = jnp.zeros((B, H, Dk, v.shape[-1]), F32)

    def token(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None, None]
        m = jnp.einsum("bhkv,bhk->bhv", S, kt, precision="highest")
        d = bt[..., None] * (vt - m)
        S = S + kt[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision="highest")

    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(token, state.astype(F32), xs)
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_chunked(q, k, v, g, beta, state=None, chunk: int = CHUNK):
    """gated_delta_recurrent's arguments and results, `chunk` tokens at
    a time (module docstring). T need not be a multiple of the chunk:
    the tail is padded with tokens that leave the state as it is
    (g = 0, beta = 0, k = 0)."""
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = min(chunk, max(T, 1))
    N = -(-T // C)

    def chunks(a):  # [B, T, H, ...] -> [N, B, H, C, ...]
        a = jnp.pad(a.astype(F32), [(0, 0), (0, N * C - T)]
                    + [(0, 0)] * (a.ndim - 2))
        a = a.reshape(B, N, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    if state is None:
        state = jnp.zeros((B, H, Dk, Dv), F32)
    mm = functools.partial(jnp.einsum, precision="highest")
    row = jnp.arange(C)
    lower = row[:, None] >= row[None, :]
    strict = row[:, None] > row[None, :]
    eye = jnp.eye(C, dtype=F32)

    def one(S, x):
        Q, K, V, g_, b_ = x  # [B, H, C, D], [B, H, C]
        G = jnp.cumsum(g_, axis=-1)
        # exp(G_i - G_j) where i >= j: at most 1, so no overflow
        D = jnp.where(lower, jnp.exp(jnp.where(
            lower, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
        Kb = K * b_[..., None]
        A = -jnp.where(strict, mm("bhik,bhjk->bhij", Kb, K) * D, 0.0)
        # T [diag(beta) V, diag(beta) (K . exp(G))] by forward
        # substitution (I - A is unit lower triangular): the product
        # (I + A)(I + A^2)(I + A^4)... is the same matrix but passes
        # through powers of A, which near-parallel keys at beta up to 2
        # (A's entries up to 2) take to 1e27 before they cancel
        eG = jnp.exp(G)[..., None]
        UW = jax.lax.linalg.triangular_solve(
            eye - A, jnp.concatenate([V * b_[..., None], Kb * eG], axis=-1),
            left_side=True, lower=True, unit_diagonal=True)
        U, W = UW[..., :Dv], UW[..., Dv:]
        Vp = U - mm("bhik,bhkv->bhiv", W, S)
        O = mm("bhik,bhkv->bhiv", Q * eG, S) + mm(
            "bhij,bhjv->bhiv", jnp.where(
                lower, mm("bhik,bhjk->bhij", Q, K) * D, 0.0), Vp)
        last = G[..., -1:]
        S = jnp.exp(last)[..., None] * S + mm(
            "bhik,bhiv->bhkv", K * jnp.exp(last - G)[..., None], Vp)
        return S, O

    state, o = jax.lax.scan(one, state.astype(F32),
                            tuple(chunks(a) for a in (q, k, v, g, beta)))
    # [N, B, H, C, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        B, N * C, H, Dv)
    return o[:, :T], state


def pack_heads(state, pack: int):
    """[..., H, Dk, Dv] -> the pool's layout [..., H / pack, Dk, pack Dv]:
    `pack` heads side by side in a lane row (TransformerConfig.gdn_pack:
    heads of 192 values in pairs, whole lane tiles; one head a row where
    its values are whole tiles already)."""
    if pack == 1:
        return state
    *lead, H, Dk, Dv = state.shape
    s = state.reshape(*lead, H // pack, pack, Dk, Dv)
    return jnp.moveaxis(s, -3, -2).reshape(*lead, H // pack, Dk, pack * Dv)


def unpack_heads(packed, pack: int):
    """pack_heads' inverse: [..., H / pack, Dk, pack Dv] -> [..., H, Dk,
    Dv]."""
    if pack == 1:
        return packed
    *lead, Hp, Dk, PP = packed.shape
    s = packed.reshape(*lead, Hp, Dk, pack, PP // pack)
    return jnp.moveaxis(s, -2, -3).reshape(*lead, Hp * pack, Dk, PP // pack)


def run_starts(slots, positions):
    """(first, fresh) [S] bool of a step's ragged rows: whether row t
    is the first of its run (the row before it is not the same
    sequence's previous token), and whether its sequence's state
    starts from zero there (position 0, or a pad row)."""
    same = (jnp.roll(slots, 1) == slots) & (
        jnp.roll(positions, 1) + 1 == positions)
    first = ~same.at[0].set(False) | (slots < 0)
    return first, first & ((positions == 0) | (slots < 0))


def gated_delta_step_xla(q, k, v, g, beta, pool, slots, positions):
    """gated_delta_step without a kernel: a loop over the rows, each
    reading its sequence's slot (an earlier row of its run has written
    it) and writing it back."""
    S_rows = q.shape[0]
    pad = pool.shape[0] - 1
    pack = q.shape[1] // pool.shape[1]
    _, fresh = run_starts(slots, positions)
    where = jnp.where(slots < 0, pad, slots)

    def row(t, carry):
        pool, out = carry
        S = jnp.where(fresh[t], 0.0, unpack_heads(pool[where[t]], pack))
        o, S = gated_delta_recurrent(
            q[t][None, None], k[t][None, None], v[t][None, None],
            g[t][None, None], beta[t][None, None], S[None])
        return (pool.at[where[t]].set(pack_heads(S[0], pack)),
                out.at[t].set(o[0, 0]))

    pool, out = jax.lax.fori_loop(
        0, S_rows, row, (pool, jnp.zeros(v.shape, F32)))
    return out, pool


def run_flags(slots, positions, pool):
    """(where, flags) [S] int32 of a step's ragged rows over a pool
    [slots + 1, ...]: the slot each row's state lives in (the LAST for
    a pad row), and where its state comes from (1: the first row of
    its run, from its slot; 3: that and a zero state; 0: the row
    before)."""
    first, fresh = run_starts(slots, positions)
    where = jnp.where(slots < 0, pool.shape[0] - 1, slots).astype(jnp.int32)
    return where, first.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)


# VMEM the walk's slots may take (the rest of the limit: the rows'
# blocks, double-buffered, and the compiler's own temporaries), the
# most runs a batch, the runs of several rows that can be held aside,
# the rows of a grid step
_STEP_VMEM_LIMIT = 64 << 20
_SLOTS_VMEM = 48 << 20
_MAX_BATCH = 8
_MAX_ASIDE = 4
_TILE_ROWS = 8
# bits of a row's flag beside run_flags' two (1: the first row of its
# run, 2: from a zero state): the step holds two runs of one slot, the
# row is the first of its batch, the one after which the batch's copies
# change hands, a row of a run held aside; of a run's: it reads its
# slot, it writes it
_SAFE, _BATCH, _HAND, _ASIDE = 4, 8, 16, 32
_READS, _WRITES = 1, 2


def walk_shape(pool_shape):
    """(batch, aside) of the walk over a pool [slots + 1, ...] float32:
    the runs a batch and the runs of several rows it can hold aside,
    two batches' and those runs' whole slots inside _SLOTS_VMEM; a
    batch of 0 where two slots do not fit."""
    slot = 4
    for d in pool_shape[1:]:
        slot *= d
    fit = _SLOTS_VMEM // slot
    aside = min(_MAX_ASIDE, max(0, fit - 2))
    return min(_MAX_BATCH, (fit - aside) // 2), aside


def walk_plan(slots, positions, pool, batch: int, aside: int, tile: int):
    """The scalars of the walk over a step's ragged rows (int32). By
    row [S]: its flag (run_flags' two bits, _SAFE, _BATCH, _HAND,
    _ASIDE), the VMEM buffer its run's state is in, its run's
    number among the batched runs. By batched run [S] and by run held
    aside [aside]: its slot (the LAST for a pad row's) and whether it
    reads and writes it (_READS, _WRITES). By grid step [S / tile]:
    the tile whose rows held aside it computes. And (the tiles that
    hold such rows, the last batch's number).

    Runs of ONE row (decode rows, pad rows) are batched `batch` at a
    time in their order, and a batch's copies change hands (its
    predecessor's write-backs awaited, its successor's fetches issued)
    after half its rows. Runs of SEVERAL rows (prefill chunks), where
    the step has `aside` of them at most and some run of one row
    beside them, are held aside: each has a buffer of its own from the
    walk's first copy to its last, and their rows are computed a tile
    a grid step FROM THE FIRST GRID STEP ON, whatever their place in
    the step, under the batches' copies, which is the only time the
    chip has to spare: computed in their place (the scheduler puts
    them last) nothing is left to hide them under. With more such
    runs, or none of one row, every run is batched. Where two runs
    live in ONE slot (the scheduler gives a sequence one run a step,
    so in no served step) every row is _SAFE, nothing is held aside
    and a batch is one run."""
    S = slots.shape[0]
    i32 = lambda a: a.astype(jnp.int32)
    rows = jnp.arange(S)
    where, flags = run_flags(slots, positions, pool)
    first, live = (flags & 1) == 1, slots >= 0
    starts = first & live
    safe = jnp.any((where[:, None] == where[None, :]) & starts[:, None]
                   & starts[None, :] & (rows[:, None] != rows[None, :]))
    several = ~(first & jnp.roll(first, -1).at[S - 1].set(True))
    held = several & ~safe & jnp.any(~several) & (
        jnp.sum(i32(first & several)) <= aside)
    # the batched runs, and their batches' edges among the rows not
    # held aside
    per = jnp.where(safe, 1, batch)
    brun = jnp.cumsum(i32(first & ~held)) - 1
    opens = first & ~held & (brun % per == 0)
    among = jnp.cumsum(i32(~held)) - 1
    nth = among - jax.lax.cummax(jnp.where(opens, among, 0))
    after = jax.lax.cummin(jnp.concatenate(
        [jnp.where(held, S, rows)[1:], jnp.full((1,), S)]), reverse=True)
    closes = ~held & ((after == S) | opens[jnp.minimum(after, S - 1)])
    hand = max(1, batch // 2)
    hands = ~safe & ~held & ((nth == hand - 1) | (closes & (nth < hand - 1)))
    arun = jnp.cumsum(i32(first & held)) - 1
    buf = jnp.where(held, 2 * batch + arun,
                    (brun // per % 2) * batch + brun % per)
    flags = (flags + _SAFE * i32(safe) + _BATCH * i32(opens)
             + _HAND * i32(hands) + _ASIDE * i32(held))
    # by run: what its first row says, picked by a one-hot [runs, rows]
    pick = lambda firsts, number, n: lambda a: jnp.sum(jnp.where(
        firsts[None, :] & (number[None, :] == jnp.arange(n)[:, None]),
        i32(a)[None, :], 0), axis=1)
    bits = lambda of: _READS * of((flags & 3) == 1) + _WRITES * of(live)
    of_brun = pick(first & ~held, brun, S)
    of_arun = pick(first & held, arun, max(aside, 1))
    # grid step g computes the rows held aside of the g-th tile that
    # has some (the last such tile again where there are no more: an
    # output block is never come back to)
    G = S // tile
    has = jnp.any(held.reshape(G, tile), axis=1)
    nth_tile = jnp.cumsum(i32(has)) - 1
    tiles = jnp.sum(jnp.where(
        has[None, :] & (nth_tile[None, :] == jnp.minimum(
            jnp.arange(G), nth_tile[G - 1])[:, None]),
        jnp.arange(G)[None, :], 0), axis=1)
    return (flags, i32(buf), i32(brun), of_brun(where), bits(of_brun),
            of_arun(where), bits(of_arun), i32(tiles),
            jnp.stack([nth_tile[G - 1] + 1, brun[S - 1] // per]))


def _walk_kernel(body, batch: int, aside: int, tile: int, n_scalars: int,
                 n_rows: int, n_consts: int = 0):
    """The kernel of state_step_call around a row's `body`: a grid
    step is `tile` rows."""
    def kernel(flag_ref, buf_ref, brun_ref, bslot_ref, bbits_ref, aslot_ref,
               abits_ref, tiles_ref, ends_ref, *refs):
        scalars, refs = refs[:n_scalars], refs[n_scalars:]
        ins, ins_aside = refs[:n_rows], refs[n_rows:2 * n_rows]
        consts = refs[2 * n_rows:2 * n_rows + n_consts]
        _, o_ref, o_aside, pool_out, held, rsem, wsem = refs[
            2 * n_rows + n_consts:]
        n = flag_ref.shape[0]
        g, steps = pl.program_id(0), pl.num_programs(0)
        safe = (flag_ref[0] & _SAFE) != 0
        per = jnp.where(safe, 1, batch)

        # (the aliased output IS the pool: a slot an earlier run of
        # this step wrote is read as that run left it)
        fetch = lambda slot, buf: pltpu.make_async_copy(
            pool_out.at[slot], held.at[buf], rsem.at[buf])
        leave = lambda slot, buf: pltpu.make_async_copy(
            held.at[buf], pool_out.at[slot], wsem.at[buf])
        start, wait = lambda c: c.start(), lambda c: c.wait()

        def copies(n_runs, run_of, bit, copy, act):
            """Start or wait for (`act`) the fetches or write-backs
            (`copy`) of those of `n_runs` runs that have `bit`;
            run_of(k) -> (the run's bits, its slot, its buffer)."""
            def one(k, c):
                bits, slot, buf = run_of(k)
                pl.when((bits & bit) != 0)(lambda: act(copy(slot, buf)))
                return c
            jax.lax.fori_loop(0, n_runs, one, 0)

        def of_batch(of):
            def run_of(k):
                run = jnp.clip(of * per + k, 0, n - 1)
                there = (of >= 0) & (of * per + k < n)
                return (jnp.where(there, bbits_ref[run], 0), bslot_ref[run],
                        (jnp.maximum(of, 0) % 2) * batch + k)
            return per, run_of

        held_aside = aside, lambda a: (abits_ref[a], aslot_ref[a],
                                       2 * batch + a)

        def compute(i, t, ins, o_ref):
            flag, b = flag_ref[t], buf_ref[t]

            def after(h, S):
                held[b, h] = S

            zero = lambda h: jnp.zeros(held.shape[2:], F32)
            state = lambda heads: (
                pl.when((flag & 2) != 0)(lambda: heads(zero, after)),
                pl.when((flag & 2) == 0)(lambda: heads(
                    lambda h: held[b, h], after)))
            body(t, i, *scalars, *ins, *consts, o_ref, state)

        def own_row(i, t):
            flag = flag_ref[t]
            j = brun_ref[t] // per

            @pl.when((flag & _BATCH) != 0)
            def _():
                @pl.when(safe)
                def _():
                    copies(*of_batch(j - 1), _WRITES, leave, start)
                    copies(*of_batch(j - 1), _WRITES, leave, wait)
                    copies(*of_batch(j), _READS, fetch, start)
                copies(*of_batch(j), _READS, fetch, wait)
                # reads and writes apart: in flight together they move
                # at the writes' rate, the lesser
                pl.when(~safe)(lambda: copies(
                    *of_batch(j - 1), _WRITES, leave, start))

            compute(i, t, ins, o_ref)

            @pl.when((flag & _HAND) != 0)
            def _():
                copies(*of_batch(j - 1), _WRITES, leave, wait)
                copies(*of_batch(j + 1), _READS, fetch, start)

        def aside_row(i, t):
            pl.when((flag_ref[t] & 3) == 1)(
                lambda: fetch(0, buf_ref[t]).wait())
            compute(i, t, ins_aside, o_aside)

        def row(i, c):
            # a row of this step's tile, then one held aside: the
            # second kind spread evenly between the batches' edges
            own, other = g * tile + i, tiles_ref[g] * tile + i
            pl.when((flag_ref[own] & _ASIDE) == 0)(lambda: own_row(i, own))
            pl.when((g < ends_ref[0]) & ((flag_ref[other] & _ASIDE) != 0))(
                lambda: aside_row(i, other))
            return c

        @pl.when(g == 0)
        def _():
            pl.when(~safe)(lambda: copies(*of_batch(0), _READS, fetch, start))
            copies(*held_aside, _READS, fetch, start)

        jax.lax.fori_loop(0, tile, row, 0)

        @pl.when(g == steps - 1)
        def _():
            last = of_batch(ends_ref[1])
            copies(*last, _WRITES, leave, start)
            copies(*held_aside, _WRITES, leave, start)
            copies(*last, _WRITES, leave, wait)
            copies(*held_aside, _WRITES, leave, wait)

    return kernel


def state_step_call(body, name: str, out_row, pool, slots, positions,
                    shape, interpreted: bool, scalars=(), rows=(),
                    consts=()):
    """The walk every matrix-state step kernel shares (this file's
    `gdn_state`, ops/pallas/ssm_state.py's `ssm_state`). A grid step is
    a tile of the step's ragged rows; its block of each of `rows`
    [S, ...] comes in, its block of the output [S, *out_row] goes out,
    both by Pallas's pipeline (a row at a time the few KB of a row
    queue behind the slots' megabytes and arrive microseconds late, a
    wait a row). The pool [slots + 1, ...] float32 stays in HBM,
    aliased in and out, and the kernel moves live slots alone, by its
    own copies, a batch of runs (walk_shape) at a time into one of two
    sets of VMEM buffers, READS AND WRITES APART: batch j's fetches
    are awaited, then batch j - 1's write-backs issued; batch j's rows
    update their runs' states in place in their buffers meanwhile;
    after half of them those write-backs are awaited and batch
    j + 1's fetches issued into the buffers they left (walk_plan).
    The chip moves reads alone at ~700 GB/s and writes alone at ~630,
    and both in flight together at ~640 in all, however many: apart
    they reach ~680 (PERF.md section 6, PR 45). The rows of a few runs
    of several rows are computed under those copies, from blocks of
    their own (the second set of `rows`' blocks and of the output's).
    A run that starts at position 0 and a pad row fetch nothing; a pad
    row writes nothing. `consts` are arrays every row reads whole (a
    layer's rates): one block each, fetched once and kept in VMEM.
    `body(t, i, *scalars' refs, *rows' refs, *consts' refs,
    o_ref, state)` computes row t, row i of its blocks:
    `state(heads)` runs `heads(before, after)`, before(h) matrix h of
    the run's state so far, after(h, S) what the row leaves of it.
    `scalars` are flat arrays for scalar memory, `shape` is
    walk_shape's of the pool. -> (out [S, *out_row] float32, the
    pool)."""
    batch, aside = shape
    S_rows = slots.shape[0]
    tile = next(r for r in range(min(_TILE_ROWS, S_rows), 0, -1)
                if S_rows % r == 0)
    walk = walk_plan(slots, positions, pool, batch, aside, tile)
    n_walk = len(walk)
    # a tile of an array [S, *shape]: grid step g's own, and the one whose
    # rows held aside it computes (the walk's `tiles`, prefetched)
    own = lambda *shape: pl.BlockSpec(
        (tile, *shape), lambda g, *refs: (g,) + (0,) * len(shape))
    aside_of = lambda *shape: pl.BlockSpec(
        (tile, *shape),
        lambda g, *refs: (refs[n_walk - 2][g],) + (0,) * len(shape))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    n_scalars = n_walk + len(scalars)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(S_rows // tile,),
        in_specs=[*(own(*a.shape[1:]) for a in rows),
                  *(aside_of(*a.shape[1:]) for a in rows),
                  *(pl.BlockSpec(a.shape, lambda g, *refs, n=a.ndim: (0,) * n)
                    for a in consts), hbm],
        out_specs=[own(*out_row), aside_of(*out_row), hbm],
        scratch_shapes=[
            pltpu.VMEM((2 * batch + aside, *pool.shape[1:]), F32),
            pltpu.SemaphoreType.DMA((2 * batch + aside,)),
            pltpu.SemaphoreType.DMA((2 * batch + aside,))],
    )
    out = jax.ShapeDtypeStruct((S_rows, *out_row), F32)
    o, o_aside, pool = pl.pallas_call(
        _walk_kernel(body, batch, aside, tile, len(scalars), len(rows),
                     len(consts)),
        grid_spec=grid_spec,
        out_shape=[out, out, jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool: the operand after the scalars, the rows' inputs and
        # the constants
        input_output_aliases={n_scalars + 2 * len(rows) + len(consts): 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_STEP_VMEM_LIMIT),
        interpret=interpreted,
        name=name,
    )(*walk, *scalars, *rows, *rows, *consts, pool)
    is_aside = ((walk[0] & _ASIDE) != 0).reshape((S_rows,) + (1,) * len(
        out_row))
    return jnp.where(is_aside, o_aside, o), pool


def walk_fits(pool) -> bool:
    """Whether the walk takes this pool: float32, and two slots (a
    batch of one run, twice) inside _SLOTS_VMEM."""
    return pool.dtype == F32 and walk_shape(pool.shape)[0] > 0


def _step_kernel(t, i, dec_ref, beta_ref, qT_ref, kT_ref, v_ref, o_ref,
                 state, *, n_heads: int, pack: int = 1):
    """One row: every head's state decayed, read against k, written
    with the token's correction, read against q. The state is
    [Dk sublanes, Dv lanes] a head: k and q arrive as columns
    [Dk, H] (one lane a head) and broadcast along the lanes; v, the
    correction and the output are rows. Where `pack` heads stand side
    by side in a lane row [Dk, pack Dv] (pack_heads), the row is
    advanced as ONE matrix: each head's column, decay and strength over
    its own Dv lanes (`spread`), and the sums over the sublanes are
    each head's own, lane by lane."""
    qT, kT = qT_ref[i], kT_ref[i]  # [Dk, H]
    Dk, lanes = qT.shape[0], v_ref.shape[-1]
    lane_of = lambda rows: jax.lax.broadcasted_iota(
        jnp.int32, (rows, lanes), 1)

    def spread(j, pick, rows):
        """pick(h) of each head h of lane row j, over that head's own
        lanes (the one head's as it is where a row is one head)."""
        out = pick(j * pack)
        for p in range(1, pack):
            out = jnp.where(lane_of(rows) >= p * (lanes // pack),
                            pick(j * pack + p), out)
        return out

    def heads(before, after):
        for h in range(n_heads // pack):
            kc = spread(h, lambda n: kT[:, n:n + 1], Dk)  # [Dk, 1 | lanes]
            qc = spread(h, lambda n: qT[:, n:n + 1], Dk)
            S = before(h) * spread(h, lambda n: dec_ref[t * n_heads + n], 1)
            m = jnp.sum(S * kc, axis=0, keepdims=True)  # [1, lanes]
            d = spread(h, lambda n: beta_ref[t * n_heads + n], 1) * (
                v_ref[i, h:h + 1, :] - m)
            S = S + kc * d
            after(h, S)
            o_ref[i, h:h + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)

    state(heads)


def gated_delta_step(q, k, v, g, beta, pool, slots, positions):
    """One step over ragged rows. q, k [S, H, Dk], v [S, H, Dv], g,
    beta [S, H] float32; pool [slots + 1, H / pack, Dk, pack Dv] float32
    (pack_heads' layout; its last slot is the pad rows'); slots [S]
    int32, each row's sequence's slot (-1: a pad row); positions [S],
    each row's token's position. -> (o [S, H, Dv] float32, the pool with
    every run's last state in its sequence's slot)."""
    return _gated_delta_step(q, k, v, g, beta, pool, slots, positions,
                             walk_shape(pool.shape), interpret())


@kernel_jit(8, 9)
def _gated_delta_step(q, k, v, g, beta, pool, slots, positions, shape,
                      interpreted: bool):
    f32 = lambda a: a.astype(F32)
    H, rows = q.shape[1], pool.shape[1]
    # v's heads side by side as the pool's are: [S, H Dv] is both
    o, pool = state_step_call(
        functools.partial(_step_kernel, n_heads=H, pack=H // rows),
        "gdn_state", (rows, pool.shape[-1]), pool, slots, positions, shape,
        interpreted,
        scalars=(jnp.exp(f32(g)).reshape(-1), f32(beta).reshape(-1)),
        rows=(f32(q).transpose(0, 2, 1), f32(k).transpose(0, 2, 1),
              f32(v).reshape(v.shape[0], rows, -1)))
    return o.reshape(v.shape), pool


def step_fits(n_rows: int, pool) -> bool:
    """Whether the step kernel takes these shapes: whole lanes and
    sublanes a lane row of heads (a head's matrix, or pack_heads' few
    side by side: heads of 96 x 192 in pairs), the walk's slots inside
    the kernel's VMEM (walk_fits), the rows' decays and strengths in
    scalar memory (counted a lane row: heads side by side are so few
    that the bound's room holds them)."""
    _, H, Dk, Dv = pool.shape
    return (Dk % 8 == 0 and Dv % 128 == 0 and walk_fits(pool)
            and 2 * n_rows * H * 4 <= 256 << 10)
