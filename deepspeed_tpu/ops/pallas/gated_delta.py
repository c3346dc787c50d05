"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464):
the state a linear-attention head carries and the two ways serving
advances it.

A value head carries S in R^{Dk x Dv}, float32. For each token, with
its head's q and k (L2-normalised, q scaled by Dk^-0.5), v, the log
decay g <= 0 and the write strength beta in (0, 1):

    S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

- `gated_delta_step`: one serving step over ragged rows, the Pallas
  kernel `gdn_state`. Row t is one token; rows of one sequence are
  adjacent and in order (a RUN: a decode row is a run of one, a prefill
  chunk a run of several), so a step is a segmented recurrence. The
  grid walks the rows; a run's state is fetched from its sequence's
  slot of the pool [slots + 1, H, Dk, Dv] on its first row (zeros at
  position 0, whatever the slot holds), stays in VMEM from row to row
  of the run (an output block whose index does not change is not
  written back between grid steps) and is written to the slot once,
  after its last row. The pool is aliased in and out: what moves is
  each live sequence's 4 x H x Dk x Dv bytes in and out, never the
  pool. The pool's LAST slot belongs to no sequence: pad rows write
  there.
- `gated_delta_chunked`: a whole prompt, the chunked form: algebra on
  the recurrence (the paper's WY representation), C tokens at a time as
  matmuls. With G_i = g_1 + ... + g_i inside the chunk, D_ij =
  exp(G_i - G_j) for i >= j (never above 1) and S_0 the state before
  it: A = -strict_tril((diag(beta) K) K^T . D); T = (I - A)^-1;
  U = T diag(beta) V; W = T diag(beta) (K . exp(G)); V' = U - W S_0;
  O = (Q . exp(G)) S_0 + tril((Q K^T) . D) V';
  S_C = exp(G_C) S_0 + (K . exp(G_C - G))^T V'. A is strictly lower
  triangular, hence nilpotent: T = (I + A)(I + A^2)(I + A^4)... with
  log2 C factors, matmuls and no substitution loop.
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

from . import interpret

F32 = jnp.float32
# the published chunk of the family's kernels
CHUNK = 64
_STEP_VMEM_LIMIT = 48 << 20


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
        # (I - A)^-1 of a nilpotent A: (I + A)(I + A^2)(I + A^4)...
        Tm, P, n = eye + A, A, 1
        while 2 * n < C:
            P = mm("bhij,bhjk->bhik", P, P)
            Tm = mm("bhij,bhjk->bhik", Tm, eye + P)
            n *= 2
        eG = jnp.exp(G)[..., None]
        U = mm("bhij,bhjv->bhiv", Tm, V * b_[..., None])
        W = mm("bhij,bhjk->bhik", Tm, Kb * eG)
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
    _, fresh = run_starts(slots, positions)
    where = jnp.where(slots < 0, pad, slots)

    def row(t, carry):
        pool, out = carry
        S = jnp.where(fresh[t], 0.0, pool[where[t]])
        o, S = gated_delta_recurrent(
            q[t][None, None], k[t][None, None], v[t][None, None],
            g[t][None, None], beta[t][None, None], S[None])
        return (pool.at[where[t]].set(S[0]), out.at[t].set(o[0, 0]))

    pool, out = jax.lax.fori_loop(
        0, S_rows, row, (pool, jnp.zeros(v.shape, F32)))
    return out, pool


def run_state(flag, pool_in, pool_out, heads):
    """`heads(before)` of one row of a matrix-state kernel, with
    `before(h)` the run's state so far: the slot's (flag 1: its first
    row), zeros (3: a sequence's first token, a pad row), or what the
    row before left in the output block (0). Three bodies, so that a
    row loads its state from one place."""
    pl.when(flag == 1)(lambda: heads(lambda h: pool_in[0, h]))
    pl.when(flag == 3)(lambda: heads(
        lambda h: jnp.zeros(pool_out.shape[2:], F32)))
    pl.when(flag == 0)(lambda: heads(lambda h: pool_out[0, h]))


def run_flags(slots, positions, pool):
    """(where, flags) [S] int32 of a step's ragged rows over a pool
    [slots + 1, ...]: the slot each row's state lives in (the LAST for
    a pad row), and where its state comes from (run_state: 1 the first
    row of its run, 3 that and a zero state, 0 the row before)."""
    first, fresh = run_starts(slots, positions)
    where = jnp.where(slots < 0, pool.shape[0] - 1, slots).astype(jnp.int32)
    return where, first.astype(jnp.int32) + 2 * fresh.astype(jnp.int32)


def state_step_call(kernel, name: str, out_row, pool, walk, scalars=(),
                    rows=()):
    """The walk every matrix-state step kernel shares (this file's
    `gdn_state`, ops/pallas/ssm_state.py's `ssm_state`): the grid is
    the step's ragged rows; row t's block of each of `rows` [S, ...]
    comes in, its block of the output [S, *out_row] goes out; the pool
    [slots + 1, ...] float32 is aliased in and out and a row's block of
    it is its sequence's slot (`walk`: run_flags of the step's rows),
    so a run's state stays in VMEM from row to row and is written once.
    The kernel is handed (slot_ref, flag_ref, *scalars' refs, *rows'
    refs, pool_in, o_ref, pool_out): flag_ref[t] says where row t's
    state comes from (run_state), `scalars` are flat arrays for scalar
    memory. -> (out [S, *out_row] float32, the pool)."""
    where, flags = walk
    S_rows = where.shape[0]
    row = lambda *block: pl.BlockSpec(
        block, lambda t, *_: (t,) + (0,) * (len(block) - 1))
    slot = pl.BlockSpec(
        (1, *pool.shape[1:]),
        lambda t, where, *_: (where[t],) + (0,) * (pool.ndim - 1))
    n_scalars = 2 + len(scalars)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalars,
        grid=(S_rows,),
        in_specs=[*(row(1, *a.shape[1:]) for a in rows), slot],
        out_specs=[row(1, *out_row), slot],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S_rows, *out_row), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool: the operand after the scalars and the rows' inputs
        input_output_aliases={n_scalars + len(rows): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_STEP_VMEM_LIMIT),
        interpret=interpret(),
        name=name,
    )(where, flags, *scalars, *rows, pool)


def _step_kernel(slot_ref, flag_ref, dec_ref, beta_ref, qT_ref, kT_ref,
                 v_ref, pool_in, o_ref, pool_out, *, n_heads: int):
    """One row: every head's state decayed, read against k, written
    with the token's correction, read against q. The state is
    [Dk sublanes, Dv lanes] a head: k and q arrive as columns
    [Dk, H] (one lane a head) and broadcast along the lanes; v, the
    correction and the output are rows."""
    t = pl.program_id(0)
    flag = flag_ref[t]
    qT, kT = qT_ref[0], kT_ref[0]  # [Dk, H]

    def heads(before):
        for h in range(n_heads):
            kc, qc = kT[:, h:h + 1], qT[:, h:h + 1]  # [Dk, 1]
            S = before(h) * dec_ref[t * n_heads + h]
            m = jnp.sum(S * kc, axis=0, keepdims=True)  # [1, Dv]
            d = beta_ref[t * n_heads + h] * (v_ref[0, h:h + 1, :] - m)
            S = S + kc * d
            pool_out[0, h] = S
            o_ref[0, h:h + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)

    run_state(flag, pool_in, pool_out, heads)


def gated_delta_step(q, k, v, g, beta, pool, slots, positions):
    """One step over ragged rows. q, k [S, H, Dk], v [S, H, Dv], g,
    beta [S, H] float32; pool [slots + 1, H, Dk, Dv] float32 (its last
    slot is the pad rows'); slots [S] int32, each row's sequence's slot
    (-1: a pad row); positions [S], each row's token's position.
    -> (o [S, H, Dv] float32, the pool with every run's last state in
    its sequence's slot)."""
    walk = run_flags(slots, positions, pool)
    f32 = lambda a: a.astype(F32)
    return state_step_call(
        functools.partial(_step_kernel, n_heads=q.shape[1]), "gdn_state",
        v.shape[1:], pool, walk,
        scalars=(jnp.exp(f32(g)).reshape(-1), f32(beta).reshape(-1)),
        rows=(f32(q).transpose(0, 2, 1), f32(k).transpose(0, 2, 1), f32(v)))


def step_fits(n_rows: int, pool) -> bool:
    """Whether the step kernel takes these shapes: whole lanes and
    sublanes a head's matrix, the slot's four buffers (in and out, each
    double-buffered) inside the kernel's VMEM, the rows' decays and
    strengths in scalar memory."""
    _, H, Dk, Dv = pool.shape
    return (Dk % 8 == 0 and Dv % 128 == 0 and pool.dtype == F32
            and 4 * H * Dk * Dv * 4 <= _STEP_VMEM_LIMIT // 2
            and 2 * n_rows * H * 4 <= 256 << 10)
