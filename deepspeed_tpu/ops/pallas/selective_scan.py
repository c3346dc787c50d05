"""The selective scan of the Mamba-1 mixer (Gu & Dao, arXiv:2312.00752):
the state a sequence carries in such a layer and the ways serving
advances it.

A sequence carries h in R^{I x N} (I channels, N the state's width),
float32. For each token, with its input x in R^I and its step dt > 0 a
CHANNEL, the layer's rates A < 0 a (channel, state) PAIR, and ONE B and
C in R^N:

    h[c, n] <- exp(dt[c] A[c, n]) h[c, n] + dt[c] B[n] x[c]
    y[c] = sum_n C[n] h[c, n]

(the skip D x, the gate and the projections are the mixer's:
inference/model.py _selective_scan). The decay is a MATRIX, one rate a
pair: ops/pallas/ssm_state.py's recurrence has one scalar a head, and
its chunked form (SSD) is algebra that only a scalar decay allows, so
nothing of it is reused here but the walk and the layout.

THE LAYOUT is ssm_state.py's, for its reasons: the pool is
[slots + 1, I / 128, N, 128] float32, N on the sublanes and 128
channels a lane row, its last slot the pad rows'. B and C are columns
[N, 1] broadcast along the lanes once a row; dt, dt x and the output
are rows of 128 lanes in the order the activations have; the read
against C is a sum over sublanes; and the layer's rates are a [N, 128]
tile a lane row that stays in VMEM for the whole call (`consts` of
gated_delta.state_step_call), where the decay exp(dt A) is made: made
outside it would be the state's own bytes over again, a row.

- `sscan_step`: one serving step over ragged rows, the Pallas kernel
  `sscan_state` on gated_delta.py's walk (`state_step_call`).
- `sscan_step_xla`: the same step as a loop over the rows in XLA
  (decode_impl 'xla', the CPU), in the pool's layout.
- `sscan_chunked`: a whole prompt, `chunk` tokens at a time: inside a
  chunk the recurrence h_t = a_t h_{t-1} + b_t is an associative scan
  of (a, b) pairs, elementwise over [chunk, N, I] (the channels on the
  minor axis); a pad token has dt = 0 (a = 1, b = 0) and leaves the
  state as it is.
- `sscan_recurrent`: the recurrence itself as a `lax.scan`, the oracle
  the three are tested against.
"""

import functools

import jax
import jax.numpy as jnp

from . import interpret, kernel_jit
from .gated_delta import F32, run_flags, state_step_call, walk_fits, walk_shape

LANES = 128


def pool_view(state, lanes: int):
    """[..., I, N] -> the pool's layout [..., I / lanes, N, lanes]."""
    *lead, I, N = state.shape
    return jnp.swapaxes(state.reshape(*lead, I // lanes, lanes, N), -1, -2)


def state_view(packed):
    """pool_view's inverse: [..., I / lanes, N, lanes] -> [..., I, N]."""
    *lead, rows, N, lanes = packed.shape
    return jnp.swapaxes(packed, -1, -2).reshape(*lead, rows * lanes, N)


def sscan_recurrent(x, dt, A, Bm, Cm, state=None):
    """x, dt [B, T, I], A [I, N], Bm, Cm [B, T, N] float32, state
    [B, I, N] or None (zeros) -> (y [B, T, I], the state after the last
    token). Token by token."""
    B, _, I = x.shape
    A = A.astype(F32)
    if state is None:
        state = jnp.zeros((B, I, A.shape[-1]), F32)

    def token(h, xs):
        xt, dtt, bt, ct = xs
        h = h * jnp.exp(dtt[..., None] * A) + (
            (dtt * xt)[..., None] * bt[:, None, :])
        return h, jnp.sum(h * ct[:, None, :], axis=-1)

    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (x, dt, Bm, Cm))
    state, y = jax.lax.scan(token, state.astype(F32), xs)
    return jnp.moveaxis(y, 0, 1), state


def sscan_chunked(x, dt, A, Bm, Cm, state=None, chunk: int = 256):
    """sscan_recurrent's arguments and results, `chunk` tokens at a time
    (module docstring). T need not be a multiple of the chunk: the tail
    is padded with tokens that leave the state as it is (dt = 0)."""
    B, T, I = x.shape
    A = A.astype(F32)
    Q = min(chunk, max(T, 1))
    n = -(-T // Q)

    def chunks(a):  # [B, T, W] -> [n, B, Q, W]
        a = jnp.pad(a.astype(F32), [(0, 0), (0, n * Q - T), (0, 0)])
        return jnp.moveaxis(a.reshape(B, n, Q, a.shape[-1]), 1, 0)

    if state is None:
        state = jnp.zeros((B, I, A.shape[-1]), F32)
    # the channels on the minor axis ([.., N, I]: a minor axis of 16
    # would lie in 128 lanes, eight times the bytes, on the chip)
    At = A.T

    def after(first, then):  # two stretches of tokens as one
        return first[0] * then[0], then[0] * first[1] + then[1]

    def one(h0, xs):
        X, d, Bc, Cc = xs  # [B, Q, I], [B, Q, I], [B, Q, N], [B, Q, N]
        a = jnp.exp(d[:, :, None, :] * At)  # [B, Q, N, I]: at most 1
        b = (d * X)[:, :, None, :] * Bc[..., None]
        a, b = jax.lax.associative_scan(after, (a, b), axis=1)
        h = a * h0[:, None] + b
        return h[:, -1], jnp.sum(h * Cc[..., None], axis=2)

    state, y = jax.lax.scan(one, jnp.swapaxes(state.astype(F32), 1, 2),
                            tuple(chunks(a) for a in (x, dt, Bm, Cm)))
    return (jnp.moveaxis(y, 0, 1).reshape(B, n * Q, I)[:, :T],
            jnp.swapaxes(state, 1, 2))


def _rate_tiles(A, pool):
    """The layer's rates A [I, N] as the pool lays a state out:
    [I / lanes, N, lanes]."""
    return pool_view(A.astype(F32), pool.shape[-1])


def sscan_step_xla(x, dt, A, Bm, Cm, pool, slots, positions):
    """sscan_step without a kernel: a loop over the rows, each reading
    its sequence's slot (an earlier row of its run has written it) and
    writing it back, in the pool's own layout."""
    S_rows, I = x.shape
    _, rows, N, lanes = pool.shape
    where, flags = run_flags(slots, positions, pool)
    At = _rate_tiles(A, pool)
    f32 = lambda a: a.astype(F32)
    dt = f32(dt).reshape(S_rows, rows, 1, lanes)
    dx = dt * f32(x).reshape(S_rows, rows, 1, lanes)
    Bc, Cc = (f32(a)[:, None, :, None] for a in (Bm, Cm))  # [S, 1, N, 1]

    def row(t, carry):
        pool, out = carry
        h = jnp.where(flags[t] == 3, 0.0, pool[where[t]])
        h = h * jnp.exp(dt[t] * At) + Bc[t] * dx[t]
        return (pool.at[where[t]].set(h),
                out.at[t].set(jnp.sum(h * Cc[t], axis=1).reshape(I)))

    pool, out = jax.lax.fori_loop(
        0, S_rows, row, (pool, jnp.zeros((S_rows, I), F32)))
    return out, pool


def _step_kernel(t, i, dt_ref, dx_ref, bc_ref, a_ref, o_ref, state, *,
                 n_rows: int, shape):
    """One row: every lane row of channels (`shape` [N, 128]) decayed
    by exp(dt A) with its own tile of rates, written with dt x against
    B, read against C. Row i of the blocks dt_ref and dx_ref
    [tile, I / 128, 128] and of bc_ref [tile, N, 2] (B, then C, as
    columns, broadcast along the lanes once a row, outside the two
    branches of `state`); a_ref [I / 128, N, 128], whole."""
    Bb = jnp.broadcast_to(bc_ref[i, :, 0:1], shape)
    Cb = jnp.broadcast_to(bc_ref[i, :, 1:2], shape)

    def heads(before, after):
        for j in range(n_rows):
            S = before(j) * jnp.exp(dt_ref[i, j:j + 1, :] * a_ref[j]) \
                + Bb * dx_ref[i, j:j + 1, :]
            after(j, S)
            o_ref[i, j:j + 1, :] = jnp.sum(S * Cb, axis=0, keepdims=True)

    state(heads)


def sscan_step(x, dt, A, Bm, Cm, pool, slots, positions):
    """One step over ragged rows. x, dt [S, I], A [I, N], Bm, Cm [S, N]
    float32; pool [slots + 1, I / 128, N, 128] float32 (its last slot
    is the pad rows'); slots [S] int32, each row's sequence's slot (-1:
    a pad row); positions [S], each row's token's position. -> (y [S, I]
    float32, the pool with every run's last state in its sequence's
    slot)."""
    return _sscan_step(x, dt, A, Bm, Cm, pool, slots, positions,
                       walk_shape(pool.shape), interpret())


@kernel_jit(8, 9)
def _sscan_step(x, dt, A, Bm, Cm, pool, slots, positions, shape,
                interpreted: bool):
    S_rows, I = x.shape
    _, rows, N, lanes = pool.shape
    f32 = lambda a: a.astype(F32)
    dt = f32(dt).reshape(S_rows, rows, lanes)
    y, pool = state_step_call(
        functools.partial(_step_kernel, n_rows=rows, shape=(N, lanes)),
        "sscan_state", (rows, lanes), pool, slots, positions, shape,
        interpreted,
        rows=(dt, dt * f32(x).reshape(S_rows, rows, lanes),
              jnp.stack([f32(Bm), f32(Cm)], axis=-1)),
        consts=(_rate_tiles(A, pool),))
    return y.reshape(S_rows, I), pool


def sscan_step_fits(n_rows: int, pool) -> bool:
    """Whether the step kernel takes this pool: whole lanes and
    sublanes a lane row of channels, and the walk's slots inside the
    kernel's VMEM (gated_delta.walk_fits)."""
    _, rows, N, lanes = pool.shape
    return lanes == LANES and N % 8 == 0 and walk_fits(pool)
