"""The state-space recurrence of the Mamba-2 mixer (Dao & Gu,
arXiv:2405.21060): the state a head carries and the two ways serving
advances it.

A head carries S in R^{P x N} (P its width, N the state's), float32.
For each token, with its head's input x in R^P and step dt > 0, the
head's decay rate A < 0, and ONE B and C in R^N for all the heads of a
GROUP (G groups of H / G heads in their order, head h in group
h // (H / G); one group, or several):

    S <- exp(dt A) S + (dt x) B^T;  y = S C

(the skip D x and everything after are the mixer's: inference/model.py
_state_space). It is not the delta rule with a term removed: the decay
is one scalar a head a token, the write does not read the state back,
and B and C are shared by a group's heads. B and C arrive FLAT,
[..., G N], the groups side by side as the mixer's convolution leaves
them; the step reads G off the pool's N, the other forms take `groups`.

- `ssm_step`: one serving step over ragged rows, the Pallas kernel
  `ssm_state`. The walk is gated_delta.py's (`state_step_call`: a run's
  state fetched from its sequence's slot by the kernel's own copies,
  updated in place in one VMEM buffer from row to row, written once;
  the pool in HBM, aliased in and out, its last slot the pad rows'); the
  body is this file's.

  THE LAYOUT. A head's matrix is [P, N] = [64, 128] at the published
  widths, and the lane tile is 128 wide. Two layouts were compiled for
  a described v5e and timed on the chip at the cell's shapes, 128
  decode rows of 128 heads (my chip run, PR 44; PERF.md section 6):
  N on the lanes ([H, P, N]: dt x as a COLUMN a head, one lane
  broadcast a head, the read against C a LANE reduction of every vreg
  of the state, the output a column a head) took 1.824 ms a call,
  589 GB/s of the state's bytes in and out; the one kept here 1.721 ms,
  624 GB/s (3.593 against 3.385 ms at 256 rows): the matrix
  TRANSPOSED, [N sublanes, P lanes], with as many heads side by side as
  fill a lane row (two of 64: `pack`), the pool
  [slots + 1, H / pack, N, pack P]. Then B and C are columns [N, 1],
  broadcast along the lanes ONCE a row for all the heads (once a group
  where there are several: the heads of a lane row lie in one group,
  TransformerConfig checks, so a row of the pool reads one column); dt x, the
  decay and the output are ROWS of 128 lanes in the order the
  activations already have ([H P] = [H / pack, pack P]), a sublane
  broadcast each; and the read against C is a sum over sublanes. Nothing
  is transposed around the kernel, no scalar is read a head. Both wait
  for the slots' copies most of the time (8 MiB a row in and out; the
  arithmetic alone is 2.5 us a row, my chip run, PR 45), which is why
  the cross-lane work of the first costs 6% and not a multiple; 6% of
  nine layers is 0.9 ms of a 21.8 ms iteration.
- `ssm_chunked`: a whole prompt, the chunked form (the paper's SSD):
  algebra on the recurrence, Q tokens at a time as matmuls. With
  G_i = sum_{j<=i} dt_j A inside the chunk, L_ij = exp(G_i - G_j) for
  i >= j (never above 1), X' = diag(dt) X and S_0 the state before it:
  Y = diag(exp(G)) C S_0^T + (tril(C B^T) . L) X';
  S_Q = exp(G_Q) S_0 + X'^T diag(exp(G_Q - G)) B. C B^T is ONE [Q, Q]
  matrix a group. In XLA: a serving step is never a whole prompt here.
- `ssm_recurrent`: the recurrence itself as a `lax.scan`, the oracle
  both are tested against; `ssm_step_xla` is the step over rows without
  a kernel (decode_impl 'xla', the CPU).

The pool holds the PACKED layout whoever advances it (`pack_state` /
`unpack_state` are the two views). Everything here is float32: the
state is what a sequence IS in such a layer.
"""

import functools

import jax
import jax.numpy as jnp

from . import interpret, kernel_jit
from .gated_delta import (
    F32,
    run_flags,
    state_step_call,
    walk_fits,
    walk_shape,
)

LANES = 128


def pack_state(state, pack: int):
    """[..., H, P, N] -> the pool's layout [..., H / pack, N, pack P]."""
    *lead, H, P, N = state.shape
    s = state.reshape(*lead, H // pack, pack, P, N)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, H // pack, N, pack * P)


def unpack_state(packed, pack: int):
    """pack_state's inverse: [..., H / pack, N, pack P] -> [..., H, P, N]."""
    *lead, Hp, N, PP = packed.shape
    s = packed.reshape(*lead, Hp, N, pack, PP // pack)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, Hp * pack, PP // pack, N)


def _by_head(a, groups: int, heads: int):
    """B or C [..., G N] -> [..., H, N]: each head its group's."""
    a = a.reshape(*a.shape[:-1], groups, a.shape[-1] // groups)
    return jnp.repeat(a, heads // groups, axis=-2)


def ssm_recurrent(x, dt, A, Bm, Cm, state=None, groups: int = 1):
    """x [B, T, H, P], dt [B, T, H], A [H], Bm, Cm [B, T, G N] float32,
    state [B, H, P, N] or None (zeros) -> (y [B, T, H, P], the state
    after the last token). Token by token."""
    B, _, H, P = x.shape
    if state is None:
        state = jnp.zeros((B, H, P, Bm.shape[-1] // groups), F32)

    def token(S, xs):
        xt, dtt, bt, ct = xs
        S = S * jnp.exp(dtt * A)[..., None, None] + (
            (dtt[..., None] * xt)[..., None]
            * _by_head(bt, groups, H)[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, _by_head(ct, groups, H),
                             precision="highest")

    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (x, dt, Bm, Cm))
    state, y = jax.lax.scan(token, state.astype(F32), xs)
    return jnp.moveaxis(y, 0, 1), state


def ssm_chunked(x, dt, A, Bm, Cm, state=None, chunk: int = 256,
                groups: int = 1):
    """ssm_recurrent's arguments and results, `chunk` tokens at a time
    (module docstring). T need not be a multiple of the chunk: the tail
    is padded with tokens that leave the state as it is (dt = 0)."""
    B, T, H, P = x.shape
    N = Bm.shape[-1] // groups
    Hg = H // groups  # heads a group, in their order
    Q = min(chunk, max(T, 1))
    n = -(-T // Q)

    def chunks(a):  # [B, T, ...] -> [n, B, Q, ...]
        a = jnp.pad(a.astype(F32), [(0, 0), (0, n * Q - T)]
                    + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(B, n, Q, *a.shape[2:]), 1, 0)

    if state is None:
        state = jnp.zeros((B, H, P, N), F32)
    mm = functools.partial(jnp.einsum, precision="highest")
    row = jnp.arange(Q)
    lower = row[:, None] >= row[None, :]

    def one(S, xs):
        X, d, Bc, Cc = xs  # [B, Q, H, P], [B, Q, H], [B, Q, G N]
        G = jnp.cumsum(d * A, axis=1)  # [B, Q, H]
        # exp(G_i - G_j) where i >= j: at most 1, so no overflow
        L = jnp.where(lower[None, :, :, None], jnp.exp(jnp.where(
            lower[None, :, :, None], G[:, :, None] - G[:, None], 0.0)), 0.0)
        # a group's heads apart: [.., H, ..] -> [.., groups, Hg, ..]
        split = lambda a, axis: a.reshape(
            *a.shape[:axis], groups, Hg, *a.shape[axis + 1:])
        Bc, Cc = (a.reshape(B, Q, groups, N) for a in (Bc, Cc))
        Xp = split(X * d[..., None], 2)
        CB = jnp.where(lower[:, :, None],
                       mm("bign,bjgn->bijg", Cc, Bc), 0.0)
        Y = mm("bigh,bghpn,bign->bighp", split(jnp.exp(G), 2), split(S, 1),
               Cc) + mm("bijgh,bjghp->bighp",
                        CB[..., None] * split(L, 3), Xp)
        last = G[:, -1:]  # [B, 1, H]
        S = jnp.exp(last[:, 0])[..., None, None] * S + mm(
            "bjghp,bjgn->bghpn",
            Xp * split(jnp.exp(last - G), 2)[..., None], Bc
        ).reshape(S.shape)
        return S, Y.reshape(X.shape)

    state, y = jax.lax.scan(one, state.astype(F32),
                            tuple(chunks(a) for a in (x, dt, Bm, Cm)))
    # [n, B, Q, H, P] -> [B, T, H, P]
    return jnp.moveaxis(y, 0, 1).reshape(B, n * Q, H, P)[:, :T], state


def ssm_step_xla(x, dt, A, Bm, Cm, pool, slots, positions):
    """ssm_step without a kernel: a loop over the rows, each reading
    its sequence's slot (an earlier row of its run has written it) and
    writing it back."""
    S_rows, H, P = x.shape
    pack = H // pool.shape[1]
    where, flags = run_flags(slots, positions, pool)

    def row(t, carry):
        pool, out = carry
        S = jnp.where(flags[t] == 3, 0.0, unpack_state(pool[where[t]], pack))
        y, S = ssm_recurrent(x[t][None, None], dt[t][None, None], A,
                             Bm[t][None, None], Cm[t][None, None], S[None],
                             groups=Bm.shape[-1] // pool.shape[2])
        return (pool.at[where[t]].set(pack_state(S[0], pack)),
                out.at[t].set(y[0, 0]))

    pool, out = jax.lax.fori_loop(
        0, S_rows, row, (pool, jnp.zeros(x.shape, F32)))
    return out, pool


def _step_kernel(t, i, dec_ref, dx_ref, bc_ref, o_ref, state, *,
                 n_rows: int, shape, groups: int):
    """One row: every lane row of heads (`pack` heads side by side,
    `shape` [N, pack P]) decayed, written with dt x against its group's
    B, read against its group's C. Row i of the blocks dec_ref and
    dx_ref [tile, H / pack, pack P]: each head's decay repeated over
    its P lanes, and dt x; of bc_ref [tile, N, 2 G]: the groups' B,
    then their C, as columns. ONE group's pair is broadcast along the
    lanes once a row, outside the two branches of `state`; of several,
    each where its first lane row of heads begins."""
    column = lambda c: jnp.broadcast_to(bc_ref[i, :, c:c + 1], shape)
    per = n_rows // groups  # lane rows of heads a group
    one = (column(0), column(1)) if groups == 1 else None

    def heads(before, after):
        for j in range(n_rows):
            if one is not None:
                Bb, Cb = one
            elif j % per == 0:
                Bb, Cb = column(j // per), column(groups + j // per)
            S = before(j) * dec_ref[i, j:j + 1, :] + Bb * dx_ref[i, j:j + 1, :]
            after(j, S)
            o_ref[i, j:j + 1, :] = jnp.sum(S * Cb, axis=0, keepdims=True)

    state(heads)


def ssm_step(x, dt, A, Bm, Cm, pool, slots, positions):
    """One step over ragged rows. x [S, H, P], dt [S, H], A [H], Bm, Cm
    [S, G N] float32; pool [slots + 1, H / pack, N, pack P] float32 (its
    last slot is the pad rows'); slots [S] int32, each row's sequence's
    slot (-1: a pad row); positions [S], each row's token's position.
    -> (y [S, H, P] float32, the pool with every run's last state in
    its sequence's slot)."""
    return _ssm_step(x, dt, A, Bm, Cm, pool, slots, positions,
                     walk_shape(pool.shape), interpret())


@kernel_jit(8, 9)
def _ssm_step(x, dt, A, Bm, Cm, pool, slots, positions, shape,
              interpreted: bool):
    S_rows, H, P = x.shape
    _, Hp, N, PP = pool.shape
    f32 = lambda a: a.astype(F32)
    dt = f32(dt)
    dec = jnp.repeat(jnp.exp(dt * f32(A)), P, axis=-1)
    G = Bm.shape[-1] // N

    def columns():
        bc = jnp.stack([f32(Bm), f32(Cm)], axis=-1)  # [S, G N, 2]
        if G == 1:
            return bc
        # -> [S, N, 2 G]: the groups' B, then their C, columns of N
        return bc.reshape(S_rows, G, N, 2).transpose(0, 2, 3, 1).reshape(
            S_rows, N, 2 * G)

    y, pool = state_step_call(
        functools.partial(_step_kernel, n_rows=Hp, shape=(N, PP), groups=G),
        "ssm_state", (Hp, PP), pool, slots, positions, shape, interpreted,
        rows=(dec.reshape(S_rows, Hp, PP),
              (f32(x) * dt[..., None]).reshape(S_rows, Hp, PP), columns()))
    return y.reshape(S_rows, H, P), pool


def ssm_step_fits(n_rows: int, pool) -> bool:
    """Whether the step kernel takes this pool: whole lanes and
    sublanes a lane row of heads, and the walk's slots inside the
    kernel's VMEM (gated_delta.walk_fits). (No row count refuses: the
    rows' decays and steps are vectors, not scalars.)"""
    _, Hp, N, PP = pool.shape
    return PP == LANES and N % 8 == 0 and walk_fits(pool)
