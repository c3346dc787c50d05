"""Pallas flash attention (TPU), forward + backward kernels.

TPU-native replacement for the reference's fused attention CUDA kernels
(ref: csrc/transformer/ softmax_kernels.cu + strided_batch_gemm for
training). Flash-attention-2-style online softmax, with:

- **bf16 MXU inputs everywhere**: all matmuls feed the MXU in the input
  dtype with f32 accumulation (`preferred_element_type`) — never
  pre-cast to f32 (f32 matmul runs at 1/4 rate on v5e).
- **GQA via BlockSpec index maps**: q is [B*H, S, D], kv stays
  [B*KV, S, D]; the kv block index map folds the q-head → kv-head
  mapping (h // group) so repeated KV heads are never materialized in
  HBM (fixes VERDICT W4's n_rep× HBM traffic multiplier).
- **Pallas backward**: two kernels (dq; dk/dv) recomputing probabilities
  from the saved logsumexp — replaces round 1's XLA lax.scan backward
  that materialized [BH, S, block_k] probability tiles.
- **a tile does the work its kind holds** (`_tile_kinds`, one predicate
  for the three kernels and for `tile_census`): tiles the mask empties
  are pruned with @pl.when; an `interior` tile (every element passes)
  runs with no iota, compare or select; a `diagonal` / `window_edge`
  tile (a triangle passes) is walked in static slabs that multiply only
  the run the triangle reaches (the backward kernels mask only the
  sub-tile the edge crosses; the forward, which its mask costs nothing,
  its slab's whole run); every tile the shapes cannot prove to be one
  of those runs the `general` body, the iota mask over the whole tile.

grid layout: the innermost grid dims are sequential on TPU, so running
accumulators live in VMEM scratch across those steps and outputs are
written on the last step (out index maps that ignore the inner dims keep
the block resident until then).

Numerics are validated against the pure-jnp oracle in
tests/test_flash_attention.py exactly as the reference validates CUDA
kernels against torch (ref: tests/unit/ops).
"""

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret

NEG_INF = -1e30


def _dot(a, b, trans_a=False, trans_b=False):
    """MXU matmul with f32 accumulation, keeping input dtype (bf16 ok).

    bf16 operands take the MXU's native single pass whatever
    `jax_default_matmul_precision` says — Mosaic rejects a higher
    contract precision on bf16 operands ("Bad lhs type"); f32 operands
    follow the config."""
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
                   else None),
    )


# ---------------------------------------------------------------------------
# what a tile holds
# ---------------------------------------------------------------------------

# An edge tile is walked in slabs of its sequential axis (q rows in the
# forward and dq, k columns in dk/dv), SLAB_* long; a tile they do not
# divide is one slab. A slab multiplies the run of the other axis that
# its triangle reaches, so a triangle costs (n + 1) / 2n of its tile, n
# slabs a tile: 12/16 of a 1,024 tile at 512, 10/16 at 256. What the chip
# measured (PERF.md section 6, PR 56): the backward kernels take the
# time of their work down to 256 (128 ties); the forward's slabs of 256
# run no faster than the whole masked tile and its slabs of 512 take
# exactly 12/16 of it.
SLAB_FWD = 512
SLAB_BWD = 256


def _slab(block: int, slab: int) -> int:
    return slab if block % slab == 0 else block


def _edge_share(block: int) -> float:
    """Share of a tile an edge tile multiplies, over the three kernels
    by the products a tile costs each: 2 forward, 3 dq, 4 dk/dv (the
    split backward recomputes QK^T and dP)."""
    def share(slab):
        n = block // _slab(block, slab)
        return (n + 1) / (2 * n)

    return (2 * share(SLAB_FWD) + 7 * share(SLAB_BWD)) / 9


class TileKinds(NamedTuple):
    """What the mask leaves of the tile at (q_start, k_start): `live`,
    any element at all; of a live tile exactly one of `interior` (every
    element), `diagonal` (col <= row in the tile's own coordinates: the
    lower triangle), `window_edge` (col > row: the strict upper
    triangle) and `general` (anything else, or not proven). A flag is a
    Python bool where the shapes alone decide it."""

    live: Any
    interior: Any
    diagonal: Any
    window_edge: Any
    general: Any


def _tile_kinds(q_start, k_start, block_q: int, block_k: int, seq_len: int,
                causal: bool, window: int, alibi: bool) -> TileKinds:
    """THE classification of a tile, for the kernels (traced starts) and
    for tile_census (ints). The three special kinds exist only where
    they are exact: square tiles that divide the sequence (no padded
    column, and a tile's corner lies on the diagonal), a window of a
    whole number of tiles (its edge then runs corner to corner too) and
    no ALiBi (which biases every score). The window is judged tile by
    tile: one that reaches past every tile of the sequence binds
    nowhere, and leaves interior and diagonal tiles alone."""
    live = True
    if causal:
        live = k_start < q_start + block_q
    if window > 0:
        live = live & (k_start + block_k - 1 > q_start - window)
    if (block_q != block_k or seq_len % block_q or window % block_k
            or alibi):
        return TileKinds(live, False, False, False, live)
    if not causal:
        return TileKinds(True, True, False, False, False)
    behind = q_start - k_start  # a multiple of the tile
    interior = behind > 0
    edge = False
    if 0 < window < seq_len:
        interior = interior & (behind < window)
        edge = behind == window
    return TileKinds(live, interior, behind == 0, edge, False)


def tile_census(seq_len: int, window: int = 0, block_q: int = 512,
                block_k: int = 1024, causal: bool = True,
                alibi: bool = False) -> Dict[str, float]:
    """The tiles one head's forward visits, by kind, by the kernels' own
    predicate (each backward kernel visits the same tiles): `interior`,
    `edge` (diagonal + window_edge), `general`; `work`, the tile units
    multiplied (an edge tile _edge_share, every other tile 1); `needed`,
    the tile units the mask keeps; and `work_over_needed`.
    No chip, no trace: shapes alone, as flash_attention clamps them."""
    bq, bk = min(block_q, seq_len), min(block_k, seq_len)
    edge = _edge_share(bq)
    out = {"interior": 0, "edge": 0, "general": 0, "work": 0.0}
    for q_start in range(0, seq_len, bq):
        for k_start in range(0, seq_len, bk):
            kinds = _tile_kinds(q_start, k_start, bq, bk, seq_len, causal,
                                window, alibi)
            if not kinds.live:
                continue
            if kinds.diagonal or kinds.window_edge:
                out["edge"] += 1
                out["work"] += edge
            else:
                out["interior" if kinds.interior else "general"] += 1
                out["work"] += 1.0
    rows = np.arange(seq_len)
    span = rows + 1 if causal else np.full(seq_len, seq_len)
    if window > 0:
        span = np.minimum(span, window)
    out["needed"] = float(span.sum()) / (bq * bk)
    out["work_over_needed"] = out["work"] / out["needed"]
    return out


_ALL = slice(None)


def _when_kind(needed, kinds: TileKinds, **bodies) -> None:
    """Run, of a needed tile, the body of its kind; a kind the shapes
    rule out (a flag that is False before any tracing) is not built."""
    for kind, body in bodies.items():
        flag = getattr(kinds, kind)
        if flag is not False:
            pl.when(needed & flag)(body)


def _general_mask(q_start, k_start, shape, q_axis: int, seq_len: int,
                  causal: bool, window: int, slope):
    """(keep, bias) of a whole tile of `shape` whose axis `q_axis` runs
    over the q rows: the general body's iota mask (padded columns, the
    diagonal, the window) and its ALiBi bias (None without a slope)."""
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    keep = cols < seq_len  # k padding
    if causal:
        keep = jnp.logical_and(keep, cols <= rows)
    if window > 0:
        keep = jnp.logical_and(keep, cols > rows - window)
    bias = None if slope is None else slope * (cols - rows).astype(jnp.float32)
    return keep, bias


def _bwd_edge(part, block: int, lower: bool, q_axis: int) -> None:
    """A backward kernel's walk of an edge tile in slabs of its
    sequential axis (q rows where `q_axis` is 0: dq; k columns where it
    is 1: dk/dv): `part(slab, run, keep)` for the run of the other axis
    that lies wholly inside the triangle (no mask; where the slab has
    one), then for the sub-tile the edge crosses. `lower`: col <= row
    is kept (diagonal), else col > row (window_edge). A q slab of the
    lower triangle reaches the columns BEFORE it, a k slab the rows
    after it; the strict upper triangle the other way round."""
    t = _slab(block, SLAB_BWD)
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), q_axis)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1 - q_axis)
    keep = col <= row if lower else col > row
    before = lower == (q_axis == 0)
    for a in range(0, block, t):
        slab = slice(a, a + t)
        inside = slice(0, a) if before else slice(a + t, block)
        if inside.stop > inside.start:
            part(slab, inside)
        part(slab, slab, keep)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _win_jbase(i, bq: int, bk: int, window: int, nk: int):
    """First k block the sliding window needs for q block i."""
    jb = jnp.maximum(i * bq - window + 1, 0) // bk
    return jnp.minimum(jb, nk - 1)


def _win_j(i, j, bq: int, bk: int, window: int, nk: int):
    """Window-relative grid step j → absolute k block (clamped; the
    kernel's `needed` check drops clamped-overflow steps)."""
    return jnp.minimum(_win_jbase(i, bq, bk, window, nk) + j, nk - 1)


def _fwd_kernel(
    *refs, scale: float, block_q: int, block_k: int, seq_len: int,
    causal: bool, window: int, nk_total: int, H: int, alibi: bool,
):
    if alibi:
        q_ref, k_ref, v_ref, ab_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc = refs
        ab_ref = None
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # k block step (sequential; window-relative)
    nk = pl.num_programs(2)
    # program_id must stay OUT of pl.when bodies (cond sub-jaxprs don't
    # substitute it under the interpreter)
    slope = ab_ref[pl.program_id(0) % H] if alibi else None

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    q_start = i * block_q
    if window > 0:
        # the grid walks only the ~window/bk blocks the band needs; steps
        # clamped past the end are dropped
        j_abs = _win_j(i, j, block_q, block_k, window, nk_total)
        k_start = j_abs * block_k
        needed = _win_jbase(i, block_q, block_k, window, nk_total) + j < nk_total
    else:
        k_start = j * block_k
        needed = True
    kinds = _tile_kinds(q_start, k_start, block_q, block_k, seq_len, causal,
                        window, alibi)
    needed = needed & kinds.live

    def update(rows, cols, keep=None, bias=None):
        """One online-softmax step of the q rows `rows` over the k
        columns `cols`: rows are independent in m / l / acc, so a slab
        updates its own."""
        q = q_ref[0, rows, :]
        s = _dot(q, k_ref[0, cols, :], trans_b=True) * scale  # f32
        if bias is not None:
            s = s + bias
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_sc[rows]  # (n, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # the old sums rescaled BEFORE the exponentials: the same
        # operations, and the windowed layer's forward 5% faster on a
        # v5e than with the rescale after them (PERF.md section 6, PR 56)
        corr = jnp.exp(m_prev - m_new)  # (n, 1)
        l = l_sc[rows] * corr
        acc = acc_sc[rows] * corr
        p = jnp.exp(s - m_new)  # f32
        l = l + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, cols, :]
        acc = acc + _dot(p.astype(v.dtype), v)
        l_sc[rows] = l
        acc_sc[rows] = acc
        m_sc[rows] = m_new

    def edge(lower):
        # a slab's ONE product over the columns its triangle reaches,
        # the mask over all of them: the forward is not bound by its
        # mask, and a second product a slab cost it more than the mask
        t = _slab(block_q, SLAB_FWD)
        for a in range(0, block_q, t):
            cols = slice(0, a + t) if lower else slice(a, block_k)
            shape = (t, cols.stop - cols.start)
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            update(slice(a, a + t), cols,
                   col <= row + a if lower else col > row)

    _when_kind(needed, kinds,
               interior=lambda: update(_ALL, _ALL),
               diagonal=lambda: edge(True),
               window_edge=lambda: edge(False),
               general=lambda: update(_ALL, _ALL, *_general_mask(
                   q_start, k_start, (block_q, block_k), 0, seq_len, causal,
                   window, slope)))

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_sc[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[:] + jnp.log(l_safe)).reshape(1, block_q).astype(jnp.float32)


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _kv_index(b, H: int, KV: int, G: int):
    """q-head-major grid index b (over B*H) → kv index (over B*KV).

    q head h attends kv head h // G (heads grouped contiguously)."""
    return (b // H) * KV + (b % H) // G


def _clamp_j(j, i, bq: int, bk: int, causal: bool, window: int = 0, nk: int = 0):
    """k-block index for the k-sequential kernels' DMA (fwd, dq).

    window > 0: grid j is window-relative — translate to the absolute
    block (iterations scale with the window, not S).
    causal: blocks strictly above the diagonal are skipped by @pl.when,
    but Pallas would still stream their tiles; clamping to the last
    needed block makes pruned steps revisit a resident block."""
    if window > 0:
        j = _win_j(i, j, bq, bk, window, nk)
    if causal:
        jmax = ((i + 1) * bq - 1) // bk
        j = jnp.minimum(j, jmax)
    return j


def _win_ibase(j, bk: int, bq: int):
    """First q block the causal band reaches for k block j."""
    return (j * bk) // bq


def _win_i(j, i, bk: int, bq: int, nq: int):
    """Window-relative grid step i → absolute q block for the
    q-sequential dk/dv kernel."""
    return jnp.minimum(_win_ibase(j, bk, bq) + i, nq - 1)


def _clamp_i(i, j, bq: int, bk: int, causal: bool, window: int = 0, nq: int = 0):
    """q-block index for the q-sequential dk/dv kernel's DMA."""
    if window > 0:
        i = _win_i(j, i, bk, bq, nq)
    if causal:
        imin = (j * bk) // bq
        i = jnp.maximum(i, imin)
    return i


def _flash_fwd(q, k, v, slopes, causal, block_q, block_k, H, KV, window=0,
               alibi=False):
    """q: [B*H, S, D]; k,v: [B*KV, S, D] → (o [B*H,S,D], lse [B*H,S])."""
    BH, S, D = q.shape
    G = H // KV
    scale = 1.0 / (D**0.5)
    bq, bk = block_q, block_k
    Sp = pl.cdiv(S, bq) * bq
    Sk = pl.cdiv(S, bk) * bk
    qp = _pad_to(q, Sp, 1)
    kp = _pad_to(k, Sk, 1)
    vp = _pad_to(v, Sk, 1)
    nq, nk = Sp // bq, Sk // bk

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=bq, block_k=bk, seq_len=S, causal=causal,
        window=window, nk_total=nk, H=H, alibi=alibi,
    )
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec(
            (1, bk, D),
            lambda b, i, j: (_kv_index(b, H, KV, G), _clamp_j(j, i, bq, bk, causal, window, nk), 0),
        ),
        pl.BlockSpec(
            (1, bk, D),
            lambda b, i, j: (_kv_index(b, H, KV, G), _clamp_j(j, i, bq, bk, causal, window, nk), 0),
        ),
    ]
    inputs = [qp, kp, vp]
    if alibi:
        # per-q-head slopes, whole [H] array resident in SMEM
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(slopes)
    # window: the k grid walks only the blocks the band can touch
    nkw = min(nk, pl.cdiv(bq + window - 1, bk) + 1) if window > 0 else nk
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nkw),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # lse carries a singleton middle dim so the block's trailing two
            # dims (1, bq) satisfy the TPU (8,128) tiling rule via equality
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sp, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret(),
        name="flash_fwd",
    )(*inputs)
    return o[:, :S], lse[:, 0, :S]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(
    *refs, scale: float, block_q: int, block_k: int, seq_len: int,
    causal: bool, window: int, nk_total: int, H: int, alibi: bool,
):
    if alibi:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, ab_ref,
         dq_ref, dq_sc) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc = refs
        ab_ref = None
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # k block step (sequential; window-relative)
    nk = pl.num_programs(2)
    slope = ab_ref[pl.program_id(0) % H] if alibi else None

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    q_start = i * block_q
    if window > 0:
        k_start = _win_j(i, j, block_q, block_k, window, nk_total) * block_k
        needed = _win_jbase(i, block_q, block_k, window, nk_total) + j < nk_total
    else:
        k_start = j * block_k
        needed = True
    kinds = _tile_kinds(q_start, k_start, block_q, block_k, seq_len, causal,
                        window, alibi)
    needed = needed & kinds.live

    def part(rows, cols, keep=None, bias=None):
        """dq of the q rows `rows` gains what the k columns `cols` give."""
        q = q_ref[0, rows, :]
        k = k_ref[0, cols, :]
        s = _dot(q, k, trans_b=True) * scale  # f32
        if bias is not None:
            s = s + bias
        n = s.shape[0]
        lse = lse_ref[0, :, rows].reshape(n, 1)
        p = jnp.exp(s - lse)  # f32
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dp = _dot(do_ref[0, rows, :], v_ref[0, cols, :], trans_b=True)  # f32
        delta = delta_ref[0, :, rows].reshape(n, 1)
        ds = p * (dp - delta) * scale  # f32
        dq_sc[rows] = dq_sc[rows] + _dot(ds.astype(k.dtype), k)

    _when_kind(needed, kinds, interior=lambda: part(_ALL, _ALL),
               diagonal=lambda: _bwd_edge(part, block_q, True, 0),
               window_edge=lambda: _bwd_edge(part, block_q, False, 0),
               general=lambda: part(_ALL, _ALL, *_general_mask(
                   q_start, k_start, (block_q, block_k), 0, seq_len, causal,
                   window, slope)))

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    *refs, scale: float, block_q: int, block_k: int, seq_len: int,
    causal: bool, window: int, n_group: int, nq_total: int, KV: int,
    alibi: bool,
):
    if alibi:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, ab_ref,
         dk_ref, dv_ref, dk_sc, dv_sc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_sc, dv_sc) = refs
        ab_ref = None
    j = pl.program_id(1)   # k block
    g = pl.program_id(2)   # q-head within the kv group (sequential)
    i = pl.program_id(3)   # q block step (sequential; window-relative)
    nq = pl.num_programs(3)
    # q head this (b, g) step attends with
    slope = (ab_ref[(pl.program_id(0) % KV) * n_group + g] if alibi
             else None)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    k_start = j * block_k
    if window > 0:
        q_start = _win_i(j, i, block_k, block_q, nq_total) * block_q
        # rows beyond the window never see this k block (kinds.live)
        needed = _win_ibase(j, block_k, block_q) + i < nq_total
    else:
        q_start = i * block_q
        needed = True
    kinds = _tile_kinds(q_start, k_start, block_q, block_k, seq_len, causal,
                        window, alibi)
    needed = needed & kinds.live

    def part(cols, rows, keep=None, bias=None):
        """dk / dv of the k columns `cols` gain what the q rows `rows`
        give. Transposed orientation (k, q): no in-kernel transposes."""
        q = q_ref[0, rows, :]
        s_t = _dot(k_ref[0, cols, :], q, trans_b=True) * scale  # f32
        if bias is not None:
            s_t = s_t + bias
        lse = lse_ref[0, :, rows]  # (1, n) broadcasts over the k rows
        p_t = jnp.exp(s_t - lse)  # f32
        if keep is not None:
            p_t = jnp.where(keep, p_t, 0.0)
        do = do_ref[0, rows, :]
        dv_sc[cols] = dv_sc[cols] + _dot(p_t.astype(do.dtype), do)
        dp_t = _dot(v_ref[0, cols, :], do, trans_b=True)  # f32
        delta = delta_ref[0, :, rows]  # (1, n)
        ds_t = p_t * (dp_t - delta) * scale
        dk_sc[cols] = dk_sc[cols] + _dot(ds_t.astype(q.dtype), q)

    _when_kind(needed, kinds, interior=lambda: part(_ALL, _ALL),
               diagonal=lambda: _bwd_edge(part, block_k, True, 1),
               window_edge=lambda: _bwd_edge(part, block_k, False, 1),
               general=lambda: part(_ALL, _ALL, *_general_mask(
                   q_start, k_start, (block_k, block_q), 1, seq_len, causal,
                   window, slope)))

    @pl.when(jnp.logical_and(g == n_group - 1, i == nq - 1))
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, slopes, o, lse, do, causal, block_q, block_k, H, KV,
               window=0, alibi=False, delta_adjust=None):
    BH, S, D = q.shape
    BKV = k.shape[0]
    G = H // KV
    scale = 1.0 / (D**0.5)
    bq, bk = block_q, block_k
    Sp = pl.cdiv(S, bq) * bq
    Sk = pl.cdiv(S, bk) * bk
    nq, nk = Sp // bq, Sk // bk

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [BH,S]
    if delta_adjust is not None:
        # lse cotangent (flash_attention_with_lse): d lse/d s = p, so the
        # extra ds term is p * g_lse — algebraically identical to
        # shrinking delta by g_lse (ds = p * (dp - (delta - g_lse)))
        delta = delta - delta_adjust
    qp = _pad_to(q, Sp, 1)
    dop = _pad_to(do, Sp, 1)
    lsep = _pad_to(lse, Sp, 1).reshape(BH, 1, Sp)
    deltap = _pad_to(delta, Sp, 1).reshape(BH, 1, Sp)
    kp = _pad_to(k, Sk, 1)
    vp = _pad_to(v, Sk, 1)

    kv_ix = lambda b: _kv_index(b, H, KV, G)
    # window-relative inner grids: k steps per q block / q steps per k
    # block scale with the window, not S
    nkw = min(nk, pl.cdiv(bq + window - 1, bk) + 1) if window > 0 else nk
    niw = min(nq, pl.cdiv(bk + window - 1, bq) + 1) if window > 0 else nq

    dq_in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (kv_ix(b), _clamp_j(j, i, bq, bk, causal, window, nk), 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (kv_ix(b), _clamp_j(j, i, bq, bk, causal, window, nk), 0)),
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
    ]
    dq_inputs = [qp, kp, vp, dop, lsep, deltap]
    if alibi:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_inputs.append(slopes)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=bq, block_k=bk, seq_len=S,
            causal=causal, window=window, nk_total=nk, H=H, alibi=alibi,
        ),
        grid=(BH, nq, nkw),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret(),
        name="flash_bwd_dq",
    )(*dq_inputs)

    # q-head index for the dk/dv grid: (b_kv, g) → q head row in [B*H)
    q_ix = lambda b, g: (b // KV) * H + (b % KV) * G + g

    dkv_in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, j, g, i: (q_ix(b, g), _clamp_i(i, j, bq, bk, causal, window, nq), 0)),
        pl.BlockSpec((1, bk, D), lambda b, j, g, i: (b, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, j, g, i: (b, j, 0)),
        pl.BlockSpec((1, bq, D), lambda b, j, g, i: (q_ix(b, g), _clamp_i(i, j, bq, bk, causal, window, nq), 0)),
        pl.BlockSpec((1, 1, bq), lambda b, j, g, i: (q_ix(b, g), 0, _clamp_i(i, j, bq, bk, causal, window, nq))),
        pl.BlockSpec((1, 1, bq), lambda b, j, g, i: (q_ix(b, g), 0, _clamp_i(i, j, bq, bk, causal, window, nq))),
    ]
    dkv_inputs = [qp, kp, vp, dop, lsep, deltap]
    if alibi:
        dkv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkv_inputs.append(slopes)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, n_group=G, scale=scale, block_q=bq, block_k=bk,
            seq_len=S, causal=causal, window=window, nq_total=nq, KV=KV,
            alibi=alibi,
        ),
        grid=(BKV, nk, G, niw),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, g, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, g, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BKV, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret(),
        name="flash_bwd_dkv",
    )(*dkv_inputs)

    return dq[:, :S], dk[:, :S], dv[:, :S]


# ---------------------------------------------------------------------------
# custom VJP + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, slopes, causal, block_q, block_k, H, KV, window, alibi):
    o, _ = _flash_fwd(q, k, v, slopes, causal, block_q, block_k, H, KV,
                      window, alibi)
    return o


def _flash_fwd_rule(q, k, v, slopes, causal, block_q, block_k, H, KV, window,
                    alibi):
    o, lse = _flash_fwd(q, k, v, slopes, causal, block_q, block_k, H, KV,
                        window, alibi)
    # Named for remat policies: models/transformer remat="save_attn"
    # saves exactly these (the kernel's own residuals), so the layer-body
    # recompute in the backward skips re-running the fwd kernel while
    # everything else (projections, MLP) still rematerializes.
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, slopes, o, lse)


def _flash_bwd_rule(causal, block_q, block_k, H, KV, window, alibi, res, do):
    q, k, v, slopes, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, slopes, o, lse, do, causal, block_q,
                            block_k, H, KV, window, alibi)
    # ALiBi slopes are architectural constants, never trained
    return dq, dk, dv, jnp.zeros_like(slopes)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, block_q, block_k, H, KV):
    return _flash_fwd(q, k, v, None, causal, block_q, block_k, H, KV)


def _flash_lse_fwd_rule(q, k, v, causal, block_q, block_k, H, KV):
    o, lse = _flash_fwd(q, k, v, None, causal, block_q, block_k, H, KV)
    # named like _flash_fwd_rule's residuals so remat="save_attn*"
    # policies keep ring-flash hop residuals too (without the names the
    # backward would re-run the whole forward ring per layer)
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd_rule(causal, block_q, block_k, H, KV, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _flash_bwd(q, k, v, None, o, lse, do, causal, block_q, block_k,
                      H, KV, delta_adjust=dlse)


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_with_lse(
    q, k, v, causal: bool = True, block_q: int = 512, block_k: int = 1024,
):
    """flash_attention that ALSO returns the per-row logsumexp
    ([B, H, S] f32) and is differentiable in both outputs — the partial
    attention primitive ring attention's hops merge with
    (o_c = Σ o_i · exp(lse_i - lse_c), lse_c = logaddexp(lse_i)).
    The lse cotangent folds into the existing backward kernels as a
    delta adjustment; no new kernel code."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    assert H % KV == 0, f"n_heads {H} not a multiple of kv_heads {KV}"
    # the kernels tile K by q's padded length (self-attention shapes)
    assert k.shape[1] == S, "flash_attention_with_lse needs Sq == Sk"
    bq = min(block_q, S)
    bk = min(block_k, S)

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, x.shape[1], D)

    o, lse = _flash_lse(to_bh(q), to_bh(k), to_bh(v), causal, bq, bk, H, KV)
    return (o.reshape(B, H, S, D).transpose(0, 2, 1, 3),
            lse.reshape(B, H, S))


def flash_attention(
    q, k, v, causal: bool = True, block_q: int = 512, block_k: int = 1024,
    window: int = 0, alibi=None,
):
    """[B,S,H,D] x [B,S,KV,D] x [B,S,KV,D] → [B,S,H,D] flash attention.

    GQA (KV < H) is handled inside the kernels via index maps — callers
    must NOT pre-repeat KV heads.

    window > 0: token-exact sliding window (Mistral-class) — requires
    causal; out-of-window blocks are pruned from both compute (@pl.when)
    and DMA (index-map clamps), so FLOPs/traffic scale with window, not
    S^2.

    alibi: optional [H] per-head ALiBi slopes (Bloom-class; ref the CUDA
    attn_softmax_context alibi path) — the bias slope_h * (col - row)
    joins each score tile from SMEM before the online softmax; the
    backward kernels recompute probabilities with the same bias."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    assert H % KV == 0, f"n_heads {H} not a multiple of kv_heads {KV}"
    assert window == 0 or causal, "sliding window requires causal attention"
    bq = min(block_q, S)
    bk = min(block_k, S)

    use_alibi = alibi is not None
    slopes = (jnp.asarray(alibi, jnp.float32).reshape(H) if use_alibi
              else jnp.zeros((1,), jnp.float32))

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, S, D)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), slopes, causal, bq, bk, H, KV,
               window, use_alibi)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)
