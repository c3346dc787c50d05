"""Pallas paged-KV kernels: decode attention + cache write (TPU).

TPU-native redesign of the FastGen ragged hot path
(ref: inference/v2/kernels/ragged_ops/blocked_flash/ paged flash,
linear_blocked_kv_rotary/ fused KV-cache store; the block table is a
scalar-prefetch argument and BlockSpec index maps do the paging — the
idiomatic Mosaic equivalent of the reference's attention-atom
descriptors).

Cache layout: [num_blocks, block_size, KV_heads, head_dim].
One cache block is a CONTIGUOUS (block_size, KV, D) tile — a single
256KB-class DMA fetches every head's slice of a page, with a static
head loop over it (measured 8x fewer steps and much higher effective
bandwidth than a per-head grid). The trailing (KV, D) dims satisfy TPU
(8,128) tiling; TP shards the KV dim. "Block i of sequence s" lives at
cache[table[s, i]]; pages beyond a sequence's context are never
streamed.

paged_decode_attention is the one entry for "attend these rows over
this paged cache". Two ways to page, one per case (the entry picks from
the arguments, dtype and shape it is handed, nothing else):

- the live-block walk (_block_ring): grid (seqs,), the arenas left in
  HBM, a fori_loop over a row's live blocks with manual DMA a few
  blocks ahead. Dead table slots cost nothing. Takes the fused
  single-token write+attend (paged_decode_fused: every row a sequence
  of its own, walked alone, _walk_live_blocks) and the unfused,
  unquantised attention the shared-table program runs, at head dims
  that are multiples of 128 and, through PACKED pools (kv_pack: two KV
  heads of 64 in one 128-lane row; 30 heads of 128, no whole tiles,
  as 2 heads of 1,920; 4 heads of 128, or 8 of 64, as 2 heads of 256),
  at head dim 64 and at head counts the layout would pad. In the
  shared-table attention ADJACENT rows with equal tables (a prefill
  chunk's rows: walk_groups) walk as one GROUP of up to 256 / Gp rows
  (32 where a KV head serves up to 8 query heads): the group's first
  row reads the blocks of the group's longest context ONCE and
  multiplies each by all the group's queries in one pair of matmuls a
  KV head (_group_softmax), every row masked to its own context and
  window; the group's other rows' grid steps walk nothing. What stays
  per row: a row whose neighbours have other tables (every decode
  row) walks alone exactly as before, and rows that share a table
  without being adjacent each read it for themselves. So who builds a
  step puts a chunk's rows next to each other (the scheduler does);
- the (seqs, table_slots) BlockSpec grid (_decode_kernel): the block
  table is a scalar-prefetch argument and index maps do the paging; a
  slot beyond the context clamps to the last needed block, so a pruned
  step revisits a resident tile (no DMA, no compute, but a grid step:
  ~0.34 us each on a v5e). Keeps what the walk cannot take: int8 KV
  (scale tiles ride the index maps), the fused write at head dims that
  are neither a multiple of 128 nor packed, and block shapes Mosaic
  refuses as a manual DMA (_walks_live_blocks: a minor dim that is not
  whole lanes, so head dims other than 64 below 128, or 64 with an odd
  KV count, or heads that are no whole tiles under a mesh or int8,
  where pools are not packed).

paged_kv_write is the shared-table program's cache store, once a K/V
layer before the walk: a row goes to cache[blk, off] by ONE DMA of its
own bytes, HBM to HBM ([blk, off] are the pool's two untiled dims); no
block passes through VMEM. A pool whose slot is not whole tiles
(kv_write_path: the same tile rule as the walk's, _whole_tiles) keeps
the read-modify-write of the slot's block.

int8 per-block KV quantization (docs/paged_attention.md): pools may
hold int8 codes with a per-block [block_size, KV] f32 scale tile
riding the same index maps — dequant fuses into the attention inner
loop and the fused write+attend mode quantizes new rows in-kernel.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret, kernel_jit
from .flash_attention import NEG_INF, _dot


def _arena_block(idx, n_blocks: int):
    """THE containment clamp for every block index that reaches a DMA
    or BlockSpec index map: a violated block-table contract (caller
    bug) must produce wrong-but-contained traffic, never a wild DMA —
    an out-of-bounds manual DMA doesn't just crash the program, it can
    wedge the TPU runtime for every later client. Change containment
    policy HERE, nowhere else."""
    return jnp.clip(idx, 0, n_blocks - 1)


# ---------------------------------------------------------------------------
# int8 per-block KV quantization
#
# One scale per (token slot, KV head), stored in per-block scale tiles
# [num_blocks, block_size, KV] riding alongside the int8 code pools —
# "block i's scales" live at k_scale[i], so a block and its scales move
# together through every path that moves pages (COW copies, export/
# import handoffs, spill-to-host). Dequantization is FUSED into the
# attention inner loop (codes stream from HBM at half the bf16 bytes;
# the f32 multiply is VPU work the MXU wait hides), and quantization of
# a decode step's new rows happens inside the fused write+attend kernel.
# ---------------------------------------------------------------------------

KV_QUANT_MAX = 127.0
# the scale is amax * (1/127), spelled as a MULTIPLY in both the XLA
# and the in-kernel quantizer: XLA strength-reduces a divide-by-
# constant to this multiply in some programs but not others, and the
# resulting 1-ULP scale skew would break the codes-are-identical
# contract between the fused and separate write paths
_KV_QUANT_INV = 1.0 / 127.0


def quantize_kv_rows(k, v):
    """Quantize new KV rows [T, KV, D] -> int8 codes + per-(row, head)
    f32 scales ([T, KV]). THE rounding authority: the in-kernel
    quantizer in _decode_kernel uses the same formula, so a token's
    codes are identical whether it entered through prefill's separate
    write, the chunked-continuation write, or the fused write+attend
    kernel — token identity across those paths depends on it."""
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    ks = jnp.max(jnp.abs(kf), axis=-1) * jnp.float32(_KV_QUANT_INV)
    vs = jnp.max(jnp.abs(vf), axis=-1) * jnp.float32(_KV_QUANT_INV)
    ks = jnp.where(ks > 0, ks, jnp.float32(1.0))
    vs = jnp.where(vs > 0, vs, jnp.float32(1.0))
    qk = jnp.clip(jnp.round(kf / ks[..., None]),
                  -KV_QUANT_MAX, KV_QUANT_MAX).astype(jnp.int8)
    qv = jnp.clip(jnp.round(vf / vs[..., None]),
                  -KV_QUANT_MAX, KV_QUANT_MAX).astype(jnp.int8)
    return qk, ks, qv, vs


def _quant_row_kernel(row, compute_dtype):
    """In-kernel quantize of one [KV, D] row (must mirror
    quantize_kv_rows bit for bit); returns (codes int8, scale [KV] f32,
    dequantized row in compute_dtype)."""
    rf = row.astype(jnp.float32)
    sc = jnp.max(jnp.abs(rf), axis=-1) * jnp.float32(_KV_QUANT_INV)
    sc = jnp.where(sc > 0, sc, jnp.float32(1.0))
    q = jnp.clip(jnp.round(rf / sc[:, None]),
                 -KV_QUANT_MAX, KV_QUANT_MAX).astype(jnp.int8)
    deq = (q.astype(jnp.float32) * sc[:, None]).astype(compute_dtype)
    return q, sc, deq


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

def _win_jbase_decode(ctx, window: int, block_size: int):
    """First table slot the sliding window needs (window > 0)."""
    return jnp.maximum(ctx - window, 0) // block_size


def _decode_kernel(
    tbl_ref, ctx_ref, slot_ref,  # scalar prefetch: [S, NB] block table,
    # [S] ctx lens, [S] write slots (fused write+attend; all -1
    # sentinel when not fused)
    q_ref, *rest,
    block_size: int, scale: float, n_kv: int, gp: int, window: int,
    fused: bool, alibi: bool, quant: bool,
):
    # positional ref layout (mirrors paged_decode_attention's arg
    # order): q, [kn, vn], k, v, [ks, vs], [ab] | o, [ck, cv,
    # [cks, cvs]] | acc, m, l scratch. quant adds the per-block scale
    # tiles next to their code pools on BOTH sides.
    i = 0
    kn_ref = vn_ref = ck_out = cv_out = None
    ks_ref = vs_ref = cks_out = cvs_out = None
    ab_ref = None
    if fused:
        kn_ref, vn_ref = rest[i], rest[i + 1]
        i += 2
    k_ref, v_ref = rest[i], rest[i + 1]
    i += 2
    if quant:
        ks_ref, vs_ref = rest[i], rest[i + 1]
        i += 2
    if alibi:  # [KV, Gp] ALiBi slopes ride as the LAST input
        ab_ref = rest[i]
        i += 1
    o_ref = rest[i]
    i += 1
    if fused:
        ck_out, cv_out = rest[i], rest[i + 1]
        i += 2
        if quant:
            cks_out, cvs_out = rest[i], rest[i + 1]
            i += 2
    acc_sc, m_sc, l_sc = rest[i:i + 3]
    s = pl.program_id(0)
    j = pl.program_id(1)  # table slot (sequential; window-relative)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    ctx = ctx_ref[s]
    last = jnp.maximum(ctx - 1, 0) // block_size
    # fused: the cache holds only positions < ctx-1 (the new token rides
    # in as its own column below) — a block with no OLD live column is
    # skipped entirely, which also keeps the online softmax away from
    # the all-masked NaN corner (ctx==1, or a token opening a new block)
    eff_ctx = ctx - 1 if fused else ctx
    if window > 0:
        # grid walks only the ~window/bs slots inside the window
        j_abs = _win_jbase_decode(ctx, window, block_size) + j
        needed = j_abs * block_size < eff_ctx
    else:
        j_abs = j
        needed = j * block_size < eff_ctx

    @pl.when(needed)
    def _compute():
        k = k_ref[0]  # (bs, KV, D)
        v = v_ref[0]
        if quant:
            # dequant fused into the attention inner loop: int8 codes
            # stream from HBM, the per-(slot, head) scale tile rides in
            # the same BlockSpec index map as its code block
            k = (k.astype(jnp.float32)
                 * ks_ref[0][..., None]).astype(q_ref.dtype)
            v = (v.astype(jnp.float32)
                 * vs_ref[0][..., None]).astype(q_ref.dtype)
        cols = j_abs * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (gp, block_size), 1
        )
        # fused: the new token's row is NOT in the cache yet — mask its
        # position (ctx-1) out here; its contribution enters as a single
        # extra online-softmax column at the final grid step below. This
        # keeps the per-block compute identical to the non-fused kernel
        # (an earlier variant folded the row into the loaded block with
        # a (bs, KV, D) select at EVERY grid step — ~10us/call of VPU
        # time at decode widths).
        live = cols < eff_ctx
        if window > 0:
            live = jnp.logical_and(live, cols >= ctx - window)
        for h in range(n_kv):
            q = q_ref[0, h]  # (Gp, D)
            kh = k[:, h, :]  # (bs, D)
            st = _dot(q, kh, trans_b=True) * scale  # (Gp, bs) f32
            if alibi:
                # bias slope_h * key_pos: exact up to the per-row shift
                # softmax cancels (single query at position ctx-1)
                st = st + ab_ref[h, :][:, None] * cols.astype(jnp.float32)
            st = jnp.where(live, st, NEG_INF)

            row = slice(h * gp, (h + 1) * gp)
            m_prev = m_sc[row]
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=1, keepdims=True))
            p = jnp.exp(st - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_sc[row] = l_sc[row] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[row] = acc_sc[row] * corr + _dot(p.astype(v.dtype), v[:, h, :])
            m_sc[row] = m_new

    if fused:
        slot = slot_ref[s]
        if quant:
            # quantize the new row ONCE (codes/scales shared by the
            # column update and the store); attention sees the
            # round-tripped value so this step's logits match every
            # later step's read of the same codes
            qkn, skn, kn_use = _quant_row_kernel(kn_ref[0], q_ref.dtype)
            qvn, svn, vn_use = _quant_row_kernel(vn_ref[0], q_ref.dtype)
        else:
            kn_use = kn_ref[0]
            vn_use = vn_ref[0]

        @pl.when(jnp.logical_and(j == nb - 1, slot >= 0))
        def _new_token_column():
            # the new token's score as a 1-column online-softmax update,
            # straight from the VMEM-resident kn/vn rows
            for h in range(n_kv):
                q = q_ref[0, h]  # (Gp, D)
                stn = (jnp.sum(q * kn_use[h][None, :], axis=1,
                               keepdims=True) * scale
                       ).astype(jnp.float32)  # (Gp, 1)
                if alibi:
                    # the new token sits at key position ctx-1
                    stn = stn + (ab_ref[h, :][:, None]
                                 * (ctx - 1).astype(jnp.float32))
                row = slice(h * gp, (h + 1) * gp)
                m_prev = m_sc[row]
                m_new = jnp.maximum(m_prev, stn)
                p = jnp.exp(stn - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_sc[row] = l_sc[row] * corr + p
                acc_sc[row] = (acc_sc[row] * corr
                               + p * vn_use[h][None, :].astype(jnp.float32))
                m_sc[row] = m_new

        @pl.when(j == nb - 1)
        def _store():
            # at the final step the index clamp guarantees the loaded
            # block IS the write target (tbl[s, last]); RMW the new
            # token's row into it once. Pad rows (slot -1) write the
            # loaded block back unchanged — their table points at the
            # reserved scratch block, never a live one.
            kb = k_ref[0]
            vb = v_ref[0]
            rowm = jax.lax.broadcasted_iota(
                jnp.int32, (block_size, 1, 1), 0
            ) == jnp.maximum(slot, 0) % block_size
            wmask = jnp.logical_and(slot >= 0, rowm)
            if quant:
                ck_out[0] = jnp.where(wmask, qkn[None], kb)
                cv_out[0] = jnp.where(wmask, qvn[None], vb)
                # the scale tile RMWs alongside its code block (same
                # target index map, (bs, KV) row mask). The mask is
                # built from its own 2-D iota: slicing rowm[:, :, 0]
                # (a rank change on an i1 vector) trips an internal LLO
                # check in libtpu 0.0.34's Mosaic.
                smask = jnp.logical_and(
                    slot >= 0,
                    jax.lax.broadcasted_iota(jnp.int32, (block_size, 1), 0)
                    == jnp.maximum(slot, 0) % block_size)
                cks_out[0] = jnp.where(smask, skn[None], ks_ref[0])
                cvs_out[0] = jnp.where(smask, svn[None], vs_ref[0])
            else:
                ck_out[0] = jnp.where(wmask, kn_ref[0][None], kb)
                cv_out[0] = jnp.where(wmask, vn_ref[0][None], vb)

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_sc[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (
            (acc_sc[:] / l_safe)
            .reshape(n_kv, gp, acc_sc.shape[-1])
            .astype(o_ref.dtype)
        )


def kv_pack(kv_heads: int, head_dim: int, itemsize: int) -> int:
    """KV heads that lie side by side in one head of a PACKED pool
    [NB, bs, KV / f, f D] (1: not packed), for a pool of `itemsize`
    bytes a value:

    - 2 at head dim 64 with an even count of KV heads. The TPU's tiled
      HBM layout pads a 64-wide minor dim to 128 lanes, so a pool
      [NB, bs, KV, 64] would take, and stream, twice its bytes, and
      Mosaic refuses a manual DMA of such a block (_walks_live_blocks);
    - at head dims that are multiples of 128, for more than the
      layout's 8-row tile of heads that are no whole tiles
      (_whole_tiles: 30 heads of 128 in 16 bits, which the layout
      would pad to 32 and keep on the (S, NB) grid): the fewest that
      leave whole tiles with nothing padded (kv_pair_fold's rule: 15,
      a pool of 2 heads of 1,920). On a v5e the shared-table walk of
      128 rows over 389 live blocks takes 1.28 ms so against 2.21 ms
      over 30 heads held in 32 (2.39 as 8 heads of 512, 1.95 as 2 of
      2,048 on the grid: PERF.md section 6, PR 63);
    - fewer than the layout's 8-row tile of heads that ARE whole tiles
      (after the pairing of heads of 64: 4 heads of 128, 8 of 64): the
      MOST that leave whole tiles, which in 16 bits leaves 2 heads (1
      is no whole tile there), so 2 for 4 heads of 128 and 4 for 8
      heads of 64, both a pool of 2 heads of 256. A decode row's visit
      of a (pool head, block) costs 0.245 us at 4 heads of 128 against
      0.17 at 8 or more, and a block of 2 x 256 holds the same bytes in
      half the visits (PERF.md section 6, PR 64). Decided for the
      served 16 bits where the pool's dtype is wider (as kv_pair_fold),
      so that a float32 test walks the model's layout. Pools of 2
      heads, and of 8 or more whole-tile heads, stay as they are.

    A packed pool holds the same row-major bytes, heads f p .. f p +
    f - 1 side by side in row p. Every kernel here then runs UNCHANGED
    at (KV / f, f D): the queries of a row's heads become one group
    whose rows are zero outside their own head's lanes (block-diagonal,
    _pack_queries), so one score matmul over the f D lanes gives every
    head's scores, and of the output lanes each row keeps its own
    head's (_unpack_out). The MXU multiplies f times the needed
    values; the walk is bound by its DMAs and its per-(head, block)
    loop, which shrinks f-fold. Who allocates a pool asks this (and
    packs only unquantised pools on one device: scale tiles and head
    sharding are per KV head); everything below reads the packing off
    the shapes it is handed."""
    if (head_dim % 128 == 0 and kv_heads > 8
            and not _whole_tiles(kv_heads, head_dim, itemsize)):
        return kv_pair_fold(kv_heads, head_dim, itemsize)
    lanes = 2 if head_dim == 64 and kv_heads % 2 == 0 else 1
    heads, width = kv_heads // lanes, head_dim * lanes
    served = min(itemsize, 2)
    if heads < 8 and _whole_tiles(heads, width, served):
        return lanes * max(f for f in range(1, heads + 1) if heads % f == 0
                           and _whole_tiles(heads // f, width * f, served))
    return lanes


def _whole_tiles(kv_heads: int, head_dim: int, itemsize: int) -> bool:
    """Whether the trailing (KV, D) dims of a pool are whole tiles of
    its HBM layout, so that Mosaic takes a manual DMA of one block
    (.at[blk], the walks) or of one slot's row (.at[blk, off],
    paged_kv_write). THE tile rule, from AOT compiles for v5e, libtpu
    0.0.34, KV 1-48 at D 128 and 384 in int8, bf16 and float32 (PR 52):
    D fills whole lanes ("Slice shape along dimension 3 must be aligned
    to tiling (128), but is 64"; a scale pool's [.., 1, KV] view fails
    here), and KV is a multiple of the layout's sublane tile ("dimension
    2 ... tiling (2) / (4) / (8)"), which is the power of two covering
    KV, at most 8 and at least 4 / itemsize (int8 4, bf16 2), or 1 for
    every KV of a 32-bit pool of exactly 128 lanes."""
    if head_dim % 128:
        return False
    if itemsize == 4 and head_dim == 128:
        return True
    tile = max(min(8, pl.next_power_of_2(kv_heads)), 4 // itemsize)
    return kv_heads % tile == 0


def kv_pair_fold(pairs: int, width: int, itemsize: int = 2) -> int:
    """Pairs of K/V heads (differential attention: a pair is ONE head
    of `width` = 2 head_dim values) that lie side by side in ONE head
    of a pool: the fewest that leave the pool's heads whole tiles of
    its HBM layout (_whole_tiles: 10 pairs of 128 values in 16 bits
    would lie in 16 heads' room; 5 a head are 2 heads of 640, the same
    bytes and no padding); 1 where no fold does. Asked for the served
    16 bits whatever a test's dtype, so that the layout is the model's.
    Who allocates a pool asks this (kv_pack asks it for plain heads
    that are no whole tiles); for the pairs the serving model lays the
    new rows and the queries against the pool's heads itself
    (inference/model.py kv_pool_shape, _pool_rows, _fold_pairs)."""
    return next((f for f in range(1, pairs + 1) if pairs % f == 0
                 and _whole_tiles(pairs // f, width * f, itemsize)), 1)


def kv_write_path(pool_shape, dtype) -> str:
    """How paged_kv_write reaches a pool [NBLK, bs, KV, D] of this shape
    and dtype: "rows" (a row's own bytes DMA'd to its slot,
    _kv_write_rows_kernel) where a slot's (KV, D) is whole tiles
    (_whole_tiles), else "blocks" (the slot's whole block read, patched
    in VMEM and written back, _kv_write_blocks_kernel: what Mosaic
    refuses as a row copy the BlockSpec pipeline pads). Static, from
    what the call can see and nothing else; the engine's init.pool span
    reports it a pool (`kv_write`)."""
    _, _, KV, D = pool_shape
    rows = _whole_tiles(KV, D, jnp.dtype(dtype).itemsize)
    return "rows" if rows else "blocks"


def _packing(q, k_cache) -> int:
    """Heads a pool row holds, from the shapes: pool lanes over the
    queries' head dim (1: not packed)."""
    return k_cache.shape[3] // q.shape[2]


def _packed_group(n: int) -> int:
    """Query rows a packed pool head's group is laid out in
    (_pack_queries): its n = kv_pack x G queries, more than a sublane
    tile of them filled up to whole tiles (15 -> 16) so that a chunk's
    rows stack by a free reshape and walk as one group (_group_rows);
    up to 8 are _group_queries' to pad, as every pool's."""
    return n if n <= 8 else -(-n // 8) * 8


def _pack_queries(q, pack: int, G: int):
    """[S, H, D] -> [S, H / (pack G) * Gq, pack * D], head h's values
    in the lanes of its place (h // G) % pack within its pool row,
    zeros in the others' (G = queries a KV head); a pool row's pack * G
    queries followed by zero rows up to Gq = _packed_group of them."""
    S, H, D = q.shape
    n, rows = H // (pack * G), _packed_group(pack * G)
    eye = jnp.eye(pack, dtype=q.dtype)
    wide = jnp.einsum(
        "skigd,ij->skigjd", q.reshape(S, n, pack, G, D), eye)
    if rows != pack * G:  # (else ONE reshape: the packed cells' pinned text)
        wide = jnp.pad(wide.reshape(S, n, pack * G, pack * D),
                       ((0, 0), (0, 0), (0, rows - pack * G), (0, 0)))
    return wide.reshape(S, n * rows, pack * D)


def _unpack_out(out, pack: int, G: int):
    """[S, H / (pack G) * Gq, pack * D] -> [S, H, D]: each query's own
    head's lanes (the rows _pack_queries filled up are cut)."""
    S, HP, PD = out.shape
    D, rows = PD // pack, _packed_group(pack * G)
    eye = jnp.eye(pack, dtype=out.dtype)
    if rows != pack * G:
        out = out.reshape(S, HP // rows, rows, PD)[:, :, :pack * G]
    return jnp.einsum(
        "skigjd,ij->skigd",
        out.reshape(S, HP // rows, pack, G, pack, D), eye
    ).reshape(S, HP // rows * pack * G, D)


def _pack_rows(new, pool):
    """New rows [T, KV, D] in the shape of the pool's rows
    ([T, KV / pack, pack * D] of a packed pool: a plain reshape)."""
    return None if new is None else new.reshape(new.shape[0], *pool.shape[2:])


def _group_queries(q, n_kv: int, alibi_slopes=None):
    """[S, H, D] queries -> [S, KV, Gp, D] with each KV head's group
    sublane-padded to Gp = max(G, 8) rows, and the ALiBi slopes (or
    None) as the matching [KV, Gp] f32 table. Returns (qg, ab, G, Gp)."""
    S, H, D = q.shape
    G = H // n_kv
    Gp = max(G, 8)  # sublane-pad tiny query blocks
    qg = q.reshape(S, n_kv, G, D)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    ab = None
    if alibi_slopes is not None:
        ab = jnp.asarray(alibi_slopes, jnp.float32).reshape(n_kv, G)
        if Gp != G:
            ab = jnp.pad(ab, ((0, 0), (0, Gp - G)))
    return qg, ab, G, Gp


def paged_decode_attention(q, k_cache, v_cache, block_table, ctx_lens,
                           window: int = 0,
                           k_new=None, v_new=None, slots=None,
                           alibi_slopes=None, k_scale=None, v_scale=None,
                           scale=None):
    """One-token-per-sequence attention over the paged KV cache: THE
    entry for "attend these rows over this paged cache". Which kernel
    runs is read from the arguments here and nowhere else, never from a
    flag:

    - k_new/v_new/slots given, unquantised, head dim a multiple of 128
      (supports_fused_v2) and a pool whose heads are whole tiles
      (_whole_tiles: Mosaic refuses the row's DMA into any other):
      paged_decode_fused, the per-row live-block walk with the new
      row DMA'd into its slot;
    - attend only, unquantised, a block shape Mosaic takes as a manual
      DMA (_walks_live_blocks): the live-block walk, grid (S,) — each
      row reads the live blocks of its table and nothing else, so the
      time follows the contexts and not the table's width. This is
      what the shared-table program calls after paged_kv_write, rows
      of one prefill chunk sharing a table: ADJACENT rows with equal
      tables are walked as one GROUP (up to 256 / max(G, 8) rows, a
      longer run as several), whose blocks are read once, by its
      longest context, and multiplied by all its rows' queries
      together, each row masked to its own ctx_lens and window. Rows
      that share a table should therefore be adjacent (the contract
      paged_latent_attention states too); apart they stay correct and
      each reads the table for itself, as every row of a table of its
      own does. The choice is read from the tables, per call;
    - everything else on the (S, NB) BlockSpec grid: int8 KV (k_scale
      given: the scale tiles ride the index maps), the fused write at
      other head dims, and block shapes the walk cannot take
      (_whole_tiles: D % 128 != 0; 16-bit pools whose KV count is
      not 2, 4 or a multiple of 8, which are those kv_pack does not
      pack: 1, 3, 5-7 heads, or any such count under a mesh; a pool
      of 4 heads walks as it is under a mesh and, on one device, as
      the 2 wide heads kv_pack lays it in).

    The attend-only pallas_call is named `paged_decode_grid` whatever
    its grid: in a trace that name means "the shared-table decode
    attention". The walk with the write is `paged_decode_fused`.

    q: [S, H, D] (the new token's queries)
    k_cache/v_cache: [num_blocks, block_size, KV, D]
    k_scale/v_scale: optional [num_blocks, block_size, KV] f32 — int8
      per-block KV quantization: the caches hold int8 codes and each
      block carries a (block_size, KV) scale tile; dequant fuses into
      the attention inner loop, and the fused write+attend mode
      quantizes the new rows in-kernel (codes + scales RMW'd back
      through aliased outputs, so fused mode returns
      (out, k_cache, v_cache, k_scale, v_scale)).
    block_table: [S, NB] int32 — cache block ids per sequence; rows of
      one sequence (a prefill chunk) adjacent, to share one read
    ctx_lens: [S] int32 — context length INCLUDING the new token; rows
      with 0 are batch padding (the walk stores zeros, the grid garbage;
      sliced by the caller either way)
    window > 0: token-exact sliding window (Mistral-class serving) —
      only the ~window/block_size slots inside it are visited
    k_new/v_new [S, KV, D] + slots [S]: FUSED write+attend — the new
      token's KV enters the cache inside the call (attention sees it),
      replacing the separate paged_kv_write call (which cost a second
      kernel launch per layer; decode at small batch is launch-bound).
      Returns (out, new_k_cache, new_v_cache) with the caches aliased
      in place. REQUIRES: distinct sequences per row (no
      chunked-continuation rows sharing a table — their writes would
      race across grid steps) and pad rows (ctx 0 / slot -1) pointing
      at a reserved scratch block, since the grid writes each row's
      target block back even when nothing changed. The write slot must
      be ctx-1's flat slot.
    A PACKED pool (kv_pack: [num_blocks, block_size, KV / f, f D],
    f = 2 at head dim 64, 15 for 30 heads of 128, 2 for 4 heads of
    128 and 4 for 8 of 64; told from the shapes) is attended at
    (KV / f, f D) through this same entry,
    queries block-diagonal, k_new/v_new reshaped, `scale` (default
    1/sqrt(D)) kept the true head dim's.
    returns: [S, H, D] (fused: (out, k_cache, v_cache))
    """
    S, H, D = q.shape
    KV = k_cache.shape[2]
    fused = k_new is not None
    quant = k_scale is not None
    scale = 1.0 / (D**0.5) if scale is None else scale
    pack = _packing(q, k_cache)
    if pack > 1:
        G = H // (KV * pack)
        rows = _packed_group(pack * G)
        if alibi_slopes is not None and rows != pack * G:
            # the rows _pack_queries fills a group up with: slope 0
            alibi_slopes = jnp.pad(
                jnp.asarray(alibi_slopes, jnp.float32).reshape(KV, pack * G),
                ((0, 0), (0, rows - pack * G))).reshape(-1)
        out = paged_decode_attention(
            _pack_queries(q, pack, G), k_cache, v_cache, block_table,
            ctx_lens, window, _pack_rows(k_new, k_cache),
            _pack_rows(v_new, v_cache), slots, alibi_slopes, k_scale,
            v_scale, scale)
        if fused:
            return (_unpack_out(out[0], pack, G), *out[1:])
        return _unpack_out(out, pack, G)
    if fused and not quant and supports_fused_v2(D) and _whole_tiles(
            KV, D, k_cache.dtype.itemsize):
        return paged_decode_fused(q, k_cache, v_cache, block_table, ctx_lens,
                                  k_new, v_new, slots, window=window,
                                  alibi_slopes=alibi_slopes, scale=scale)
    qg, ab, G, Gp = _group_queries(q, KV, alibi_slopes)
    if not fused and not quant and _walks_live_blocks(qg, k_cache):
        out = _attend_live_blocks(qg, ab, k_cache, v_cache, block_table,
                                  ctx_lens, window, scale, interpret())
        return out[:, :, :G, :].reshape(S, H, D)
    out, *pools = _attend_grid(qg, ab, k_cache, v_cache, block_table,
                               ctx_lens, window, scale, k_new, v_new, slots,
                               k_scale, v_scale, interpret())
    out = out[:, :, :G, :].reshape(S, H, D)
    return (out, *pools) if fused else out


@kernel_jit(6, 7, 13)
def _attend_grid(qg, ab, k_cache, v_cache, block_table, ctx_lens,
                 window: int, scale: float, k_new, v_new, slots,
                 k_scale, v_scale, interpreted: bool):
    """paged_decode_attention on the (S, NB) BlockSpec grid
    (_decode_kernel): qg [S, KV, Gp, D] grouped queries, ab the
    [KV, Gp] ALiBi table or None -> [out [S, KV, Gp, D], then the
    updated pools when k_new is given (k, v, and the scale pools when
    quantised)]."""
    S, KV, Gp, D = qg.shape
    NBLK, bs = k_cache.shape[:2]
    NB = block_table.shape[1]
    fused = k_new is not None
    alibi = ab is not None
    quant = k_scale is not None
    slots_arr = (slots.astype(jnp.int32) if fused
                 else jnp.full((S,), -1, jnp.int32))

    def kv_block_of(s, j, tbl_ref, ctx_ref, slot_ref):
        last = jnp.maximum(ctx_ref[s] - 1, 0) // bs
        if window > 0:
            j = _win_jbase_decode(ctx_ref[s], window, bs) + j
        j = jnp.minimum(j, last)
        # clip to the arena: a violated table contract must stay
        # contained (a wild block index can wedge the TPU runtime)
        return _arena_block(tbl_ref[s, j], NBLK)

    def kv_index(s, j, *refs):
        return (kv_block_of(s, j, *refs), 0, 0, 0)

    def sc_index(s, j, *refs):
        # a block's scale tile rides the SAME paging as its codes
        return (kv_block_of(s, j, *refs), 0, 0)

    def row_index(s, j, *refs):
        return (s, 0, 0)

    def q_index(s, j, *refs):
        return (s, 0, 0, 0)

    def tgt_block_of(s, j, tbl_ref, ctx_ref, slot_ref):
        # constant in j: the sequence's NEWEST block — flushed once
        last = jnp.maximum(ctx_ref[s] - 1, 0) // bs
        return _arena_block(tbl_ref[s, last], NBLK)

    def tgt_index(s, j, *refs):
        return (tgt_block_of(s, j, *refs), 0, 0, 0)

    def tgt_sc_index(s, j, *refs):
        return (tgt_block_of(s, j, *refs), 0, 0)

    NBw = min(NB, pl.cdiv(window, bs) + 1) if window > 0 else NB
    kv_spec = pl.BlockSpec((1, bs, KV, D), kv_index)
    sc_spec = pl.BlockSpec((1, bs, KV), sc_index)
    in_specs = [pl.BlockSpec((1, KV, Gp, D), q_index)]
    if fused:
        in_specs += [pl.BlockSpec((1, KV, D), row_index),
                     pl.BlockSpec((1, KV, D), row_index)]
    in_specs += [kv_spec, kv_spec]
    if quant:
        in_specs += [sc_spec, sc_spec]
    if alibi:  # whole [KV, Gp] slope table resident in VMEM
        in_specs.append(pl.BlockSpec((KV, Gp), lambda s, j, *refs: (0, 0)))
    out_specs = [pl.BlockSpec((1, KV, Gp, D), q_index)]
    out_shape = [jax.ShapeDtypeStruct(qg.shape, qg.dtype)]
    aliases = {}
    if fused:
        tgt_spec = pl.BlockSpec((1, bs, KV, D), tgt_index)
        out_specs += [tgt_spec, tgt_spec]
        out_shape += [jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                      jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)]
        # args: (3 scalar-prefetch), q, kn, vn, k_cache, v_cache
        # [, k_scale, v_scale] — code pools and scale tiles alias
        # through so the arena updates in place
        aliases = {6: 1, 7: 2}
        if quant:
            tgt_sc_spec = pl.BlockSpec((1, bs, KV), tgt_sc_index)
            out_specs += [tgt_sc_spec, tgt_sc_spec]
            out_shape += [
                jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
                jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype)]
            aliases = {6: 1, 7: 2, 8: 3, 9: 4}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, NBw),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((KV * Gp, D), jnp.float32),
            pltpu.VMEM((KV * Gp, 1), jnp.float32),
            pltpu.VMEM((KV * Gp, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, block_size=bs, scale=scale, n_kv=KV, gp=Gp,
            window=window, fused=fused, alibi=alibi, quant=quant,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpreted,
        name="paged_decode_grid",
    )(block_table, ctx_lens, slots_arr, qg,
      *((k_new, v_new) if fused else ()), k_cache, v_cache,
      *((k_scale, v_scale) if quant else ()), *((ab,) if alibi else ()))


def paged_decode_attention_xla(q, k_cache, v_cache, block_table, ctx_lens,
                               window: int = 0, alibi_slopes=None,
                               k_scale=None, v_scale=None):
    """jnp oracle for the kernels (tests; the engine's
    decode_impl='xla'; TP meshes whose heads do not divide).

    Gathers each sequence's paged KV into a dense [S, NB*bs, KV, D]
    context — O(S·max_ctx) memory, fine at test scale. THIS is the
    per-step block-table gather materialization the fused kernel
    exists to avoid; it stays as the reference/oracle path only.

    window > 0: token-exact sliding window per row.
    alibi_slopes: optional [H] — score bias slope_h * key_pos (the
    single query row makes the absolute form exact under softmax).
    k_scale/v_scale: int8-KV mode — per-block scale tiles
    [NBLK, bs, KV]; codes gather with their scales and dequantize to
    the compute dtype exactly as the kernel's fused dequant does."""
    S, H, D = q.shape
    _, bs, KV, _ = k_cache.shape
    KV *= _packing(q, k_cache)  # a packed pool: a row-major view of KV x D
    G = H // KV
    k = k_cache[block_table].reshape(S, -1, KV, D)  # [S, NB*bs, KV, D]
    v = v_cache[block_table].reshape(S, -1, KV, D)
    if k_scale is not None:
        ks = k_scale[block_table].reshape(S, -1, KV)
        vs = v_scale[block_table].reshape(S, -1, KV)
        k = (k.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    logits = jnp.einsum("shd,skhd->shk", q, k).astype(jnp.float32)
    logits = logits / (D**0.5)
    pos = jnp.arange(k.shape[1])
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        logits = logits + (slopes[None, :, None]
                           * pos.astype(jnp.float32)[None, None, :])
    mask = pos[None, :] < ctx_lens[:, None]  # [S, NB*bs]
    if window > 0:
        mask = mask & (pos[None, :] >= ctx_lens[:, None] - window)
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("shk,skhd->shd", probs, v)


# ---------------------------------------------------------------------------
# per-row live-block walk: one grid step a row, manual-DMA block loop
# (the fused single-token kernel and the shared-table attention)
# ---------------------------------------------------------------------------

# VMEM block buffers per row parity, each with its DMA semaphores: a
# block is being computed while the next _RING - 1 are in flight. At
# the serving cells' shapes 3 read 5% under 2 and 4 no better than 3
# (chip, PR 30: PERF.md section 6)
_RING = 3


def _live_span(ctx, block_size: int, window: int, new_col: bool = False):
    """[first, end) table slots of a row's LIVE blocks, from its context
    length (a scalar in a kernel, an array outside one): the sliding
    window's first slot to the last block that holds a cached column
    (new_col: the newest token, position ctx-1, is not in the cache)."""
    cached = jnp.maximum(ctx - 1, 0) if new_col else ctx
    first = _win_jbase_decode(ctx, window, block_size) if window > 0 else 0
    return first, (cached + block_size - 1) // block_size


def _block_ring(s, span_of, tbl_ref, k_any, v_any, bufk, bufv, lsem,
                n_seqs: int):
    """The walk's DMA side: blocks of a row's table move from the HBM
    arenas into a ring of VMEM buffers `bufk`/`bufv`
    [2, ring, bs, KV, D] ring - 1 iterations ahead of their use.
    `span_of(row)` gives the [first, end) table slots grid step `row`
    walks (empty: that step walks nothing and no load is issued for it).
    Scratch persists across grid steps, so the step of row s also issues
    the first ring - 1 blocks of row s+1 (buffer sets alternate by row
    parity): the common short-context case never stalls.

    Returns walk(first, end, visit, init): a fori_loop over step s's
    slots, carry = visit(j, k_block, v_block, carry) with the (bs, KV,
    D) blocks of slot j. Every load issued is waited by the step it was
    issued for, so `first`/`end` must be span_of(s)."""
    ring = bufk.shape[1]
    # every HBM index is CLAMPED to the arena: a violated block-table
    # contract (caller bug) must produce wrong-but-contained results,
    # never a wild DMA — an out-of-bounds manual DMA doesn't just crash
    # the program, it can wedge the TPU runtime for every later client
    n_blk = k_any.shape[0]

    def load(sq, j):
        blk = _arena_block(tbl_ref[sq, j], n_blk)
        pltpu.make_async_copy(k_any.at[blk], bufk.at[sq % 2, j % ring],
                              lsem.at[sq % 2, j % ring, 0]).start()
        pltpu.make_async_copy(v_any.at[blk], bufv.at[sq % 2, j % ring],
                              lsem.at[sq % 2, j % ring, 1]).start()

    def prefetch_first(sq):
        jb, nb = span_of(sq)
        for j in range(ring - 1):
            pl.when(jb + j < nb)(functools.partial(load, sq, jb + j))

    @pl.when(s == 0)
    def _prefetch_self():
        prefetch_first(0)

    @pl.when(s + 1 < n_seqs)
    def _prefetch_next_row():
        prefetch_first(s + 1)

    bufset = s % 2

    def walk(first, end, visit, init):
        def body(j, carry):
            bslot = j % ring

            @pl.when(j + ring - 1 < end)
            def _prefetch_ahead():
                load(s, j + ring - 1)

            pltpu.make_async_copy(k_any.at[0], bufk.at[bufset, bslot],
                                  lsem.at[bufset, bslot, 0]).wait()
            pltpu.make_async_copy(v_any.at[0], bufv.at[bufset, bslot],
                                  lsem.at[bufset, bslot, 1]).wait()
            return visit(j, bufk[bufset, bslot], bufv[bufset, bslot], carry)

        return jax.lax.fori_loop(first, end, body, init)

    return walk


def _row_softmax(walk, first, end, s, ctx, q_ref, ab_ref, *,
                 block_size: int, scale: float, n_kv: int, gp: int,
                 window: int, new_col: bool = False):
    """Row `s`'s online softmax over table slots [first, end) of `walk`,
    the cached columns of a row of context `ctx` (inside its window)
    live; new_col as _live_span's. Returns the per-head (running max,
    sum, accumulator) tuples of (Gp, 1), (Gp, 1), (Gp, D) f32; an empty
    span (ctx 0: batch padding) returns the initial carry."""
    bs = block_size
    D = q_ref.shape[-1]
    cached = jnp.maximum(ctx - 1, 0) if new_col else ctx

    def visit(j, kb, vb, carry):
        ms, ls, accs = carry  # per-head tuples: (Gp,1),(Gp,1),(Gp,D)
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (gp, bs), 1)
        live = cols < cached
        if window > 0:
            live = jnp.logical_and(live, cols >= ctx - window)
        ms2, ls2, accs2 = [], [], []
        for h in range(n_kv):
            q = q_ref[s, h]  # (Gp, D)
            st = _dot(q, kb[:, h, :], trans_b=True) * scale  # (Gp, bs)
            if ab_ref is not None:
                # bias slope_h * key_pos: exact up to the per-row shift
                # softmax cancels (single query at position ctx-1)
                st = st + ab_ref[h, :][:, None] * cols.astype(jnp.float32)
            st = jnp.where(live, st, NEG_INF)
            m_new = jnp.maximum(ms[h], jnp.max(st, axis=1, keepdims=True))
            p = jnp.exp(st - m_new)
            corr = jnp.exp(ms[h] - m_new)
            ls2.append(ls[h] * corr + jnp.sum(p, axis=1, keepdims=True))
            accs2.append(accs[h] * corr
                         + _dot(p.astype(vb.dtype), vb[:, h, :]))
            ms2.append(m_new)
        return tuple(ms2), tuple(ls2), tuple(accs2)

    init = (
        tuple(jnp.full((gp, 1), NEG_INF, jnp.float32)
              for _ in range(n_kv)),
        tuple(jnp.zeros((gp, 1), jnp.float32) for _ in range(n_kv)),
        tuple(jnp.zeros((gp, D), jnp.float32) for _ in range(n_kv)),
    )
    return walk(first, end, visit, init)


def _walk_live_blocks(
    s, tbl_ref, ctx_ref, q_ref, k_any, v_any, ab_ref,
    bufk, bufv, lsem, *,
    n_seqs: int, block_size: int, scale: float, n_kv: int, gp: int,
    window: int, new_col: bool,
):
    """Row `s`'s online softmax over the LIVE blocks of its table and
    nothing else (_live_span of its context, through _block_ring). Dead
    table slots cost nothing: no grid step, no DMA, no compare.

    new_col: the row's newest token (position ctx-1) is NOT in the
    cache — the caller folds it in as its own column (fused
    write+attend) — so only columns < ctx-1 are live. Otherwise the
    row was written before the call and columns < ctx are.

    Returns _row_softmax's carry; a row with no live block (ctx 0:
    batch padding) returns the initial one and issues no load."""

    def span_of(sq):
        return _live_span(ctx_ref[sq], block_size, window, new_col)

    walk = _block_ring(s, span_of, tbl_ref, k_any, v_any, bufk, bufv, lsem,
                       n_seqs)
    return _row_softmax(
        walk, *span_of(s), s, ctx_ref[s], q_ref, ab_ref,
        block_size=block_size, scale=scale, n_kv=n_kv, gp=gp, window=window,
        new_col=new_col)


def _store_row(o_ref, s, ls, accs):
    """Normalise row s's accumulators into o_ref[s]; a row that saw no
    column (sum 0: batch padding) stores zeros."""
    for h in range(len(ls)):
        l_safe = jnp.where(ls[h] == 0.0, 1.0, ls[h])
        o_ref[s, h] = (accs[h] / l_safe).astype(o_ref.dtype)


# query rows of a GROUP's matmuls (rows x Gp): adjacent rows that share
# a table are walked together up to 256 / Gp of them (32 at Gp 8, a
# serving cell's prefill chunk), a longer run as several groups
_GROUP_QUERY_ROWS = 256


def _group_rows(gp: int, n_rows: int) -> int:
    """Rows the shared-table walk takes as one group at most; 1 (no
    grouping) where a row's Gp query rows are not whole sublane tiles,
    so that a group's rows could not be stacked by a free reshape."""
    return 1 if gp % 8 else max(1, min(_GROUP_QUERY_ROWS // gp, n_rows))


def walk_groups(block_table, max_rows: int, xp=jnp):
    """[S] int32: for each row, the row that WALKS for it in the
    shared-table attention: itself, or the first row of its group.
    Adjacent rows with equal tables form a run (rows of one prefill
    chunk follow each other and share theirs); a run is cut into groups
    of `max_rows`. Equal tables that are not adjacent do not group.
    Written over `xp` (jnp in the kernel's entry, numpy in walk_reads)
    so that the two cannot disagree."""
    idx = xp.arange(block_table.shape[0], dtype=xp.int32)
    first = xp.concatenate([
        xp.ones((1,), bool),
        xp.any(block_table[1:] != block_table[:-1], axis=1)])
    marks = xp.where(first, idx, 0)
    if xp is np:
        run = np.maximum.accumulate(marks)  # the run's first row
    else:
        # the same running maximum as ONE masked [S, S] reduction: XLA
        # lowers a cumulative maximum on the TPU to a loop of S trips
        # (0.2 ms at 128 rows, 1.1 ms at 512: chip, PR 46)
        run = jnp.max(jnp.where(idx[None, :] <= idx[:, None],
                                marks[None, :], 0), axis=1)
    return idx - (idx - run) % max_rows


def _walk_group_rows(queries_per_kv: int, pack: int, n_rows: int) -> int:
    """_group_rows of a call of n_rows rows over a pool that packs
    `pack` KV heads a head, each serving queries_per_kv query heads:
    by the Gp the entry lays the queries out in (_packed_group of a
    packed pool's, _group_queries' max(G, 8))."""
    group = (_packed_group(queries_per_kv * pack) if pack > 1
             else queries_per_kv)
    return _group_rows(max(group, 8), n_rows)


def walk_reads(block_table, ctx_lens, block_size: int, queries_per_kv: int,
               pack: int = 1):
    """(blocks fetched, rows that rode) of one shared-table call over
    these host arrays, by the walk's own grouping: a group's blocks
    count once, by its longest row; a row rides when another row walks
    for it (rows of context 0, batch padding, left out).
    queries_per_kv: query heads a KV head serves (H / KV); pack: KV
    heads a pool's head holds (kv_pack).
    The scheduler's kv_block_reads / kv_grouped_rows."""
    rows = np.arange(len(ctx_lens))
    lead = walk_groups(block_table, _walk_group_rows(
        queries_per_kv, pack, len(ctx_lens)), np)
    reads = np.zeros(len(ctx_lens), np.int64)
    np.maximum.at(reads, lead, -(-ctx_lens // block_size))
    return int(reads.sum()), int(np.sum((ctx_lens > 0) & (lead != rows)))


def _wholly_live(j, shortest, longest, block_size: int, window: int):
    """Whether table slot j holds a live column in EVERY place for every
    row of a group whose contexts run from `shortest` to `longest`: the
    slot ends inside the shortest context and, under a window, starts
    inside the longest row's window. A group's visit masks the other
    slots of its span and no others (scalars in the kernel, arrays in
    walk_masks)."""
    whole = (j + 1) * block_size <= shortest
    if window > 0:
        whole = whole & (j * block_size >= longest - window)
    return whole


def walk_masks(block_table, ctx_lens, block_size: int, queries_per_kv: int,
               window: int = 0, pack: int = 1):
    """(blocks masked, blocks visited) by the GROUPS of one shared-table
    call over these host arrays, by the walk's own grouping and the
    kernel's own predicate (_wholly_live): of a group's span only the
    slots that can hold a dead column for some row take the visit with
    its compares and select. Rows walked alone are in neither count."""
    lead = walk_groups(block_table, _walk_group_rows(
        queries_per_kv, pack, len(ctx_lens)), np)
    masked = visited = 0
    for g in np.flatnonzero(np.bincount(lead) > 1):
        ctx = ctx_lens[lead == g]
        end = -(-ctx.max() // block_size)
        first = 0
        if window > 0 and (ctx > 0).any():
            first = (np.maximum(ctx[ctx > 0] - window, 0) // block_size).min()
        slots = np.arange(min(first, end), end)
        visited += len(slots)
        masked += int(np.sum(~_wholly_live(slots, ctx.min(), ctx.max(),
                                           block_size, window)))
    return masked, visited


def _decode_rows_kernel(
    tbl_ref, ctx_ref, first_ref, end_ref, n_ref,    # scalar prefetch
    q_ref, k_any, v_any,                            # inputs (caches in HBM)
    *rest,                                          # [ab, abv], out, scratch
    alibi: bool, group: int, n_seqs: int, **opts,
):
    """The shared-table decode attention: attend only. Every row is
    already in the cache (paged_kv_write ran first), so rows that share
    one table — a prefill chunk's rows, ctx rising by one — read the
    same blocks without racing a write.

    A step's part is in n_ref: 1, a row walked alone (_row_softmax, as
    the fused kernel walks its rows); n > 1, the first row of a GROUP of
    n adjacent rows of one table, which walks the blocks of the group's
    longest context ONCE ([first_ref, end_ref): the group's span) and
    multiplies each by the group's stacked queries, every row masked to
    its own context (_group_softmax), then stores all n rows; 0, a row
    its group's first row has stored already: the step only keeps the
    next row's prefetch chain going."""
    if alibi:  # [KV, Gp] slopes and their [KV, 1, group * Gp] tiling
        ab_ref, abv_ref, o_ref, bufk, bufv, lsem, *acc = rest
    else:
        o_ref, bufk, bufv, lsem, *acc = rest
        ab_ref = abv_ref = None
    s = pl.program_id(0)
    walk = _block_ring(s, lambda sq: (first_ref[sq], end_ref[sq]), tbl_ref,
                       k_any, v_any, bufk, bufv, lsem, n_seqs)
    n = n_ref[s]
    first, end = first_ref[s], end_ref[s]

    def alone():
        _, ls, accs = _row_softmax(walk, first, end, s, ctx_ref[s], q_ref,
                                   ab_ref, **opts)
        _store_row(o_ref, s, ls, accs)

    if group == 1:  # no row can ride: every step is a row of its own
        alone()
        return
    pl.when(n == 1)(alone)

    @pl.when(n > 1)
    def _group():
        _group_softmax(walk, first, end, s, n, ctx_ref, q_ref, abv_ref,
                       o_ref, *acc, group=group, **opts)


def _fold8(x, op, identity: float):
    """(n, w) -> (8, w): rows i, i + 8, ... combined by `op`, whole
    sublane tiles against each other, so that nothing crosses sublanes
    (n that is not whole tiles is filled up with `identity`)."""
    n, w = x.shape
    if n % 8:
        x = jnp.concatenate([x, jnp.full((-n % 8, w), identity, x.dtype)])
    return functools.reduce(op, [x[i:i + 8] for i in range(0, len(x), 8)])


def _rows8(x, n: int):
    """A sublane-replicated (8, w) value as (n, w): the same tile
    again and again."""
    return jnp.tile(x, (-(-n // 8), 1))[:n]


# KV heads whose matmuls a group's visit issues back to back: a matmul
# of one 128 x 128 operand costs ~0.15 us whatever its rows (chip, PR
# 50), most of it latency that the next head's matmul hides when
# nothing else stands between them; four heads' score tiles are what
# the visit then keeps (128 KB each at 256 query rows)
_HEADS_A_PHASE = 4


def _group_softmax(walk, first, end, s, n, ctx_ref, q_ref, abv_ref, o_ref,
                   q_sc, m_sc, l_sc, acc_sc, *, group: int, block_size: int,
                   scale: float, n_kv: int, gp: int, window: int):
    """Rows s .. s+n-1 (one table, n <= group) over table slots
    [first, end) of `walk`, each block multiplied by ALL their queries
    in one pair of matmuls a KV head: `group` rows x Gp query rows,
    stacked by a reshape (Gp is whole sublane tiles) ONCE a group into
    q_sc [KV, group * Gp, D]. The tile of `group` rows is static and
    starts at s or, near the end of the batch, before it: rows of the
    tile outside s .. s+n-1 are computed and not stored.

    The visit is TRANSPOSED: a head's scores are (bs, group * Gp), the
    block's columns down the sublanes and the query rows along the
    lanes (K as the streamed operand, the queries as the held one), so
    a query row's max and sum run DOWN a lane: whole tiles against each
    other (_fold8) and one 8-sublane reduction of the max; nothing
    crosses lanes. The running max m_sc and sum l_sc are [KV, 8,
    group * Gp] (the max in every sublane, the sum split over the 8 and
    added up once, at the store), the accumulator acc_sc [KV, D,
    group * Gp] is the output transposed (V^T p, transposed back once a
    group); all f32 VMEM scratch, not a loop carry (128 KB a head at D
    128).

    A block is MASKED (every query row to its own context, read from
    ctx_ref along the lanes, and window) only where it can hold a dead
    column for some row of the group (_wholly_live): past the group's
    shortest context or, under a window, before its longest row's
    window start. Every other block of the span takes the same visit
    without the compares and the select."""
    bs = block_size
    S, _, _, D = q_ref.shape
    rg = group * gp
    start = jnp.minimum(s, S - group)
    rows = pl.ds(start, group)

    def shortest_longest(i, c):
        return (jnp.minimum(c[0], ctx_ref[s + i]),
                jnp.maximum(c[1], ctx_ref[s + i]))

    shortest, longest = jax.lax.fori_loop(1, n, shortest_longest,
                                          (ctx_ref[s], ctx_ref[s]))
    # every query row's context, along the lanes
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (8, rg), 1) // gp
    ctx_row = jax.lax.fori_loop(
        0, group,
        lambda i, c: jnp.where(lane_row == i, ctx_ref[start + i], c),
        jnp.zeros((8, rg), jnp.int32))

    for h in range(n_kv):
        q_sc[h] = q_ref[rows, h].reshape(rg, D)
    m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
    l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def update(j, kb, vb, masked: bool):
        if masked or abv_ref is not None:
            cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, rg), 0)
        if masked:
            live = cols < _rows8(ctx_row, bs)
            if window > 0:
                live = jnp.logical_and(live,
                                       cols >= _rows8(ctx_row - window, bs))
        for h0 in range(0, n_kv, _HEADS_A_PHASE):
            heads = range(h0, min(h0 + _HEADS_A_PHASE, n_kv))
            sts = [_dot(kb[:, h, :], q_sc[h], trans_b=True) for h in heads]
            ps = []
            for h, st in zip(heads, sts):
                st = st * scale  # (bs, rg)
                if abv_ref is not None:
                    st = st + abv_ref[h] * cols.astype(jnp.float32)
                if masked:
                    # a block with no live column for a row (past a
                    # shorter context, before a later window) leaves
                    # that row's sums as they were, or, before its first
                    # live block, finite values its first live block's
                    # correction (exp(-1e30 - m)) zeroes
                    st = jnp.where(live, st, NEG_INF)
                m_prev = m_sc[h]  # (8, rg)
                m_new = jnp.maximum(m_prev, jnp.max(
                    _fold8(st, jnp.maximum, NEG_INF), axis=0, keepdims=True))
                p = jnp.exp(st - _rows8(m_new, bs))
                corr = jnp.exp(m_prev - m_new)
                l_sc[h] = l_sc[h] * corr + _fold8(p, jnp.add, 0.0)
                acc_sc[h] = acc_sc[h] * _rows8(corr, D)
                m_sc[h] = m_new
                ps.append(p.astype(vb.dtype))
            pvs = [_dot(vb[:, h, :], p, trans_a=True)  # (D, rg)
                   for h, p in zip(heads, ps)]
            for h, pv in zip(heads, pvs):
                acc_sc[h] = acc_sc[h] + pv

    def visit(j, kb, vb, carry):
        whole = _wholly_live(j, shortest, longest, bs, window)
        pl.when(whole)(lambda: update(j, kb, vb, False))
        pl.when(jnp.logical_not(whole))(lambda: update(j, kb, vb, True))
        return carry

    walk(first, end, visit, 0)

    ridx = start + jax.lax.broadcasted_iota(jnp.int32, (group, 1, 1), 0)
    mine = jnp.logical_and(ridx >= s, ridx < s + n)
    for h in range(n_kv):
        l = jnp.sum(l_sc[h], axis=0, keepdims=True)  # (1, rg)
        out = acc_sc[h] / jnp.where(l == 0.0, 1.0, l)
        out = jnp.where(ctx_row[:1] > 0, out, 0.0)  # batch padding: zeros
        out = out.T.reshape(group, gp, D).astype(o_ref.dtype)
        o_ref[rows, h] = jnp.where(mine, out, o_ref[rows, h])


def _decode_fused_kernel(
    tbl_ref, ctx_ref, slot_ref,                     # scalar prefetch
    q_ref, kn_ref, vn_ref, k_any, v_any,            # inputs (caches in HBM)
    *rest,                                          # [ab], outs, scratch
    alibi: bool, **walk,
):
    """One grid step per SEQUENCE (compile size O(1) in batch — an
    earlier all-sequences-unrolled variant ran ~8us/call faster at S=8
    but its Mosaic compile exploded at S=64). The KV arenas stay in HBM
    (memory_space=ANY) and _walk_live_blocks reads ONLY the live blocks
    of this sequence's table; the new token's row is DMA'd straight
    into its cache slot (2 KB, vs RMW-ing whole 256 KB blocks through
    the output pipeline), and its attention contribution enters as one
    extra online-softmax column from VMEM."""
    if alibi:  # [KV, Gp] ALiBi slope table rides as the LAST input
        ab_ref, o_ref, ck_any, cv_any, bufk, bufv, wsem, lsem = rest
    else:
        o_ref, ck_any, cv_any, bufk, bufv, wsem, lsem = rest
        ab_ref = None
    n_seqs, bs = walk["n_seqs"], walk["block_size"]
    scale, n_kv = walk["scale"], walk["n_kv"]
    n_blk = k_any.shape[0]
    s = pl.program_id(0)
    ctx = ctx_ref[s]
    slot = slot_ref[s]

    ms, ls, accs = _walk_live_blocks(
        s, tbl_ref, ctx_ref, q_ref, k_any, v_any, ab_ref,
        bufk, bufv, lsem, new_col=True, **walk)

    if alibi:
        # fold the new token's ALiBi bias into its online-softmax column
        ab_newcol = [ab_ref[h, :][:, None] * (ctx - 1).astype(jnp.float32)
                     for h in range(n_kv)]

    # this sequence's new row -> its cache slot, started only AFTER its
    # own block loads are consumed: the write may tear bf16 values
    # mid-DMA, and although the row's column is masked out of the
    # softmax, 0 * NaN from a torn load would still poison the
    # accumulator. Other sequences' loads never touch this block (rows
    # are distinct sequences). Waited at the final grid step.
    @pl.when(slot >= 0)
    def _write_row():
        blk = _arena_block(slot // bs, n_blk)
        off = slot % bs
        pltpu.make_async_copy(kn_ref.at[s], ck_any.at[blk, off],
                              wsem.at[s, 0]).start()
        pltpu.make_async_copy(vn_ref.at[s], cv_any.at[blk, off],
                              wsem.at[s, 1]).start()

    # the new token's own column (kn/vn are VMEM-resident inputs)
    def newcol(carry):
        ms, ls, accs = carry
        ms2, ls2, accs2 = [], [], []
        for h in range(n_kv):
            q = q_ref[s, h]
            stn = (jnp.sum(q * kn_ref[s, h][None, :], axis=1,
                           keepdims=True) * scale).astype(jnp.float32)
            if alibi:
                stn = stn + ab_newcol[h]
            m_new = jnp.maximum(ms[h], stn)
            p = jnp.exp(stn - m_new)
            corr = jnp.exp(ms[h] - m_new)
            ls2.append(ls[h] * corr + p)
            accs2.append(accs[h] * corr
                         + p * vn_ref[s, h][None, :].astype(jnp.float32))
            ms2.append(m_new)
        return tuple(ms2), tuple(ls2), tuple(accs2)

    ms, ls, accs = jax.lax.cond(slot >= 0, newcol, lambda c: c,
                                (ms, ls, accs))
    _store_row(o_ref, s, ls, accs)

    @pl.when(s == n_seqs - 1)
    def _wait_rows():
        for sq in range(n_seqs):
            @pl.when(slot_ref[sq] >= 0)
            def _w(sq=sq):
                blk = _arena_block(slot_ref[sq] // bs, n_blk)
                off = slot_ref[sq] % bs
                pltpu.make_async_copy(kn_ref.at[sq], ck_any.at[blk, off],
                                      wsem.at[sq, 0]).wait()
                pltpu.make_async_copy(vn_ref.at[sq], cv_any.at[blk, off],
                                      wsem.at[sq, 1]).wait()


# scoped VMEM the per-row walk may ask Mosaic for (the default, 16 MiB,
# is met by 128 rows of 16 KV heads: 8 MiB of whole-array q + out and
# 4 MiB of block buffers), and what its buffers may take of that
_WALK_VMEM_LIMIT = 64 << 20
_WALK_VMEM_BUDGET = 48 << 20


def _walks_live_blocks(qg, k_cache) -> bool:
    """Whether the per-row live-block walk can take these shapes; what
    it cannot stays on the (S, NB) BlockSpec grid. The walk DMAs one
    whole cache block (bs, KV, D) out of the HBM arena by hand, and
    Mosaic takes such a slice only if its trailing dims fill whole
    tiles (_whole_tiles: not D = 64, not 16-bit pools of 1, 3, 5-7 or
    12 KV heads). BlockSpec tiles are padded by the pipeline instead,
    so the grid takes every shape."""
    _, bs, KV, D = k_cache.shape
    itemsize = k_cache.dtype.itemsize
    if itemsize not in (2, 4) or not _whole_tiles(KV, D, itemsize):
        return False
    S, _, Gp, _ = qg.shape
    rg = _group_rows(Gp, S) * Gp
    need = (4 * _RING * bs * KV * D * itemsize      # k, v x row parity
            + 2 * qg.size * qg.dtype.itemsize       # whole-array q, out
            # a group's stacked queries, its f32 accumulator and its
            # running max and sum, 8 sublanes each
            + KV * rg * (D * (qg.dtype.itemsize + 4) + 2 * 8 * 4))
    return need <= _WALK_VMEM_BUDGET


# the grouped body doubles what a call takes to lower: +1.9 s over the
# dense cell's 16 layers without the boundary, AOT for v5e
@kernel_jit(6, 7, 8)
def _attend_live_blocks(qg, ab, k_cache, v_cache, block_table, ctx_lens,
                        window: int, scale: float, interpreted: bool):
    """paged_decode_attention's unfused, unquantised case on the live-
    block walk: qg [S, KV, Gp, D] grouped queries, ab the [KV, Gp] ALiBi
    table or None -> [S, KV, Gp, D]. Which rows walk alone and which as
    a group is read HERE from the tables (walk_groups) and handed to the
    kernel as each grid step's part: its span of table slots and the
    rows it stores."""
    S, KV, Gp, D = qg.shape
    bs = k_cache.shape[1]
    group = _group_rows(Gp, S)
    ctx_lens = ctx_lens.astype(jnp.int32)
    first, end = _live_span(ctx_lens, bs, window)
    first = jnp.broadcast_to(first, end.shape).astype(jnp.int32)
    n = jnp.ones((S,), jnp.int32)
    if group > 1:
        # [walking row, row]: a group's span runs from its rows' first
        # live slot to its longest row's end; a row that rides walks
        # nothing
        mine = (walk_groups(block_table, group)[None, :]
                == jnp.arange(S, dtype=jnp.int32)[:, None])
        n = jnp.sum(mine, axis=1, dtype=jnp.int32)
        end = jnp.max(jnp.where(mine, end[None, :], 0), axis=1)
        first = jnp.minimum(end, jnp.min(
            jnp.where(mine & (ctx_lens > 0)[None, :], first[None, :],
                      block_table.shape[1]), axis=1))
    alibi = () if ab is None else (
        ab, jnp.tile(ab, (1, group))[:, None, :])
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S,),
        in_specs=[vmem, hbm, hbm] + [vmem] * len(alibi),
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((2, _RING, bs, KV, D), k_cache.dtype),
            pltpu.VMEM((2, _RING, bs, KV, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, _RING, 2)),
        ] + ([
            pltpu.VMEM((KV, group * Gp, D), qg.dtype),
            pltpu.VMEM((KV, 8, group * Gp), jnp.float32),
            pltpu.VMEM((KV, 8, group * Gp), jnp.float32),
            pltpu.VMEM((KV, D, group * Gp), jnp.float32),
        ] if group > 1 else []),
    )
    return pl.pallas_call(
        functools.partial(
            _decode_rows_kernel, n_seqs=S, block_size=bs, scale=scale,
            n_kv=KV, gp=Gp, window=window, alibi=ab is not None,
            group=group,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WALK_VMEM_LIMIT),
        interpret=interpreted,
        # the trace name of THE SHARED-TABLE DECODE ATTENTION, whatever
        # its grid: the benchmark's readers, chip_smoke.py and the AOT
        # tests find the program by it (the int8 and fused-write cases,
        # still on the (S, NB) grid, carry the same name)
        name="paged_decode_grid",
    )(block_table, ctx_lens, first, end, n, qg, k_cache, v_cache, *alibi)


def supports_fused_v2(head_dim: int) -> bool:
    """The per-sequence-grid kernel's row-write DMA needs lane-aligned
    (KV, D) slices."""
    return head_dim % 128 == 0


# DMA semaphores a core has (2,048 bytes of them, 4 each: AOT for v5e,
# libtpu 0.0.34, "Allocation (size=4096) would exceed memory (size=2048)
# ... space=sflag" at 512 rows)
_DMA_SEMAPHORES = 512


def fused_write_fits(n_rows: int) -> bool:
    """Whether a fused write+attend call can take this many rows: the
    walk with the write (paged_decode_fused) holds two DMA semaphores a
    row until its last grid step, beside its block ring's. A wider step
    writes first and attends after (paged_kv_write, then the walk), as
    the shared-table program does; who fuses asks here first."""
    return 2 * n_rows + 2 * _RING * 2 <= _DMA_SEMAPHORES


def paged_decode_fused(q, k_cache, v_cache, block_table, ctx_lens,
                       k_new, v_new, slots, window: int = 0,
                       alibi_slopes=None, scale=None):
    """Fused single-token decode: write the batch's new KV rows into the
    paged arenas AND attend over them, one kernel launch (what
    paged_decode_attention runs for k_new on unquantised pools at
    D % 128 == 0; other head dims and int8 pools take _decode_kernel's
    fused mode).

    Same contract as paged_decode_attention's fused mode: rows are
    DISTINCT sequences; ctx INCLUDES the new token; slots [S] are the
    new tokens' flat cache slots (-1 = pad row, nothing written).
    Returns (out [S, H, D], k_cache, v_cache) with the arenas updated in
    place (donate them).

    Requires head_dim % 128 == 0: the per-row (KV, D) write DMA must be
    lane-aligned (supports_fused_v2)."""
    scale = 1.0 / (q.shape[-1]**0.5) if scale is None else scale
    return _decode_fused(q, k_cache, v_cache, block_table, ctx_lens,
                         k_new, v_new, slots, alibi_slopes, window, scale,
                         interpret())


@kernel_jit(9, 10, 11)
def _decode_fused(q, k_cache, v_cache, block_table, ctx_lens, k_new, v_new,
                  slots, alibi_slopes, window: int, scale: float,
                  interpreted: bool):
    S, H, D = q.shape
    bs, KV = k_cache.shape[1:3]
    alibi = alibi_slopes is not None
    qg, ab, G, Gp = _group_queries(q, KV, alibi_slopes)
    ab = (ab,) if alibi else ()

    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            vmem(), vmem(), vmem(),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + ([vmem()] if alibi else []),
        out_specs=[
            vmem(),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, _RING, bs, KV, D), k_cache.dtype),
            pltpu.VMEM((2, _RING, bs, KV, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((S, 2)),
            pltpu.SemaphoreType.DMA((2, _RING, 2)),
        ],
    )
    out, ck, cv = pl.pallas_call(
        functools.partial(
            _decode_fused_kernel, n_seqs=S, block_size=bs, scale=scale,
            n_kv=KV, gp=Gp, window=window, alibi=alibi,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, KV, Gp, D), q.dtype),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        # args: 3 scalar prefetch, q, kn, vn, k_cache, v_cache [, ab]
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WALK_VMEM_LIMIT),
        interpret=interpreted,
        name="paged_decode_fused",
    )(block_table, ctx_lens, slots.astype(jnp.int32), qg,
      k_new, v_new, k_cache, v_cache, *ab)
    return out[:, :, :G, :].reshape(S, H, D), ck, cv


# ---------------------------------------------------------------------------
# paged KV write
# ---------------------------------------------------------------------------

def _kv_write_rows_kernel(
    slots_ref, kn_any, vn_any, ck_in, cv_in, ck_out, cv_out, sem,
    *, block_size: int, n_blocks: int,
):
    """kv_write_path "rows": every live row of the call goes to its
    cache slot by ONE DMA a pool, HBM to HBM. Rows and pools stay where
    they are (memory_space=ANY); nothing passes through VMEM. The pool
    is [NBLK, bs, KV, D], so [blk, off] indexes two UNTILED dims and a
    copy is the slot's whole (KV, D) tile set, 1-4 KB. One grid step: a
    loop starts the copies, a second waits them, all on ONE DMA
    semaphore (the copies of a call are of one size, so a wait takes
    whichever has landed, and when every started copy has been waited
    every row has). The aliased inputs are the outputs' buffers and are
    not read."""
    del ck_in, cv_in

    def copies(t):
        slot = slots_ref[t]
        blk = _arena_block(slot // block_size, n_blocks)
        off = slot % block_size
        return (pltpu.make_async_copy(kn_any.at[t], ck_out.at[blk, off], sem),
                pltpu.make_async_copy(vn_any.at[t], cv_out.at[blk, off], sem))

    def each_live_row(do):
        def row(t, carry):
            @pl.when(slots_ref[t] >= 0)
            def _():
                for copy in copies(t):
                    do(copy)
            return carry

        jax.lax.fori_loop(0, slots_ref.shape[0], row, 0)

    each_live_row(lambda copy: copy.start())
    each_live_row(lambda copy: copy.wait())


def _kv_write_blocks_kernel(
    slots_ref, kn_ref, vn_ref, ck_in, cv_in, ck_out, cv_out,
    *, block_size: int, n_blocks: int,
):
    """kv_write_path "blocks": read-modify-write one token row into its
    cache block, for the pools whose rows Mosaic refuses as a DMA
    (_whole_tiles). Whole cache blocks pass through VMEM, one grid step
    a token: tokens are pre-sorted by slot so consecutive grid steps
    hitting the same block keep it resident, and the block is copied
    from the aliased input only on first visit (a later copy would
    erase rows written by earlier same-block steps). A call moves its
    DISTINCT blocks' bytes in and out, ~200 x its rows' at 128 tokens a
    block (0.16 ms a dense layer's call on a v5e, PERF.md section 6,
    PR 52)."""
    t = pl.program_id(0)
    slot = slots_ref[t]

    def cb(i):  # clamped block id of token i (same clip as cache_index)
        return _arena_block(slots_ref[i] // block_size, n_blocks)

    first = jnp.logical_or(t == 0, cb(t) != cb(jnp.maximum(t - 1, 0)))

    @pl.when(first)
    def _copy():
        ck_out[...] = ck_in[...]
        cv_out[...] = cv_in[...]

    @pl.when(slot >= 0)
    def _write():
        # Mosaic cannot vector-store at a dynamic sublane offset, so the
        # row write is a masked full-block select (VPU, block in VMEM)
        off = slot % block_size
        row = jax.lax.broadcasted_iota(jnp.int32, (1, block_size, 1, 1), 1)
        mask = row == off
        kn = kn_ref[0][None, None]  # (1, 1, KV, D)
        vn = vn_ref[0][None, None]
        ck_out[...] = jnp.where(mask, kn, ck_out[...])
        cv_out[...] = jnp.where(mask, vn, cv_out[...])


def paged_kv_write(cache_k, cache_v, k_new, v_new, flat_slots):
    """Write [T, KV, D] new KV rows into [NBLK, bs, KV, D] caches at flat
    slot ids [T] (block*bs + offset; -1 rows are dropped; a slot past
    the arena lands in its last block, _arena_block). The TPU-native
    fused-cache-store (ref: inference/v2/kernels/ragged_ops/
    linear_blocked_kv_rotary/ — rotary is applied upstream in XLA).
    A row moves by one DMA of its own bytes and no block is read or
    written back, so a call costs its rows and not the blocks they land
    in; a pool shape Mosaic refuses that copy of keeps the block
    read-modify-write (kv_write_path picks, from the pool's shape and
    dtype alone). Either way bytes are copied: the pools after a call
    are bit-identical to the jnp scatter's (inference/model.
    _write_kv_xla) in every dtype. The live slots of ONE call are
    distinct (a sequence's positions are, and sequences share no block
    they write); two rows with one slot land in no promised order, as
    in the scatter.
    Rows of a packed pool (kv_pack) may come as [T, KV, D] of the model's
    heads: the same bytes as the pool's [T, KV / 2, 128]."""
    return _kv_write(cache_k, cache_v, k_new, v_new, flat_slots, interpret())


@kernel_jit(5)
def _kv_write(cache_k, cache_v, k_new, v_new, flat_slots, interpreted: bool):
    NBLK, bs, KV, D = cache_k.shape
    T = flat_slots.shape[0]
    # a packed pool (kv_pack) takes its rows in its own shape
    k_new, v_new = _pack_rows(k_new, cache_k), _pack_rows(v_new, cache_v)
    slots = flat_slots.astype(jnp.int32)
    if kv_write_path(cache_k.shape, cache_k.dtype) == "rows":
        kernel = _kv_write_rows_kernel
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[hbm] * 4,
            out_specs=[hbm] * 2,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        )
    else:
        kernel = _kv_write_blocks_kernel
        order = jnp.argsort(slots)
        slots, k_new, v_new = slots[order], k_new[order], v_new[order]

        def cache_index(t, slots_ref):
            # clip both ends: negatives are pad rows, and an over-range
            # slot (caller contract bug) must stay inside the arena
            return (_arena_block(slots_ref[t] // bs, NBLK), 0, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, KV, D), lambda t, slots_ref: (t, 0, 0)),
                pl.BlockSpec((1, KV, D), lambda t, slots_ref: (t, 0, 0)),
                pl.BlockSpec((1, bs, KV, D), cache_index),
                pl.BlockSpec((1, bs, KV, D), cache_index),
            ],
            out_specs=[
                pl.BlockSpec((1, bs, KV, D), cache_index),
                pl.BlockSpec((1, bs, KV, D), cache_index),
            ],
            scratch_shapes=[],
        )
    return pl.pallas_call(
        functools.partial(kernel, block_size=bs, n_blocks=NBLK),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
            jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
        ],
        # alias caches through: rows land in place, no copy of the arena
        input_output_aliases={3: 0, 4: 1},
        interpret=interpreted,
        name="paged_kv_write",
    )(slots, k_new, v_new, cache_k, cache_v)


def paged_scale_write(k_scale, v_scale, ks_new, vs_new, flat_slots):
    """Write [T, KV] per-row quant scales into the [NBLK, bs, KV] scale
    pools at flat slot ids [T] — the scale half of a quantized
    paged_kv_write. Rides the SAME entry through a [NBLK, bs, 1, KV]
    view (the KV axis lands on the lane dim, so the block tile stays
    lane-aligned and dtype-generic), and so behind the same jit
    boundary: the layers' scale writes are one lowering. A slot's KV
    scales are a part of one lane tile, which no DMA addresses, so
    this view takes kv_write_path "blocks" at every KV under 128."""
    NBLK, bs, KV = k_scale.shape
    ck, cv = paged_kv_write(
        k_scale.reshape(NBLK, bs, 1, KV), v_scale.reshape(NBLK, bs, 1, KV),
        ks_new[:, None, :], vs_new[:, None, :], flat_slots)
    return ck.reshape(NBLK, bs, KV), cv.reshape(NBLK, bs, KV)


# ---------------------------------------------------------------------------
# latent attention (MLA): one shared key of width latent + rope whose
# first `v_dim` values are also the value
#
# The pool is [num_blocks, block_size, C], one row a token and no V
# pool. C is kv_lora_rank + qk_rope_head_dim PADDED to whole lanes
# (latent_lanes: 576 -> 640): the TPU's tiled HBM layout pads the
# minor dim to 128 whatever the array says, and Mosaic takes a manual
# DMA of a block only if its minor dim fills whole tiles ("Slice shape
# along dimension 2 must be aligned to tiling (128), but is 576", AOT
# for v5e). So the pad lanes are in the shape, hold zeros, and cost
# 64 / 576 = 11% of the pool's bytes and of the score matmul. Decode is the
# absorbed form: every head's query arrives already multiplied into the
# latent space, [S, H, C], so a row is multi-query attention of H heads
# over ONE key, and all H heads are the rows of one MXU matmul.
# ---------------------------------------------------------------------------

def _latent_rows_kernel(
    tbl_ref, ctx_ref, grp_ref, uni_ref,             # scalar prefetch
    q_ref, pool_any,                                # inputs (pool in HBM)
    o_ref, buf, lsem, *acc,                         # out, scratch
    n_seqs: int, block_size: int, v_dim: int, tile: int,
):
    """A grid step owns a TILE of `tile` adjacent rows. Each row's
    online softmax runs over the live blocks of its table, all heads at
    once, with every live block of a TABLE read from HBM once for all
    of that table's rows: `buf` [2, NB, bs, C] holds the whole table (a
    buffer set per table, alternating), rows of one table follow each
    other (grp_ref: the table's index, rising by one where the table
    changes; _LATENT_GROUP blocks are multiplied a loop trip), and a
    row that shares its predecessor's table finds that row's blocks
    resident and loads only the blocks its longer context adds. The row
    BEFORE a new table starts all of that table's first row's loads
    into the other set, so they land while it computes. Every load
    started is waited exactly once, by the first row that needs it.
    Live blocks only (as _walk_live_blocks): a dead table slot costs no
    DMA and no step.

    What a step does is in uni_ref (latent_tiles): where the tile's rows
    all name one table (a prefill chunk's inner rows, padding), the
    tile takes ONE visit (_latent_tile_visit: a trip multiplies its
    blocks by the tile's stacked queries); anywhere else (decode rows, a
    chunk's edge) each row of the tile walks alone, by the same loop
    body as a tile of one."""
    bs = block_size
    n_blk = pool_any.shape[0]
    G = _LATENT_GROUP
    base = pl.program_id(0) * tile

    def nblk_of(r):
        return pl.cdiv(ctx_ref[r], bs)

    def fresh_of(r):
        return jnp.logical_or(
            r == 0, grp_ref[r] != grp_ref[jnp.maximum(r - 1, 0)])

    def load_range(r, lo, hi):
        def one(j, c):
            blk = _arena_block(tbl_ref[r, j], n_blk)
            pltpu.make_async_copy(pool_any.at[blk], buf.at[grp_ref[r] % 2, j],
                                  lsem.at[grp_ref[r] % 2, j]).start()
            return c

        jax.lax.fori_loop(lo, hi, one, 0)

    def start_table_after(r):
        """The row before a new table starts that table's first row's
        loads, into the other set (r: a row walking alone, or the last
        row of a tile's visit, at the visit's start)."""
        @pl.when(r + 1 < n_seqs)
        def _next_table():
            nxt = jnp.minimum(r + 1, n_seqs - 1)

            @pl.when(fresh_of(nxt))
            def _start():
                load_range(nxt, 0, nblk_of(nxt))

    def wait_group(bufset, j0, n_blocks: int, resident, nblk):
        for i in range(n_blocks):
            @pl.when(jnp.logical_and(j0 + i < nblk, j0 + i >= resident))
            def _wait(j=j0 + i):
                pltpu.make_async_copy(pool_any.at[0], buf.at[bufset, j],
                                      lsem.at[bufset, j]).wait()

    def group_of(bufset, j0, n_blocks: int):
        return buf[bufset, pl.ds(j0, n_blocks)].reshape(
            n_blocks * bs, buf.shape[-1])

    @pl.when(base == 0)
    def _first_row():
        # a group's last blocks may lie beyond the row's live ones: they
        # are masked out of the softmax, but 0 x NaN would still poison
        # the accumulator, so the buffers start as zeros and only ever
        # hold pool rows after that
        def zero(i, c):
            buf[i // buf.shape[1], i % buf.shape[1]] = jnp.zeros(
                buf.shape[2:], buf.dtype)
            return c

        jax.lax.fori_loop(0, 2 * buf.shape[1], zero, 0)
        load_range(0, 0, nblk_of(0))

    def row_alone(i):
        """Row base + i, the tile's i-th, walks its own blocks."""
        s = base + i
        ctx = ctx_ref[s]
        nblk = nblk_of(s)
        fresh = fresh_of(s)
        bufset = grp_ref[s] % 2
        # blocks of this table resident before this row (loaded AND
        # waited by the rows before it); a fresh row's were started,
        # not waited
        resident = jnp.where(fresh, 0, nblk_of(jnp.maximum(s - 1, 0)))

        @pl.when(jnp.logical_not(fresh))
        def _the_blocks_a_longer_context_adds():
            load_range(s, resident, nblk)

        start_table_after(s)
        q = q_ref[i]  # (H, C), the softmax scale folded in by the caller
        H = q.shape[0]

        def body(g, carry):
            m, l, acc = carry
            j0 = g * G
            wait_group(bufset, j0, G, resident, nblk)
            # G blocks a step: the accumulator is rescaled once for
            # G * bs columns (at one block a step the (H, v_dim) f32
            # rescale, not the MXU, set the pace: chip, PR 33)
            kb = group_of(bufset, j0, G)
            st = _dot(q, kb, trans_b=True)  # (H, G * bs)

            def mask(st):
                cols = j0 * bs + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 1)
                return jnp.where(cols < ctx, st, NEG_INF)

            # only a row's last group holds columns past its context
            st = jax.lax.cond((j0 + G) * bs > ctx, mask, lambda st: st, st)
            m_new = jnp.maximum(m, jnp.max(st, axis=1, keepdims=True))
            p = jnp.exp(st - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * corr + _dot(p.astype(kb.dtype), kb[:, :v_dim])
            return m_new, l, acc

        init = (jnp.full((H, 1), NEG_INF, jnp.float32),
                jnp.zeros((H, 1), jnp.float32),
                jnp.zeros((H, v_dim), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, pl.cdiv(nblk, G), body, init)
        l_safe = jnp.where(l == 0.0, 1.0, l)  # ctx 0: batch padding, zeros
        o_ref[i] = (acc / l_safe).astype(o_ref.dtype)

    if tile == 1:
        row_alone(0)
        return

    one_table = uni_ref[pl.program_id(0)] != 0

    @pl.when(jnp.logical_not(one_table))
    def _each_row_alone():
        def one(i, c):
            row_alone(i)
            return c

        jax.lax.fori_loop(0, tile, one, 0)

    @pl.when(one_table)
    def _one_visit():
        def shortest_longest(i, c):
            return (jnp.minimum(c[0], ctx_ref[base + i]),
                    jnp.maximum(c[1], ctx_ref[base + i]))

        shortest, longest = jax.lax.fori_loop(
            1, tile, shortest_longest, (ctx_ref[base], ctx_ref[base]))
        nblk = pl.cdiv(longest, bs)
        fresh = fresh_of(base)
        bufset = grp_ref[base] % 2
        before = nblk_of(jnp.maximum(base - 1, 0))
        resident = jnp.where(fresh, 0, before)
        # a fresh tile's first row's blocks were started by the row
        # before it; the tile starts what its longer rows add
        load_range(base, jnp.where(fresh, nblk_of(base), before), nblk)
        start_table_after(base + tile - 1)

        def blocks(j0, n_blocks: int):
            wait_group(bufset, j0, n_blocks, resident, nblk)
            return group_of(bufset, j0, n_blocks)

        _latent_tile_visit(blocks, nblk, base, shortest, ctx_ref, q_ref,
                           o_ref, *acc, block_size=bs, v_dim=v_dim)


def _latent_tile_visit(blocks, nblk, base, shortest, ctx_ref, q_ref, o_ref,
                       m_sc, l_sc, acc_sc, *, block_size: int, v_dim: int):
    """Rows base .. base + R - 1 (q_ref and o_ref are the tile's [R, H,
    .] blocks; one table) over table slots [0, nblk): a loop trip
    multiplies _LATENT_GROUP blocks (`blocks(j0)`, waited) by ALL the
    tile's queries, R x H query rows stacked by a reshape (H is whole
    sublane tiles), where R rows alone stream the same operand tiles R
    times by H rows each. The running max m_sc and sum l_sc [R x H, 1]
    and the accumulator acc_sc [R x H, v_dim] are f32 VMEM scratch, not
    a loop carry (1 MB at 512 query rows).

    A trip works in SUB-TILES of _LATENT_SUB query rows (one row at 128
    heads), ordered as PR 50 learned in the K/V walk: every sub-tile's
    score matmul back to back, then their softmaxes, then their value
    matmuls, so that one sub-tile's vector work has another's matmul to
    run under (0.245 us a (block, row) for 0.27-0.29 with sub-tiles of
    256 or 512 rows, and 0.356 a row alone: chip, PR 54). Not
    transposed (_group_softmax is): the value matmul would want the
    trip's (G x bs, v_dim) values transposed, 16 tiles a trip where the
    K/V visit transposes one, and the MXU is at nine tenths of its
    time as it is.

    The span's last blocks, fewer than a trip's, are multiplied AS THEY
    ARE (a trip of 1 .. G - 1 blocks, one of each in the kernel's
    text): filled up to a whole trip they cost a span of 12 blocks an
    eighth more (0.245 -> 0.218 us). A whole trip is MASKED, every
    query row to its own context, only where it can hold a dead column
    for some row of the tile (_wholly_live of its last slot, by the
    tile's shortest context); the short trip at the end always is."""
    bs = block_size
    G = _LATENT_GROUP
    R, H, _ = q_ref.shape
    sub = max(1, min(R, _LATENT_SUB // H))  # rows of a sub-tile
    subs = [(r0, min(sub, R - r0)) for r0 in range(0, R, sub)]

    def ctx_of(r0, n):
        """[n * H, 1] int32: each query row's context."""
        if n == 1:
            return ctx_ref[base + r0]
        row = jax.lax.broadcasted_iota(jnp.int32, (n * H, 1), 0) // H
        return jax.lax.fori_loop(
            0, n, lambda i, c: jnp.where(row == i, ctx_ref[base + r0 + i], c),
            jnp.zeros((n * H, 1), jnp.int32))

    m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
    l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def update(j0, n_blocks: int, masked: bool):
        kb = blocks(j0, n_blocks)  # (n_blocks * bs, C)
        sts = [_dot(q_ref[r0:r0 + n].reshape(n * H, q_ref.shape[-1]), kb,
                    trans_b=True) for r0, n in subs]  # (n * H, n_blocks * bs)
        ps, corrs = [], []
        for (r0, n), st in zip(subs, sts):
            rows = slice(r0 * H, (r0 + n) * H)
            if masked:
                cols = j0 * bs + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 1)
                # a trip with no live column for a row (past a shorter
                # context) leaves that row's sums as they were
                st = jnp.where(cols < ctx_of(r0, n), st, NEG_INF)
            m_prev = m_sc[rows]
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=1, keepdims=True))
            p = jnp.exp(st - m_new)
            corrs.append(jnp.exp(m_prev - m_new))
            l_sc[rows] = (l_sc[rows] * corrs[-1]
                          + jnp.sum(p, axis=1, keepdims=True))
            m_sc[rows] = m_new
            ps.append(p.astype(kb.dtype))
        pvs = [_dot(p, kb[:, :v_dim]) for p in ps]
        for (r0, n), corr, pv in zip(subs, corrs, pvs):
            rows = slice(r0 * H, (r0 + n) * H)
            acc_sc[rows] = acc_sc[rows] * corr + pv

    def trip(g, carry):
        j0 = g * G
        whole = _wholly_live(j0 + G - 1, shortest, shortest, bs, 0)
        pl.when(whole)(lambda: update(j0, G, False))
        pl.when(jnp.logical_not(whole))(lambda: update(j0, G, True))
        return carry

    jax.lax.fori_loop(0, nblk // G, trip, 0)
    # the span's last blocks, fewer than a trip's: multiplied as they
    # are, not filled up to a trip with blocks that hold nothing live
    for n_blocks in range(1, G):
        pl.when(nblk % G == n_blocks)(
            lambda n_blocks=n_blocks: update(nblk // G * G, n_blocks, True))

    for r0, n in subs:
        rows = slice(r0 * H, (r0 + n) * H)
        l = l_sc[rows]
        out = acc_sc[rows] / jnp.where(l == 0.0, 1.0, l)
        out = jnp.where(ctx_of(r0, n) > 0, out, 0.0)  # batch padding: zeros
        o_ref[r0:r0 + n] = out.reshape(n, H, v_dim).astype(o_ref.dtype)


# live blocks the latent walk multiplies in one trip of its loop
_LATENT_GROUP = 4
# query rows (rows x heads) a tile's visit stacks in its matmuls at most,
# and of the sub-tiles a trip orders its work by
_LATENT_QUERY_ROWS = 512
_LATENT_SUB = 128


def latent_lanes(latent_dim: int) -> int:
    """The latent pool's minor dim: `latent_dim` padded to whole lanes."""
    return -(-latent_dim // 128) * 128


def latent_tile(n_rows: int, n_heads: int) -> int:
    """Adjacent rows R a grid step of the latent walk owns: as many as
    stack _LATENT_QUERY_ROWS query rows (4 at 128 heads, 8 at most),
    from the shapes alone; 1, the walk of a row a step, where the heads
    are not whole sublane tiles (no free reshape stacks them) or the
    rows are not whole tiles (tier-1's small batches; the engine's
    widths are)."""
    tile = min(8, _LATENT_QUERY_ROWS // n_heads)
    if n_heads % 8 or tile < 2 or n_rows % tile:
        return 1
    return tile


def latent_walk_fits(n_table_slots: int, pool) -> bool:
    """Whether paged_latent_attention's kernel can take this pool: its
    two whole-table buffer sets must fit the walk's VMEM budget beside
    the widest tile's scratch, _LATENT_QUERY_ROWS query rows (a model's
    heads are fewer): the tile's queries and outputs double buffered
    (the values counted as wide as the keys), a trip's float32 scores
    and their 16-bit probabilities, the float32 accumulator and the
    running max and sum (a lane tile a row); 6.0 MB at 640 bf16 lanes.
    At 128-token blocks of 640 bf16 lanes that is tables of up to 132
    blocks (16,896 tokens); the engine refuses a longer context when it
    is built with the kernel."""
    _, bs, C = pool.shape
    itemsize = pool.dtype.itemsize
    slots = -(-n_table_slots // _LATENT_GROUP) * _LATENT_GROUP
    tile = _LATENT_QUERY_ROWS * (
        4 * C * itemsize + _LATENT_GROUP * bs * (4 + itemsize)
        + C * 4 + 2 * 128 * 4)
    need = 2 * slots * bs * C * itemsize + tile
    return (C % 128 == 0 and itemsize in (2, 4)
            and need <= _WALK_VMEM_BUDGET)


def table_groups(block_table, xp=jnp):
    """[S] int32: the index of each row's TABLE, rising by one wherever
    a row's table differs from the row before it (rows of one prefill
    chunk follow each other and share theirs)."""
    same = xp.all(block_table[1:] == block_table[:-1], axis=1)
    return xp.concatenate([xp.zeros((1,), xp.int32),
                           xp.cumsum(~same, dtype=xp.int32)])


def latent_tiles(groups, tile: int):
    """[S // tile] bool: the tiles of `tile` adjacent rows whose rows all
    name ONE table (groups: table_groups of the call), which the latent
    walk visits together: a prefill chunk's rows but for its edges,
    padding rows. The kernel's entry (jnp) and latent_walk_reads (numpy)
    both ask it, so that the two cannot disagree."""
    groups = groups.reshape(-1, tile)
    return groups[:, 0] == groups[:, -1]


def latent_walk_reads(block_table, ctx_lens, block_size: int, n_heads: int):
    """(blocks fetched, rows whose visit was a tile's) of one latent
    call over these host arrays, by the kernel's own rules: a table's
    blocks are fetched ONCE a run of adjacent rows that name it, by the
    run's longest row; a row is grouped where latent_tiles says its
    tile is one table's (rows of context 0, batch padding, left out).
    The scheduler's kv_block_reads / mla_grouped_rows."""
    groups = table_groups(block_table, np)
    reads = np.zeros(len(ctx_lens), np.int64)
    np.maximum.at(reads, groups, -(-ctx_lens // block_size))
    tile = latent_tile(len(ctx_lens), n_heads)
    if tile == 1:  # a row a step: no visit is a tile's
        return int(reads.sum()), 0
    tiled = np.repeat(latent_tiles(groups, tile), tile)
    return int(reads.sum()), int(np.sum(tiled & (ctx_lens > 0)))


def paged_latent_attention(q, pool, block_table, ctx_lens, v_dim: int):
    """Absorbed-form latent attention of S rows over the paged latent
    pool, rows already written (paged_latent_write ran first): THE
    shared-table decode attention of a latent-attention model, so its
    pallas_call carries that program's trace name, `paged_decode_grid`.

    q: [S, H, C] queries in the latent space ([W_uk^T q_nope; q_rope]),
       the 1/sqrt(d_qk) softmax scale already folded in
    pool: [num_blocks, block_size, C]; a token's row is [latent (v_dim,
       also the value); rotary key]
    block_table: [S, NB] int32; rows sharing a table must be adjacent
    ctx_lens: [S] int32, the row included; 0 = batch padding (zeros out)
    returns [S, H, v_dim]: per head, sum of p * latent (the caller
    applies W_uv)."""
    return _latent_attention(q, pool, block_table, ctx_lens, v_dim,
                             interpret())


@kernel_jit(4, 5)
def _latent_attention(q, pool, block_table, ctx_lens, v_dim: int,
                      interpreted: bool):
    S, H, C = q.shape
    NB = block_table.shape[1]
    bs = pool.shape[1]
    R = latent_tile(S, H)
    # the buffers hold whole groups: a row's last group may reach past
    # the table's last slot
    NBp = -(-NB // _LATENT_GROUP) * _LATENT_GROUP
    scratch = [pltpu.VMEM((2, NBp, bs, C), pool.dtype),
               pltpu.SemaphoreType.DMA((2, NBp))]
    if R > 1:  # a tile's running max, sum and accumulator
        scratch += [pltpu.VMEM((R * H, 1), jnp.float32),
                    pltpu.VMEM((R * H, 1), jnp.float32),
                    pltpu.VMEM((R * H, v_dim), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S // R,),
        in_specs=[pl.BlockSpec((R, H, C), lambda t, *_: (t, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((R, H, v_dim), lambda t, *_: (t, 0, 0)),
        scratch_shapes=scratch,
    )
    call = pl.pallas_call(
        functools.partial(_latent_rows_kernel, n_seqs=S, block_size=bs,
                          v_dim=v_dim, tile=R),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WALK_VMEM_LIMIT),
        interpret=interpreted,
        name="paged_decode_grid",
    )
    groups = table_groups(block_table)
    return call(block_table, ctx_lens, groups,
                latent_tiles(groups, R).astype(jnp.int32), q, pool)


def paged_latent_attention_xla(q, pool, block_table, ctx_lens, v_dim: int):
    """jnp oracle for paged_latent_attention (tests; decode_impl='xla';
    tables too long for the kernel's buffers): gathers each row's pages
    into a dense [S, NB*bs, C] context."""
    S = q.shape[0]
    kc = pool[block_table].reshape(S, -1, pool.shape[-1])
    logits = jnp.einsum("shc,skc->shk", q, kc).astype(jnp.float32)
    live = jnp.arange(kc.shape[1])[None, :] < ctx_lens[:, None]
    logits = jnp.where(live[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("shk,skc->shc", probs, kc[..., :v_dim])
    return jnp.where((ctx_lens > 0)[:, None, None], out, 0).astype(q.dtype)


def _latent_write_kernel(slots_ref, new_ref, pool_in, pool_out,
                         *, block_size: int, n_blocks: int):
    """_kv_write_blocks_kernel for the one latent pool: RMW one token row into
    its block, the block copied from the aliased input on first visit
    only (tokens arrive sorted by slot)."""
    t = pl.program_id(0)
    slot = slots_ref[t]

    def cb(i):
        return _arena_block(slots_ref[i] // block_size, n_blocks)

    first = jnp.logical_or(t == 0, cb(t) != cb(jnp.maximum(t - 1, 0)))

    @pl.when(first)
    def _copy():
        pool_out[...] = pool_in[...]

    @pl.when(slot >= 0)
    def _write():
        row = jax.lax.broadcasted_iota(jnp.int32, (1, block_size, 1), 1)
        pool_out[...] = jnp.where(row == slot % block_size,
                                  new_ref[0][None], pool_out[...])


def paged_latent_write(pool, new, flat_slots):
    """Write [T, C] new latent rows into the [NBLK, bs, C] pool at flat
    slot ids [T] (block*bs + offset; -1 rows are dropped): paged_kv_write
    for a cache that is one pool."""
    return _latent_write(pool, new, flat_slots, interpret())


@kernel_jit(3)
def _latent_write(pool, new, flat_slots, interpreted: bool):
    NBLK, bs, C = pool.shape
    T = flat_slots.shape[0]
    order = jnp.argsort(flat_slots)
    slots = flat_slots[order].astype(jnp.int32)

    def pool_index(t, slots_ref):
        return (_arena_block(slots_ref[t] // bs, NBLK), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T,),
        in_specs=[pl.BlockSpec((1, 1, C), lambda t, slots_ref: (t, 0, 0)),
                  pl.BlockSpec((1, bs, C), pool_index)],
        out_specs=pl.BlockSpec((1, bs, C), pool_index),
    )
    return pl.pallas_call(
        functools.partial(_latent_write_kernel, block_size=bs,
                          n_blocks=NBLK),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        interpret=interpreted,
        name="paged_latent_write",
    )(slots, new[order][:, None, :], pool)


def paged_latent_write_xla(pool, new, flat_slots):
    """jnp scatter oracle for paged_latent_write (-1 slots dropped)."""
    NBLK, bs, C = pool.shape
    idx = jnp.where(flat_slots < 0, NBLK * bs, flat_slots)
    return pool.reshape(NBLK * bs, C).at[idx].set(
        new, mode="drop").reshape(pool.shape)
