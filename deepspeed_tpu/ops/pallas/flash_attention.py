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
- causal masking prunes fully-masked blocks with @pl.when; the diagonal
  band applies an iota mask.

grid layout: the innermost grid dims are sequential on TPU, so running
accumulators live in VMEM scratch across those steps and outputs are
written on the last step (out index maps that ignore the inner dims keep
the block resident until then).

Numerics are validated against the pure-jnp oracle in
tests/test_flash_attention.py exactly as the reference validates CUDA
kernels against torch (ref: tests/unit/ops).
"""

import functools

import jax
import jax.numpy as jnp
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
        if causal:
            needed = jnp.logical_and(needed, k_start < q_start + block_q)
    else:
        k_start = j * block_k
        needed = True
        if causal:
            needed = k_start < q_start + block_q

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = _dot(q, k, trans_b=True) * scale  # (bq, bk) f32

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if alibi:
            s = s + slope * (cols - rows).astype(jnp.float32)
        mask = cols < seq_len  # k padding
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window > 0:
            mask = jnp.logical_and(mask, cols > rows - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[:]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # (bq, bk) f32
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_sc[:] = l_sc[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        pv = _dot(p.astype(v.dtype), v)
        acc_sc[:] = acc_sc[:] * corr + pv
        m_sc[:] = m_new

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
        if causal:
            needed = jnp.logical_and(needed, k_start < q_start + block_q)
    else:
        k_start = j * block_k
        needed = True
        if causal:
            needed = k_start < q_start + block_q

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = _dot(q, k, trans_b=True) * scale  # (bq, bk) f32

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if alibi:
            s = s + slope * (cols - rows).astype(jnp.float32)
        mask = cols < seq_len
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window > 0:
            mask = jnp.logical_and(mask, cols > rows - window)

        lse = lse_ref[0].reshape(block_q, 1)  # (bq, 1)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # (bq, bk) f32
        do = do_ref[0]
        dp = _dot(do, v_ref[0], trans_b=True)  # (bq, bk) f32
        delta = delta_ref[0].reshape(block_q, 1)
        ds = p * (dp - delta) * scale  # (bq, bk) f32
        dq_sc[:] = dq_sc[:] + _dot(ds.astype(k.dtype), k)

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
        i_abs = _win_i(j, i, block_k, block_q, nq_total)
        q_start = i_abs * block_q
        needed = _win_ibase(j, block_k, block_q) + i < nq_total
        # rows beyond the window never see this k block
        needed = jnp.logical_and(
            needed, q_start <= k_start + block_k - 1 + window - 1
        )
        if causal:
            needed = jnp.logical_and(needed, k_start < q_start + block_q)
    else:
        q_start = i * block_q
        needed = True
        if causal:
            needed = k_start < q_start + block_q

    @pl.when(needed)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        # transposed orientation (bk, bq): no in-kernel transposes needed
        s_t = _dot(k, q, trans_b=True) * scale

        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
        if alibi:
            s_t = s_t + slope * (cols - rows).astype(jnp.float32)
        mask = cols < seq_len
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        if window > 0:
            mask = jnp.logical_and(mask, cols > rows - window)

        lse = lse_ref[0]  # (1, bq) broadcasts over bk rows
        p_t = jnp.where(mask, jnp.exp(s_t - lse), 0.0)  # (bk, bq) f32
        do = do_ref[0]
        dv_sc[:] = dv_sc[:] + _dot(p_t.astype(do.dtype), do)
        dp_t = _dot(v_ref[0], do, trans_b=True)  # (bk, bq) f32
        delta = delta_ref[0]  # (1, bq)
        ds_t = p_t * (dp_t - delta) * scale
        dk_sc[:] = dk_sc[:] + _dot(ds_t.astype(q.dtype), q)

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
