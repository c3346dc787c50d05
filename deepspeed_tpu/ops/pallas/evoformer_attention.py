"""Pallas evoformer (MSA/triangle) fused attention forward.

TPU-native analog of the DS4Science CUTLASS kernels
(ref: csrc/deepspeed4science/evoformer_attn/ — fused non-causal
attention over MSA tensors with up to two broadcastable pair/mask
biases; python surface deepspeed/ops/deepspeed4science/
evoformer_attn.py DS4Sci_EvoformerAttention). The reference contract:

    q/k/v:  [B, S, N, H, D]   (batch, N_seq, N_res, heads, head_dim)
    bias1:  [B, S, 1, 1, N]   per-key mask bias (broadcast over q, H)
    bias2:  [B, 1, H, N, N]   pair bias (broadcast over N_seq)

This kernel computes softmax(q·kᵀ/√d + bias1 + bias2)·v with an online
softmax over key blocks — the [N, N] logits never materialize, and the
bias tiles stream per block (the memory property the CUTLASS kernel
exists for). The grid is one (q-block, key-block) walk per (B·S·H)
slice; bias broadcasting is done by the BlockSpec index maps, not by
materializing broadcast copies.

Backward: handwritten Pallas kernels (round 5 — the reference ships a
CUTLASS backward, csrc/deepspeed4science/evoformer_attn/
attention_back.cu, because science workloads are bwd-dominated):

- dq kernel: key-sequential walk recomputing probabilities from the
  saved logsumexp (flash-style), biases re-added per tile.
- dk/dv kernel: query-sequential walk; when bias1 exists it ALSO
  accumulates the per-key row sums Σ_i ds in scratch — dbias1 is then
  a cheap XLA head-sum of those rows (bias1 broadcasts over q and H).
- db2 kernel (only when bias2 exists): grid ordered with N_seq
  INNERMOST so each (b, h, q-block, k-block) output tile stays VMEM-
  resident while the S contributions accumulate — dbias2 = Σ_s ds
  without materializing ds, and without non-consecutive output-block
  revisits (which Pallas does not guarantee to accumulate).

The chunked-XLA implementation in ops/evoformer_attention.py remains
the oracle; the public entry point wires these kernels through a
custom_vjp.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret
from .flash_attention import NEG_INF, _dot


def _evo_kernel(
    q_ref, k_ref, v_ref, b1_ref, b2_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
    *, scale: float, has_b1: bool, has_b2: bool,
):
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    q = q_ref[0]  # (Bq, D)
    k = k_ref[0]  # (Bk, D)
    st = _dot(q, k, trans_b=True) * scale  # (Bq, Bk) f32
    if has_b1:
        st = st + b1_ref[0, 0].astype(jnp.float32)  # (1, Bk) broadcast
    if has_b2:
        st = st + b2_ref[0].astype(jnp.float32)     # (Bq, Bk)

    m_prev = m_sc[:]
    m_new = jnp.maximum(m_prev, jnp.max(st, axis=1, keepdims=True))
    p = jnp.exp(st - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[:] = l_sc[:] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_sc[:] = acc_sc[:] * corr + _dot(p.astype(v_ref.dtype), v_ref[0])
    m_sc[:] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_sc[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_sc[:] + jnp.log(l_safe)).reshape(
            1, -1).astype(jnp.float32)


def _flat_views(q, k, v, bias1, bias2, block_q, block_k):
    """Shared fwd/bwd plumbing: head-major [G, N, D] flat views, bias
    reshapes with broadcast-aware sentinels, and the index maps."""
    B, S, N, H, D = q.shape
    bq = min(block_q, N)
    bk = min(block_k, N)
    if N % bq or N % bk:
        raise ValueError(f"block sizes ({bq},{bk}) must divide N={N}")
    G = B * S * H
    qf = jnp.moveaxis(q, 3, 2).reshape(G, N, D)
    kf = jnp.moveaxis(k, 3, 2).reshape(G, N, D)
    vf = jnp.moveaxis(v, 3, 2).reshape(G, N, D)
    has_b1 = bias1 is not None
    has_b2 = bias2 is not None
    b1 = (bias1.reshape(B * S, 1, N) if has_b1
          else jnp.zeros((1, 1, bk), q.dtype))
    b2 = (bias2.reshape(B * H, N, N) if has_b2
          else jnp.zeros((1, bq, bk), q.dtype))
    return (B, S, N, H, D, G, bq, bk, qf, kf, vf,
            has_b1, has_b2, b1, b2)


def evoformer_flash_fwd(q, k, v, bias1=None, bias2=None,
                        block_q: int = 256, block_k: int = 256,
                        with_lse: bool = False):
    """q/k/v [B, S, N, H, D]; bias1 [B, S, 1, 1, N] or None; bias2
    [B, 1, H, N, N] or None -> [B, S, N, H, D] (with_lse additionally
    returns the flat [G, N] logsumexp the backward kernels consume)."""
    (B, S, N, H, D, G, bq, bk, qf, kf, vf,
     has_b1, has_b2, b1, b2) = _flat_views(q, k, v, bias1, bias2,
                                           block_q, block_k)
    scale = 1.0 / (D ** 0.5)
    grid = (G, 1, N // bq, N // bk)

    def q_idx(g, _, iq, j):
        return (g, iq, 0)

    def kv_idx(g, _, iq, j):
        return (g, j, 0)

    def b1_idx(g, _, iq, j):
        # g -> (b*S + s): drop the head component
        return (g // H if has_b1 else 0, 0, j if has_b1 else 0)

    def b2_idx(g, _, iq, j):
        # g -> b*H + h: drop the N_seq component (pair bias is shared
        # across sequences)
        if not has_b2:
            return (0, 0, 0)
        return ((g // (S * H)) * H + g % H, iq, j)

    out, lse = pl.pallas_call(
        functools.partial(_evo_kernel, scale=scale, has_b1=has_b1,
                          has_b2=has_b2),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), q_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, 1, bk), b1_idx),
            pl.BlockSpec((1, bq, bk), b2_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), q_idx),
            pl.BlockSpec((1, 1, bq), lambda g, _, iq, j: (g, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, N, D), q.dtype),
            jax.ShapeDtypeStruct((G, 1, N), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret(),
    )(qf, kf, vf, b1, b2)
    o = jnp.moveaxis(out.reshape(B, S, H, N, D), 2, 3)
    if with_lse:
        return o, lse[:, 0, :]
    return o


# ---------------------------------------------------------------------------
# backward kernels (ref: attention_back.cu — here three Pallas walks)
# ---------------------------------------------------------------------------

def _evo_bwd_dq_kernel(
    q_ref, k_ref, v_ref, b1_ref, b2_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_sc,
    *, scale: float, has_b1: bool, has_b2: bool,
):
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    q = q_ref[0]
    k = k_ref[0]
    st = _dot(q, k, trans_b=True) * scale
    if has_b1:
        st = st + b1_ref[0, 0].astype(jnp.float32)
    if has_b2:
        st = st + b2_ref[0].astype(jnp.float32)
    lse = lse_ref[0].reshape(-1, 1)
    p = jnp.exp(st - lse)                           # (bq, bk)
    dp = _dot(do_ref[0], v_ref[0], trans_b=True)    # (bq, bk)
    delta = delta_ref[0].reshape(-1, 1)
    ds = p * (dp - delta)
    dq_sc[:] = dq_sc[:] + _dot(ds.astype(k.dtype), k) * scale

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _evo_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, b1_ref, b2_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dsum_ref, dk_sc, dv_sc, dsum_sc,
    *, scale: float, has_b1: bool, has_b2: bool,
):
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)
        dsum_sc[:] = jnp.zeros_like(dsum_sc)

    q = q_ref[0]
    k = k_ref[0]
    # transposed orientation (bk, bq): no in-kernel transposes
    s_t = _dot(k, q, trans_b=True) * scale
    if has_b1:
        s_t = s_t + b1_ref[0, 0].reshape(-1, 1).astype(jnp.float32)
    if has_b2:
        # b2 tile arrives (bq, bk); kernel works transposed
        s_t = s_t + b2_ref[0].T.astype(jnp.float32)
    lse = lse_ref[0]                                 # (1, bq)
    p_t = jnp.exp(s_t - lse)                         # (bk, bq)
    do = do_ref[0]
    dv_sc[:] = dv_sc[:] + _dot(p_t.astype(do.dtype), do)
    dp_t = _dot(v_ref[0], do, trans_b=True)
    delta = delta_ref[0]                             # (1, bq)
    ds_t = p_t * (dp_t - delta)
    dk_sc[:] = dk_sc[:] + _dot(ds_t.astype(q.dtype), q) * scale
    if has_b1:
        # Σ over queries of ds, per key row: dbias1's per-(g, key)
        # ingredient (the XLA epilogue sums heads)
        dsum_sc[:] = dsum_sc[:] + jnp.sum(ds_t, axis=1, keepdims=True)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)
        dsum_ref[0] = dsum_sc[:].reshape(1, -1)


def _evo_bwd_db2_kernel(
    q_ref, k_ref, v_ref, b1_ref, b2_ref, do_ref, lse_ref, delta_ref,
    db2_ref, db2_sc,
    *, scale: float, has_b1: bool, S: int,
):
    s = pl.program_id(3)  # N_seq INNERMOST: db2 tile stays resident

    @pl.when(s == 0)
    def _init():
        db2_sc[:] = jnp.zeros_like(db2_sc)

    q = q_ref[0]
    k = k_ref[0]
    st = _dot(q, k, trans_b=True) * scale
    if has_b1:
        st = st + b1_ref[0, 0].astype(jnp.float32)
    st = st + b2_ref[0].astype(jnp.float32)
    lse = lse_ref[0].reshape(-1, 1)
    p = jnp.exp(st - lse)
    dp = _dot(do_ref[0], v_ref[0], trans_b=True)
    delta = delta_ref[0].reshape(-1, 1)
    db2_sc[:] = db2_sc[:] + p * (dp - delta)

    @pl.when(s == S - 1)
    def _finalize():
        db2_ref[0] = db2_sc[:].astype(db2_ref.dtype)


def evoformer_flash_bwd(q, k, v, bias1, bias2, o, lse, do,
                        block_q: int = 256, block_k: int = 256):
    """Pallas backward: (dq, dk, dv, db1 | None, db2 | None).

    lse: flat [G, N] from evoformer_flash_fwd(with_lse=True)."""
    (B, S, N, H, D, G, bq, bk, qf, kf, vf,
     has_b1, has_b2, b1, b2) = _flat_views(q, k, v, bias1, bias2,
                                           block_q, block_k)
    scale = 1.0 / (D ** 0.5)
    of = jnp.moveaxis(o, 3, 2).reshape(G, N, D)
    dof = jnp.moveaxis(do, 3, 2).reshape(G, N, D)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)                         # [G, N]
    lse3 = lse.reshape(G, 1, N)
    delta3 = delta.reshape(G, 1, N)
    nq, nk = N // bq, N // bk

    def q_idx(g, _, iq, j):
        return (g, iq, 0)

    def kv_idx(g, _, iq, j):
        return (g, j, 0)

    def b1_idx(g, _, iq, j):
        return (g // H if has_b1 else 0, 0, j if has_b1 else 0)

    def b2_idx(g, _, iq, j):
        if not has_b2:
            return (0, 0, 0)
        return ((g // (S * H)) * H + g % H, iq, j)

    row_q = lambda g, _, iq, j: (g, 0, iq)

    dq = pl.pallas_call(
        functools.partial(_evo_bwd_dq_kernel, scale=scale, has_b1=has_b1,
                          has_b2=has_b2),
        grid=(G, 1, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, bk, D), kv_idx),
            pl.BlockSpec((1, 1, bk), b1_idx),
            pl.BlockSpec((1, bq, bk), b2_idx),
            pl.BlockSpec((1, bq, D), q_idx),
            pl.BlockSpec((1, 1, bq), row_q),
            pl.BlockSpec((1, 1, bq), row_q),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_idx),
        out_shape=jax.ShapeDtypeStruct((G, N, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret(),
    )(qf, kf, vf, b1, b2, dof, lse3, delta3)

    # dk/dv: query-sequential; swap the roles of the inner grid dims
    def kv_idx2(g, _, j, iq):
        return (g, j, 0)

    def q_idx2(g, _, j, iq):
        return (g, iq, 0)

    def b1_idx2(g, _, j, iq):
        return (g // H if has_b1 else 0, 0, j if has_b1 else 0)

    def b2_idx2(g, _, j, iq):
        if not has_b2:
            return (0, 0, 0)
        return ((g // (S * H)) * H + g % H, iq, j)

    row_q2 = lambda g, _, j, iq: (g, 0, iq)

    dk, dv, dsum = pl.pallas_call(
        functools.partial(_evo_bwd_dkv_kernel, scale=scale, has_b1=has_b1,
                          has_b2=has_b2),
        grid=(G, 1, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_idx2),
            pl.BlockSpec((1, bk, D), kv_idx2),
            pl.BlockSpec((1, bk, D), kv_idx2),
            pl.BlockSpec((1, 1, bk), b1_idx2),
            pl.BlockSpec((1, bq, bk), b2_idx2),
            pl.BlockSpec((1, bq, D), q_idx2),
            pl.BlockSpec((1, 1, bq), row_q2),
            pl.BlockSpec((1, 1, bq), row_q2),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), kv_idx2),
            pl.BlockSpec((1, bk, D), kv_idx2),
            pl.BlockSpec((1, 1, bk), lambda g, _, j, iq: (g, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, N, D), k.dtype),
            jax.ShapeDtypeStruct((G, N, D), v.dtype),
            jax.ShapeDtypeStruct((G, 1, N), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, 1), jnp.float32),
        ],
        interpret=interpret(),
    )(qf, kf, vf, b1, b2, dof, lse3, delta3)

    db1 = None
    if has_b1:
        # dsum [G, 1, N] = Σ_i ds per (b, s, h); bias1 broadcasts over
        # q AND heads, so dbias1 = Σ_h dsum, shaped back to the contract
        db1 = (jnp.sum(dsum.reshape(B, S, H, N), axis=2)
               .reshape(B, S, 1, 1, N).astype(bias1.dtype))

    db2 = None
    if has_b2:
        BH = B * H

        def g_of(bh, s):
            # data row for (b, h) at sequence s
            return ((bh // H) * S + s) * H + bh % H

        db2_f = pl.pallas_call(
            functools.partial(_evo_bwd_db2_kernel, scale=scale,
                              has_b1=has_b1, S=S),
            grid=(BH, nq, nk, S),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda bh, iq, j, s: (g_of(bh, s), iq, 0)),
                pl.BlockSpec((1, bk, D), lambda bh, iq, j, s: (g_of(bh, s), j, 0)),
                pl.BlockSpec((1, bk, D), lambda bh, iq, j, s: (g_of(bh, s), j, 0)),
                pl.BlockSpec((1, 1, bk), lambda bh, iq, j, s: (
                    (bh // H) * S + s if has_b1 else 0, 0,
                    j if has_b1 else 0)),
                pl.BlockSpec((1, bq, bk), lambda bh, iq, j, s: (bh, iq, j)),
                pl.BlockSpec((1, bq, D), lambda bh, iq, j, s: (g_of(bh, s), iq, 0)),
                pl.BlockSpec((1, 1, bq), lambda bh, iq, j, s: (g_of(bh, s), 0, iq)),
                pl.BlockSpec((1, 1, bq), lambda bh, iq, j, s: (g_of(bh, s), 0, iq)),
            ],
            out_specs=pl.BlockSpec((1, bq, bk),
                                   lambda bh, iq, j, s: (bh, iq, j)),
            out_shape=jax.ShapeDtypeStruct((BH, N, N), bias2.dtype),
            scratch_shapes=[pltpu.VMEM((bq, bk), jnp.float32)],
            interpret=interpret(),
        )(qf, kf, vf, b1, b2, dof, lse3, delta3)
        db2 = db2_f.reshape(B, 1, H, N, N)

    unflat = lambda x: jnp.moveaxis(x.reshape(B, S, H, N, D), 2, 3)
    return unflat(dq), unflat(dk), unflat(dv), db1, db2
