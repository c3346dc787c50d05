"""Flash attention numerics vs the jnp oracle (ref model: tests/unit/ops
kernel-vs-torch-reference checks). Off-TPU the Pallas kernels run through
the interpreter (flash_attention._interpret), so the CPU lane tests the
real kernel math — fwd, the Pallas dq and dk/dv backward kernels, GQA
index maps, and the padding path. The same tests compile to Mosaic when
run on TPU hardware."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import _xla_attention, causal_attention
# Mosaic requires the lse tile (1, block_q) to satisfy the (8,128)
# tiling rule, so real-TPU runs use 128-sized blocks; the interpreter
# lane keeps 64 for speed. Same kernels either way.
BLK = 128 if jax.default_backend() == "tpu" else 64

from deepspeed_tpu.ops.pallas.flash_attention import (

    _flash_bwd,
    _flash_fwd,
    flash_attention,
)

pytestmark = pytest.mark.usefixtures("pallas_interpret_module")


def make_qkv(rng, B=2, S=128, H=2, KV=None, D=64, dtype=jnp.float32):
    KV = KV or H
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)), dtype)
    return q, k, v


def oracle(q, k, v, causal=True):
    """[B,S,H,D] oracle attention with GQA repeat."""
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    return _xla_attention(q, k, v, causal=causal)


class TestForwardKernel:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("S", [128, 96])  # 96: padding path
    def test_fwd_matches_oracle(self, rng, causal, S):
        BH, D = 3, 64
        q = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            o, lse = _flash_fwd(q, k, v, None, causal, BLK, BLK, H=1, KV=1)
            ref = oracle(q[:, :, None], k[:, :, None], v[:, :, None], causal)[:, :, 0]
            # reference lse
            scale = 1.0 / (D**0.5)
            s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
            if causal:
                s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -1e30)
            lse_ref = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(o, ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(lse, lse_ref, rtol=2e-3, atol=2e-3)


class TestBackwardKernels:
    """The Pallas dq / dkdv kernels must match autodiff of the oracle."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("S", [128, 96])  # 96: padding path
    def test_grads_match_oracle(self, rng, causal, S):
        with jax.default_matmul_precision("highest"):
            self._run(rng, causal, S)

    def _run(self, rng, causal, S):
        BH, D = 3, 64
        q = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        do = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)

        def f(q, k, v):
            out = oracle(q[:, :, None], k[:, :, None], v[:, :, None], causal)[:, :, 0]
            return jnp.sum(out * do)

        dq_ref, dk_ref, dv_ref = jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        o, lse = _flash_fwd(q, k, v, None, causal, BLK, BLK, H=1, KV=1)
        dq, dk, dv = _flash_bwd(q, k, v, None, o, lse, do, causal, BLK, BLK,
                                H=1, KV=1)
        np.testing.assert_allclose(dq, dq_ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(dk, dk_ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(dv, dv_ref, rtol=2e-3, atol=2e-3)


class TestFlashGQA:
    @pytest.mark.parametrize("KV", [1, 2, 4])
    def test_fwd_and_grad_match_oracle(self, rng, KV):
        B, S, H, D = 2, 128, 4, 32
        q, k, v = make_qkv(rng, B, S, H, KV, D)
        do = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=BLK, block_k=BLK) * do)

        def f_ref(q, k, v):
            return jnp.sum(oracle(q, k, v, causal=True) * do)

        with jax.default_matmul_precision("highest"):
            out = flash_attention(q, k, v, block_q=BLK, block_k=BLK)
            ref = oracle(q, k, v)
            g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
            g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


class TestBF16:
    def test_full_layer_grad_bf16(self, rng):
        B, S, H, D = 2, 256, 2, 64
        q, k, v = make_qkv(rng, B, S, H, None, D, jnp.bfloat16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(oracle(q, k, v).astype(jnp.float32) ** 2)

        g1 = jax.grad(loss_flash)(q, k, v)
        g2 = jax.grad(loss_ref)(q, k, v)
        np.testing.assert_allclose(
            np.asarray(g1, np.float32), np.asarray(g2, np.float32), rtol=5e-2, atol=5e-2
        )


class TestWrapper:
    def test_gqa_repeat_matches_full(self, rng):
        B, S, H, D = 2, 64, 4, 32
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
        out = causal_attention(q, k, v, use_flash=False)
        k_full = jnp.repeat(k, 2, axis=2)
        v_full = jnp.repeat(v, 2, axis=2)
        ref = causal_attention(q, k_full, v_full, use_flash=False)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_xla_attention_is_causal(self, rng):
        B, S, H, D = 1, 16, 1, 8
        q, k, v = make_qkv(rng, B, S, H, None, D)
        with jax.default_matmul_precision("highest"):
            out = _xla_attention(q, k, v, causal=True)
        # first token attends only to itself
        np.testing.assert_allclose(out[0, 0, 0], v[0, 0, 0], rtol=1e-4, atol=1e-4)


class TestSlidingWindowKernel:
    """window > 0: the kernels must match the windowed XLA oracle in fwd
    AND both backward kernels, across block-boundary window sizes, GQA,
    and the padding path."""

    @pytest.mark.parametrize("window", [16, 64, 100])
    @pytest.mark.parametrize("S", [128, 96])
    def test_fwd_and_grads_match_oracle(self, rng, window, S):
        q, k, v = make_qkv(rng, B=2, S=S, H=2, D=64)

        def win_oracle(q, k, v):
            return _xla_attention(q, k, v, causal=True, window=window)

        def flash_fn(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=BLK,
                                   block_k=BLK, window=window)

        with jax.default_matmul_precision("highest"):
            o = flash_fn(q, k, v)
            ref = win_oracle(q, k, v)
            np.testing.assert_allclose(o, ref, rtol=2e-3, atol=2e-3)

            cot = jnp.asarray(rng.normal(size=o.shape), o.dtype)
            g = jax.grad(lambda *a: jnp.vdot(flash_fn(*a), cot), argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(lambda *a: jnp.vdot(win_oracle(*a), cot), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)

    def test_gqa_window(self, rng):
        q, k, v = make_qkv(rng, B=2, S=128, H=4, KV=2, D=64)
        with jax.default_matmul_precision("highest"):
            o = flash_attention(q, k, v, causal=True, block_q=BLK,
                                block_k=BLK, window=32)
            n_rep = 2
            ref = _xla_attention(q, jnp.repeat(k, n_rep, axis=2),
                                 jnp.repeat(v, n_rep, axis=2),
                                 causal=True, window=32)
        np.testing.assert_allclose(o, ref, rtol=2e-3, atol=2e-3)


class TestAlibi:
    """ALiBi-biased flash kernels vs the XLA oracle (Bloom-class models;
    ref: the CUDA softmax alibi path in csrc/transformer/inference)."""

    def _slopes(self, H):
        from deepspeed_tpu.ops.attention import alibi_slopes

        return jnp.asarray(alibi_slopes(H))

    @pytest.mark.parametrize("KV", [2, 4])
    def test_fwd_and_grads_match_oracle(self, rng, KV):
        H = 4
        q, k, v = make_qkv(rng, B=2, S=2 * BLK, H=H, KV=KV, D=64)
        ab = self._slopes(H)

        def orc(q, k, v):
            n_rep = H // KV
            return _xla_attention(jnp.repeat(q, 1, axis=2),
                                  jnp.repeat(k, n_rep, axis=2),
                                  jnp.repeat(v, n_rep, axis=2),
                                  causal=True, alibi=ab)

        with jax.default_matmul_precision("highest"):
            out = flash_attention(q, k, v, causal=True, block_q=BLK,
                                  block_k=BLK, alibi=ab)
            ref = orc(q, k, v)
            np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

            do = jnp.asarray(rng.normal(size=out.shape), out.dtype)
            gk = jax.grad(lambda *a: jnp.sum(flash_attention(
                *a, causal=True, block_q=BLK, block_k=BLK, alibi=ab) * do),
                argnums=(0, 1, 2))(q, k, v)
            go = jax.grad(lambda *a: jnp.sum(orc(*a) * do),
                          argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, go):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)

    def test_alibi_with_window(self, rng):
        """ALiBi composes with the sliding-window mask."""
        H = 4
        q, k, v = make_qkv(rng, B=1, S=2 * BLK, H=H, D=64)
        ab = self._slopes(H)
        with jax.default_matmul_precision("highest"):
            out = flash_attention(q, k, v, causal=True, block_q=BLK,
                                  block_k=BLK, window=40, alibi=ab)
            ref = _xla_attention(q, k, v, causal=True, window=40, alibi=ab)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
