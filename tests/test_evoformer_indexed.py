"""Evoformer attention + Megatron indexed-dataset tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.evoformer_attention import evoformer_attention
from deepspeed_tpu.runtime.indexed_dataset import (

    MMapIndexedDataset,
    MMapIndexedDatasetBuilder,
)

# interpreter-/compile-heavy: excluded from the fast lane (-m 'not slow')
pytestmark = [pytest.mark.slow,
              pytest.mark.usefixtures("pallas_interpret_module")]


def dense_oracle(q, k, v, biases):
    D = q.shape[-1]
    qT = jnp.moveaxis(q, -2, -3)
    kT = jnp.moveaxis(k, -2, -3)
    vT = jnp.moveaxis(v, -2, -3)
    logits = jnp.einsum("...qd,...kd->...qk", qT, kT) / np.sqrt(D)
    for b in biases:
        if b is not None:
            logits = logits + b
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.moveaxis(jnp.einsum("...qk,...kd->...qd", p, vT.astype(jnp.float32)), -3, -2)


class TestEvoformerAttention:
    def test_chunked_matches_dense_with_biases(self):
        """MSA-shaped input [B, N_seq, N_res, H, D] + mask + pair bias
        (the DS4Sci_EvoformerAttention contract)."""
        B, S, N, H, D = 2, 3, 64, 4, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, S, N, H, D))
        k = jax.random.normal(ks[1], (B, S, N, H, D))
        v = jax.random.normal(ks[2], (B, S, N, H, D))
        mask_bias = jnp.where(
            jax.random.bernoulli(ks[3], 0.9, (B, S, 1, 1, N)), 0.0, -1e9)
        pair_bias = jax.random.normal(ks[4], (B, 1, H, N, N)) * 0.5

        want = dense_oracle(q, k, v, [mask_bias, pair_bias])
        got = evoformer_attention(q, k, v, [mask_bias, pair_bias], chunk_size=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_small_n_dense_path(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 8))
        got = evoformer_attention(q, q, q, [], chunk_size=512)
        want = dense_oracle(q, q, q, [])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_flow(self):
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 2, 8))
        g = jax.grad(lambda x: evoformer_attention(x, x, x, [], chunk_size=8).sum())(q)
        assert np.isfinite(np.asarray(g)).all()


class TestIndexedDataset:
    def test_build_read_roundtrip(self, tmp_path):
        prefix = str(tmp_path / "corpus")
        b = MMapIndexedDatasetBuilder(prefix, dtype=np.int32)
        docs = [np.arange(10), np.arange(5) + 100, np.arange(17) * 3]
        for d in docs:
            b.add_item(d)
            b.end_document()
        b.finalize()

        ds = MMapIndexedDataset(prefix)
        assert len(ds) == 3
        for i, d in enumerate(docs):
            np.testing.assert_array_equal(ds[i], d.astype(np.int32))
        np.testing.assert_array_equal(ds.sizes, [10, 5, 17])
        np.testing.assert_array_equal(ds.doc_idx, [0, 1, 2, 3])
        # partial reads (the sampler's window access pattern)
        np.testing.assert_array_equal(ds.get(2, offset=4, length=3),
                                      (np.arange(17) * 3)[4:7].astype(np.int32))

    def test_uint16_tokens(self, tmp_path):
        """GPT-2-vocab datasets use uint16 (the Megatron convention)."""
        prefix = str(tmp_path / "u16")
        b = MMapIndexedDatasetBuilder(prefix, dtype=np.uint16)
        b.add_item(np.array([1, 2, 50000], np.uint16))
        b.end_document()
        b.finalize()
        ds = MMapIndexedDataset(prefix)
        assert ds.dtype == np.uint16
        np.testing.assert_array_equal(ds[0], [1, 2, 50000])

    def test_bad_magic_raises(self, tmp_path):
        p = tmp_path / "bad.idx"
        p.write_bytes(b"NOTMAGIC0" + b"\x00" * 64)
        (tmp_path / "bad.bin").write_bytes(b"")
        with pytest.raises(ValueError, match="magic"):
            MMapIndexedDataset(str(tmp_path / "bad"))


class TestEvoformerPallasKernel:
    """Fused Pallas forward for the DS4Sci contract (ref: csrc/
    deepspeed4science/evoformer_attn CUTLASS kernels) vs the chunked
    oracle; gradients route through the exact chunked vjp."""

    def _inputs(self, rng, B=1, S=2, N=128, H=2, D=32):
        q = jnp.asarray(rng.normal(size=(B, S, N, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, N, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, N, H, D)), jnp.float32)
        mask = jnp.asarray(
            np.where(rng.random((B, S, 1, 1, N)) < 0.2, -1e9, 0.0),
            jnp.float32)
        pair = jnp.asarray(rng.normal(size=(B, 1, H, N, N)), jnp.float32)
        return q, k, v, mask, pair

    def test_forward_matches_chunked(self, rng):
        from deepspeed_tpu.ops.evoformer_attention import (
            ds4sci_evoformer_attention, evoformer_attention)

        q, k, v, mask, pair = self._inputs(rng)
        with jax.default_matmul_precision("highest"):
            got = ds4sci_evoformer_attention(q, k, v, [mask, pair])
            want = evoformer_attention(q, k, v, [mask, pair],
                                       chunk_size=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_forward_no_bias_and_single_bias(self, rng):
        from deepspeed_tpu.ops.evoformer_attention import (
            ds4sci_evoformer_attention, evoformer_attention)

        q, k, v, mask, _ = self._inputs(rng)
        with jax.default_matmul_precision("highest"):
            for biases in ([], [mask]):
                got = ds4sci_evoformer_attention(q, k, v, biases)
                want = evoformer_attention(q, k, v, biases, chunk_size=64)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=2e-4,
                    atol=2e-4)

    def test_gradients_match_chunked(self, rng):
        from deepspeed_tpu.ops.evoformer_attention import (
            ds4sci_evoformer_attention, evoformer_attention)

        q, k, v, mask, pair = self._inputs(rng, N=128)

        def loss_k(q, pair):
            return ds4sci_evoformer_attention(
                q, k, v, [mask, pair]).astype(jnp.float32).sum()

        def loss_c(q, pair):
            return evoformer_attention(
                q, k, v, [mask, pair], chunk_size=64
            ).astype(jnp.float32).sum()

        with jax.default_matmul_precision("highest"):
            gq_k, gp_k = jax.grad(loss_k, argnums=(0, 1))(q, pair)
            gq_c, gp_c = jax.grad(loss_c, argnums=(0, 1))(q, pair)
        np.testing.assert_allclose(np.asarray(gq_k), np.asarray(gq_c),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gp_k), np.asarray(gp_c),
                                   rtol=2e-4, atol=2e-4)

    def test_off_contract_falls_back(self, rng):
        """N not tile-aligned: silently uses the chunked path."""
        from deepspeed_tpu.ops.evoformer_attention import (
            ds4sci_evoformer_attention, evoformer_attention)

        q, k, v, mask, pair = self._inputs(rng, N=48)
        got = ds4sci_evoformer_attention(q, k, v, [mask, pair],
                                         chunk_size=48)
        want = evoformer_attention(q, k, v, [mask, pair], chunk_size=48)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestEvoformerPallasBackward:
    """Round-5 handwritten backward kernels (ref: csrc/deepspeed4science/
    evoformer_attn/attention_back.cu) vs jax.grad of the chunked oracle
    — dq/dk/dv plus BOTH bias grads (dbias1 via the dkv row-sums,
    dbias2 via the N_seq-innermost accumulation kernel)."""

    def _inputs(self, rng, B=1, S=2, N=128, H=2, D=32):
        q = jnp.asarray(rng.normal(size=(B, S, N, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, N, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, N, H, D)), jnp.float32)
        mask = jnp.asarray(
            np.where(rng.random((B, S, 1, 1, N)) < 0.2, -1e9, 0.0),
            jnp.float32)
        pair = jnp.asarray(rng.normal(size=(B, 1, H, N, N)), jnp.float32)
        return q, k, v, mask, pair

    @pytest.mark.parametrize("which", ["both", "pair_only", "mask_only",
                                       "none"])
    def test_grads_match_chunked_oracle(self, rng, which):
        from deepspeed_tpu.ops.evoformer_attention import (
            ds4sci_evoformer_attention, evoformer_attention)

        q, k, v, mask, pair = self._inputs(rng)
        biases = {"both": [mask, pair], "pair_only": [None, pair],
                  "mask_only": [mask], "none": []}[which]
        do = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

        def loss_kernel(*args):
            n = len([b for b in biases if b is not None])
            qq, kk, vv, *bs = args
            bl = list(biases)
            bi = iter(bs)
            bl = [next(bi) if b is not None else None for b in bl]
            return jnp.sum(ds4sci_evoformer_attention(qq, kk, vv, bl) * do)

        def loss_oracle(*args):
            qq, kk, vv, *bs = args
            bl = list(biases)
            bi = iter(bs)
            bl = [next(bi) if b is not None else None for b in bl]
            return jnp.sum(
                evoformer_attention(qq, kk, vv, bl, chunk_size=64) * do)

        args = [q, k, v] + [b for b in biases if b is not None]
        argnums = tuple(range(len(args)))
        with jax.default_matmul_precision("highest"):
            gk = jax.grad(loss_kernel, argnums=argnums)(*args)
            go = jax.grad(loss_oracle, argnums=argnums)(*args)
        for a, b in zip(gk, go):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=3e-3)

    def test_multi_seq_pair_grad_accumulates(self, rng):
        """dbias2 must SUM over N_seq (the resident-tile accumulation
        the db2 kernel's grid ordering exists for): S=4 forces multiple
        s-steps per output tile."""
        from deepspeed_tpu.ops.evoformer_attention import (
            ds4sci_evoformer_attention, evoformer_attention)

        q, k, v, _, pair = self._inputs(rng, S=4)
        do = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
        with jax.default_matmul_precision("highest"):
            gk = jax.grad(lambda p: jnp.sum(
                ds4sci_evoformer_attention(q, k, v, [None, p]) * do))(pair)
            go = jax.grad(lambda p: jnp.sum(
                evoformer_attention(q, k, v, [None, p],
                                    chunk_size=64) * do))(pair)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(go),
                                   rtol=3e-3, atol=3e-3)
