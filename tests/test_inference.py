"""Inference engine tests: allocator/manager invariants, the paged
decode kernel vs its jnp oracle, and end-to-end prefill+decode equality
against the training model's full-context forward (ref strategy:
tests/unit/inference/v2/ragged + kernels tests vs torch references)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference import (
    BlockedAllocator,
    InferenceEngine,
    InferenceConfig,
    StateManager,
    init_inference,
)
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_xla,
)

# interpreter-/compile-heavy: excluded from the fast lane (-m 'not slow')
pytestmark = pytest.mark.slow

# offload parking tier: pinned_host where the backend has distinct
# memory spaces; backends without them (CPU, jax 0.4.x) fall back to
# the default host memory (platform-compat fallback since the static-
# analysis PR) — a wrongly DEVICE-resident weight still fails either
# way (TPU device memory reports 'device')
_HOST_TIERS = ("pinned_host", "unpinned_host")


class TestBlockedAllocator:
    def test_allocate_free_roundtrip(self):
        a = BlockedAllocator(8)
        got = a.allocate(3)
        assert len(got) == 3 and a.free_blocks == 5
        a.free(got)
        assert a.free_blocks == 8

    def test_exhaustion_raises(self):
        a = BlockedAllocator(4)
        a.allocate(4)
        with pytest.raises(RuntimeError):
            a.allocate(1)

    def test_double_free_raises(self):
        a = BlockedAllocator(4)
        blocks = a.allocate(2)
        a.free(blocks[:1])
        with pytest.raises(ValueError):
            a.free(blocks[:1])

    def test_unique_blocks(self):
        a = BlockedAllocator(16)
        got = a.allocate(10) + a.allocate(6)
        assert len(set(got)) == 16


class TestStateManager:
    def test_extend_grows_blocks(self):
        m = StateManager(num_blocks=16, block_size=4)
        m.extend(7, 6)  # 6 tokens → 2 blocks
        assert len(m.get(7).blocks) == 2
        m.commit(7, 6)
        m.extend(7, 1)  # 7th token still fits... no: 6+1=7 → still 2 blocks
        assert len(m.get(7).blocks) == 2
        m.commit(7, 1)
        m.extend(7, 2)  # 9 tokens → 3 blocks
        assert len(m.get(7).blocks) == 3

    def test_flush_returns_blocks(self):
        m = StateManager(num_blocks=8, block_size=4)
        m.extend(1, 16)
        assert m.free_blocks == 4
        m.flush(1)
        assert m.free_blocks == 8
        with pytest.raises(KeyError):
            m.flush(1)

    def test_block_table_padding(self):
        m = StateManager(num_blocks=8, block_size=4)
        m.extend(1, 5)
        tbl = m.block_table([1], max_blocks=4)
        assert tbl.shape == (1, 4)
        assert set(tbl[0, 2:]) == {0}


@pytest.mark.usefixtures("pallas_interpret")
class TestPagedDecodeKernel:
    @pytest.mark.parametrize("window", [0, 20, 48])
    def test_windowed_matches_oracle(self, rng, window):
        S, KV, D, bs, NBLK, NB = 3, 2, 64, 16, 32, 4
        q = jnp.asarray(rng.normal(size=(S, KV * 2, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        tbl = jnp.asarray(rng.permutation(NBLK)[: S * NB].reshape(S, NB).astype(np.int32))
        ctx = jnp.asarray(np.array([5, 33, 64], np.int32))
        with jax.default_matmul_precision("highest"):
            out = paged_decode_attention(q, kc, vc, tbl, ctx, window=window)
            ref = paged_decode_attention_xla(q, kc, vc, tbl, ctx, window=window)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_layout_mask_matches_oracle(self, rng):
        """Block-sparse decode on the kernel: the per-slot layout bitmap
        (scalar prefetch) must reproduce the oracle's per-position mask
        when cache blocks nest inside layout blocks."""
        S, KV, D, bs, NBLK, NB = 3, 2, 64, 16, 32, 4
        q = jnp.asarray(rng.normal(size=(S, KV * 2, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        tbl = jnp.asarray(rng.permutation(NBLK)[: S * NB].reshape(S, NB)
                          .astype(np.int32))
        ctx = jnp.asarray(np.array([5, 33, 64], np.int32))
        # arbitrary per-slot layout (keep the slot holding each row's own
        # token allowed so the softmax is never empty)
        slots = np.asarray(rng.integers(0, 2, (S, NB)), np.int32)
        for s in range(S):
            slots[s, (int(ctx[s]) - 1) // bs] = 1
        slots_j = jnp.asarray(slots)
        # expand to the oracle's per-position mask
        allowed_pos = jnp.repeat(slots_j.astype(bool), bs, axis=1)
        with jax.default_matmul_precision("highest"):
            out = paged_decode_attention(q, kc, vc, tbl, ctx,
                                         allowed_slots=slots_j)
            ref = paged_decode_attention_xla(q, kc, vc, tbl, ctx,
                                             allowed=allowed_pos)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_sparse_engine_decode_kernel_path(self, rng):
        """End-to-end: a sparse-trained model served with the Pallas
        kernels (decode_impl='pallas') matches the XLA-path
        engine — the allowed_slots kernel routing is exact."""
        cfg, params = small_model(
            attention_impl="sparse", sparse_mode="fixed", sparse_block=16,
            sparse_num_local_blocks=2, sparse_num_global_blocks=1)
        xla_eng = engine_for(cfg, params, kv_block_size=8,
                             decode_impl="xla")
        ker_eng = engine_for(cfg, params, kv_block_size=8,
                             decode_impl="pallas")
        prompt = np.asarray(rng.integers(0, 128, 18), np.int32)
        l_x = xla_eng.put([0], [prompt.copy()])
        l_k = ker_eng.put([0], [prompt.copy()])
        np.testing.assert_allclose(l_k, l_x, rtol=2e-4, atol=2e-4)
        for _ in range(3):
            tok = np.argmax(l_x[0])[None].astype(np.int32)
            l_x = xla_eng.put([0], [tok])
            l_k = ker_eng.put([0], [tok])
            np.testing.assert_allclose(l_k, l_x, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("G", [1, 4])
    def test_matches_oracle(self, rng, G):
        S, KV, D, bs, NBLK, NB = 3, 2, 64, 16, 32, 4
        H = KV * G
        q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        tbl = jnp.asarray(rng.permutation(NBLK)[: S * NB].reshape(S, NB).astype(np.int32))
        ctx = jnp.asarray(np.array([5, 33, 64], np.int32))
        with jax.default_matmul_precision("highest"):
            out = paged_decode_attention(q, kc, vc, tbl, ctx)
            ref = paged_decode_attention_xla(q, kc, vc, tbl, ctx)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.usefixtures("pallas_interpret")
class TestFusedWriteAttend:
    """Fused write+attend decode kernel (paged_decode_attention with
    k_new/v_new/slots): one launch replaces paged_kv_write + attention.
    Oracle = XLA scatter-write then gather-attention."""

    def _setup(self, rng, S=3, KV=2, G=2, D=64, bs=16, NBLK=32, NB=4,
               ctx_vals=(5, 33, 64)):
        H = KV * G
        q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        # block NBLK-1 is the reserved pad block: keep it out of tables
        tbl = jnp.asarray(rng.permutation(NBLK - 1)[: S * NB]
                          .reshape(S, NB).astype(np.int32))
        ctx = np.asarray(ctx_vals, np.int32)
        kn = jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
        slots = np.array([
            int(tbl[s, (ctx[s] - 1) // bs]) * bs + (ctx[s] - 1) % bs
            if ctx[s] > 0 else -1
            for s in range(S)
        ], np.int32)
        return q, kc, vc, tbl, jnp.asarray(ctx), kn, vn, jnp.asarray(slots)

    def _oracle(self, q, kc, vc, tbl, ctx, kn, vn, slots, window=0,
                allowed=None):
        from deepspeed_tpu.inference.model import _write_kv_xla

        ck, cv = _write_kv_xla(kc, vc, kn, vn, slots)
        out = paged_decode_attention_xla(q, ck, cv, tbl, ctx, window=window,
                                         allowed=allowed)
        return out, ck, cv

    @pytest.mark.parametrize("window", [0, 20])
    def test_matches_write_then_attend(self, rng, window):
        q, kc, vc, tbl, ctx, kn, vn, slots = self._setup(rng)
        with jax.default_matmul_precision("highest"):
            out, ck, cv = paged_decode_attention(
                q, kc.copy(), vc.copy(), tbl, ctx, window=window,
                k_new=kn, v_new=vn, slots=slots)
            ref, rk, rv = self._oracle(q, kc, vc, tbl, ctx, kn, vn, slots,
                                       window=window)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(ck, rk, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(cv, rv, rtol=1e-6, atol=1e-6)

    def test_v2_kernel_sparse_bitmap(self, rng):
        """Block-sparse on the manual-DMA kernel: pruned slots are never
        DMA'd; output matches the masked oracle."""
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_fused)

        q, kc, vc, tbl, ctx, kn, vn, slots = self._setup(
            rng, S=4, KV=2, G=2, D=128, bs=16, NBLK=32, NB=4,
            ctx_vals=(17, 33, 64, 0))
        tbl = tbl.at[3].set(31)
        slots = slots.at[3].set(-1)
        S, NB, bs = 4, 4, 16
        lay = np.asarray(rng.integers(0, 2, (S, NB)), np.int32)
        for s in range(3):
            lay[s, (int(ctx[s]) - 1) // bs] = 1  # own-token slot allowed
        allowed_pos = jnp.repeat(jnp.asarray(lay).astype(bool), bs, axis=1)
        with jax.default_matmul_precision("highest"):
            out, ck, cv = paged_decode_fused(
                q, kc.copy(), vc.copy(), tbl, ctx, kn, vn, slots,
                allowed_slots=jnp.asarray(lay))
            ref, rk, rv = self._oracle(q, kc, vc, tbl, ctx, kn, vn, slots,
                                       allowed=allowed_pos)
        np.testing.assert_allclose(out[:3], ref[:3], rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(ck, rk, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(cv, rv, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("window", [0, 40])
    def test_v2_kernel_matches_oracle(self, rng, window):
        """The per-sequence-grid manual-DMA kernel (paged_decode_fused,
        the D=128 dense hot path bench.py takes on hardware) vs the
        scatter+gather oracle — including ctx edges (1 = first token,
        17 = token opening a fresh block, 0 = pad row)."""
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_fused, supports_fused_v2)

        assert supports_fused_v2(128)
        q, kc, vc, tbl, ctx, kn, vn, slots = self._setup(
            rng, S=4, KV=2, G=2, D=128, bs=16, NBLK=32, NB=4,
            ctx_vals=(1, 17, 33, 0))
        tbl = tbl.at[3].set(31)  # pad row -> reserved block
        slots = slots.at[3].set(-1)
        with jax.default_matmul_precision("highest"):
            out, ck, cv = paged_decode_fused(
                q, kc.copy(), vc.copy(), tbl, ctx, kn, vn, slots,
                window=window)
            ref, rk, rv = self._oracle(q, kc, vc, tbl, ctx, kn, vn, slots,
                                       window=window)
        np.testing.assert_allclose(out[:3], ref[:3], rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(ck, rk, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(cv, rv, rtol=1e-6, atol=1e-6)

    def test_pad_row_writes_only_reserved_block(self, rng):
        """A pad row (ctx 0, slot -1, table -> reserved block) must leave
        every live block untouched."""
        S, bs, NBLK, NB = 3, 16, 32, 4
        q, kc, vc, tbl, ctx, kn, vn, slots = self._setup(
            rng, S=S, bs=bs, NBLK=NBLK, NB=NB, ctx_vals=(5, 33, 0))
        tbl = tbl.at[2].set(NBLK - 1)  # pad row -> reserved block
        slots = slots.at[2].set(-1)
        with jax.default_matmul_precision("highest"):
            out, ck, cv = paged_decode_attention(
                q, kc.copy(), vc.copy(), tbl, ctx,
                k_new=kn, v_new=vn, slots=slots)
            ref, rk, rv = self._oracle(q, kc, vc, tbl, ctx, kn, vn, slots)
        np.testing.assert_allclose(out[:2], ref[:2], rtol=2e-3, atol=2e-3)
        # all blocks except the reserved one match the oracle arenas
        np.testing.assert_allclose(ck[: NBLK - 1], rk[: NBLK - 1],
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(cv[: NBLK - 1], rv[: NBLK - 1],
                                   rtol=1e-6, atol=1e-6)

    def test_sparse_layout_fused(self, rng):
        q, kc, vc, tbl, ctx, kn, vn, slots = self._setup(rng)
        S, NB, bs = tbl.shape[0], tbl.shape[1], kc.shape[1]
        lay = np.asarray(rng.integers(0, 2, (S, NB)), np.int32)
        for s in range(S):
            lay[s, (int(ctx[s]) - 1) // bs] = 1  # own-token slot allowed
        allowed_pos = jnp.repeat(jnp.asarray(lay).astype(bool), bs, axis=1)
        with jax.default_matmul_precision("highest"):
            out, ck, cv = paged_decode_attention(
                q, kc.copy(), vc.copy(), tbl, ctx,
                allowed_slots=jnp.asarray(lay),
                k_new=kn, v_new=vn, slots=slots)
            ref, rk, rv = self._oracle(q, kc, vc, tbl, ctx, kn, vn, slots,
                                       allowed=allowed_pos)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(ck, rk, rtol=1e-6, atol=1e-6)

    def test_engine_fused_path_matches_xla_engine(self, rng):
        """End-to-end: engine with decode_impl='pallas' takes the
        fused write+attend path for
        single-token decode batches and matches the XLA engine."""
        cfg, params = small_model()
        xla_eng = engine_for(cfg, params, kv_block_size=8,
                             decode_impl="xla")
        ker_eng = engine_for(cfg, params, kv_block_size=8,
                             decode_impl="pallas")
        prompts = [np.asarray(rng.integers(0, 128, n), np.int32)
                   for n in (9, 4, 13)]
        uids = [0, 1, 2]
        l_x = xla_eng.put(uids, [p.copy() for p in prompts])
        l_k = ker_eng.put(uids, [p.copy() for p in prompts])
        np.testing.assert_allclose(l_k, l_x, rtol=2e-4, atol=2e-4)
        for _ in range(4):
            toks = [np.argmax(l_x[i])[None].astype(np.int32)
                    for i in range(3)]
            l_x = xla_eng.put(uids, toks)
            l_k = ker_eng.put(uids, toks)
            np.testing.assert_allclose(l_k, l_x, rtol=2e-4, atol=2e-4)
        # the fused program was actually compiled for this batch shape
        assert any(u for (_, u) in ker_eng._decode_fns), (
            "single-token decode batch should take the unique_rows path"
        )


class TestPerChannelInt8:
    """ChannelQuantWeight decode SPEED path: int8 codes feed the dot,
    scales apply on the output (inference/quantization.py)."""

    def test_quantize_roundtrip_error_small(self, rng):
        from deepspeed_tpu.inference.quantization import channel_quantize

        w = jnp.asarray(rng.normal(size=(64, 8, 16)), jnp.float32)
        cq = channel_quantize(w, 1)
        deq = cq.q.astype(jnp.float32) * cq.scale[None]
        err = np.abs(np.asarray(deq - w)).max()
        assert err <= np.abs(np.asarray(w)).max() / 127 + 1e-6
        assert cq.q.dtype == jnp.int8 and cq.scale.shape == (8, 16)

    def test_embed_row_scales(self, rng):
        from deepspeed_tpu.inference.quantization import channel_quantize

        w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        cq = channel_quantize(w, 1, scale_first=True)
        assert cq.scale.shape == (32,)
        deq = cq.q.astype(jnp.float32) * cq.scale[:, None]
        np.testing.assert_allclose(deq, w, atol=float(
            np.abs(np.asarray(w)).max() / 127 + 1e-6))

    def test_per_channel_generate_close_to_full(self, rng):
        cfg, params = small_model()
        full = engine_for(cfg, params)
        q8 = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32,
            quantization={"bits": 8, "per_channel": True})
        from deepspeed_tpu.inference.quantization import ChannelQuantWeight

        assert isinstance(q8.params["layers"][0]["w_qkv"],
                          ChannelQuantWeight)
        assert isinstance(q8.params["embed"], ChannelQuantWeight)
        prompt = np.asarray(rng.integers(0, 128, 12), np.int32)
        lf = full.put([0], [prompt.copy()])
        lq = q8.put([0], [prompt.copy()])
        # int8 weights: logits close enough that greedy agrees on a
        # peaked distribution; compare normalized logits coarsely
        assert np.corrcoef(lf[0], lq[0])[0, 1] > 0.99

    def test_per_channel_memory_halves(self, rng):
        from deepspeed_tpu.inference.quantization import quantized_nbytes

        cfg, params = small_model()
        full = engine_for(cfg, params)  # f32 serving
        q8 = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32,
            quantization={"bits": 8, "per_channel": True})
        full_bytes = sum(x.nbytes for x in jax.tree.leaves(full.params))
        q_bytes = quantized_nbytes(q8.params) + sum(
            x.nbytes for x in jax.tree.leaves(
                q8.params,
                is_leaf=lambda l: hasattr(l, "q"))
            if not hasattr(x, "q"))
        assert q_bytes < 0.45 * full_bytes

    def test_per_channel_int4_rejected(self, rng):
        cfg, params = small_model()
        with pytest.raises(ValueError, match="int8-only"):
            init_inference(
                params, cfg,
                dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                     min_prefill_bucket=8, max_batch_size=8),
                quantization={"bits": 4, "per_channel": True})


def small_model(variant="llama", **kw):
    base = dict(vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=128,
                variant=variant, use_flash=False)
    base.update(kw)
    cfg = T.TransformerConfig(**base)
    params = T.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def engine_for(cfg, params, **ckw):
    base = dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                min_prefill_bucket=8, max_batch_size=8)
    base.update(ckw)
    return init_inference(params, cfg, base, dtype=jnp.float32)


def oracle_next_logits(params, cfg, context):
    """Training-model full-context forward → last-token logits."""
    logits = T.forward(params, jnp.asarray([context], jnp.int32), cfg)
    return np.asarray(logits[0, -1], np.float32)


class TestEngineEndToEnd:
    @pytest.mark.parametrize("variant,kw", [
        ("llama", {}),
        ("llama", {"n_kv_heads": 2}),  # GQA
        ("gpt2", {}),
    ])
    def test_prefill_decode_matches_full_forward(self, rng, variant, kw):
        """The engine's paged prefill+decode must produce the same logits
        as the training model run on the full context each step."""
        cfg, params = small_model(variant, **kw)
        eng = engine_for(cfg, params)
        prompt = list(rng.integers(0, 128, 11))
        context = list(prompt)

        logits = eng.put([0], [np.asarray(prompt)])
        ref = oracle_next_logits(params, cfg, context)
        np.testing.assert_allclose(logits[0], ref, rtol=2e-2, atol=2e-2)

        for _ in range(5):
            tok = int(np.argmax(logits[0]))
            context.append(tok)
            logits = eng.put([0], [np.asarray([tok])])
            ref = oracle_next_logits(params, cfg, context)
            np.testing.assert_allclose(logits[0], ref, rtol=2e-2, atol=2e-2)
            assert int(np.argmax(logits[0])) == int(np.argmax(ref))

    def test_mixed_prefill_decode_batch(self, rng):
        """One put() carrying a fresh prompt + an in-flight decode."""
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        p0 = list(rng.integers(0, 128, 9))
        l0 = eng.put([0], [np.asarray(p0)])
        t0 = int(np.argmax(l0[0]))
        p1 = list(rng.integers(0, 128, 13))
        out = eng.put([1, 0], [np.asarray(p1), np.asarray([t0])])
        np.testing.assert_allclose(
            out[0], oracle_next_logits(params, cfg, p1), rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(
            out[1], oracle_next_logits(params, cfg, p0 + [t0]), rtol=2e-2, atol=2e-2)

    def test_parallel_decode_batch(self, rng):
        """Several sequences decode in ONE compiled step and match
        per-sequence oracles."""
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        prompts = [list(rng.integers(0, 128, n)) for n in (5, 9, 12)]
        logits = eng.put([0, 1, 2], [np.asarray(p) for p in prompts])
        toks = [int(np.argmax(logits[i])) for i in range(3)]
        out = eng.put([0, 1, 2], [np.asarray([t]) for t in toks])
        for i in range(3):
            ref = oracle_next_logits(params, cfg, prompts[i] + [toks[i]])
            np.testing.assert_allclose(out[i], ref, rtol=2e-2, atol=2e-2)

    def test_flush_frees_and_blocks_are_reused(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params, num_kv_blocks=3, max_seq_len=16)
        free0 = eng.state.free_blocks
        eng.put([0], [np.asarray(rng.integers(0, 128, 14))])  # 2 blocks
        assert eng.state.free_blocks == free0 - 2
        with pytest.raises(RuntimeError):  # needs 2 blocks, 1 free
            eng.put([1], [np.asarray(rng.integers(0, 128, 15))])
        eng.flush(0)
        assert eng.state.free_blocks == free0
        # reuse the same physical blocks for a new sequence — numerics
        # must be clean (no stale KV bleed-through)
        prompt = list(rng.integers(0, 128, 10))
        logits = eng.put([2], [np.asarray(prompt)])
        np.testing.assert_allclose(
            logits[0], oracle_next_logits(params, cfg, prompt), rtol=2e-2, atol=2e-2)

    def test_query_and_can_schedule(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params, num_kv_blocks=4, kv_block_size=8, max_seq_len=32)
        assert eng.can_schedule([0], [30])
        assert not eng.can_schedule([0], [40])  # > max_seq_len
        eng.put([0], [np.asarray(rng.integers(0, 128, 10))])
        q = eng.query(0)
        assert q["seen_tokens"] == 10
        assert q["free_blocks"] == 2
        assert q["max_new_tokens"] == 32 - 10
        assert not eng.can_schedule([1, 2], [16, 16])  # needs 4, has 2

    def test_generate_greedy(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        prompts = [list(rng.integers(0, 128, 6)), list(rng.integers(0, 128, 4))]
        outs = eng.generate(prompts, max_new_tokens=5)
        assert all(len(o) == 5 for o in outs)
        # oracle greedy rollout
        for p, o in zip(prompts, outs):
            ctx = list(p)
            for got in o:
                want = int(np.argmax(oracle_next_logits(params, cfg, ctx)))
                assert got == want
                ctx.append(got)
        # all sequences flushed after generate
        assert eng.state.free_blocks == eng.config.num_kv_blocks

    def test_chunked_continuation_prefill(self, rng):
        """An in-flight sequence may carry a multi-token chunk (SplitFuse
        continuation-prefill): logits equal feeding the same tokens one
        at a time, and equal the full-context oracle."""
        cfg, params = small_model()
        prompt = list(rng.integers(0, 128, 6))
        chunk = [int(t) for t in rng.integers(0, 128, 5)]

        a = engine_for(cfg, params)
        a.put([0], [np.asarray(prompt)])
        chunked = a.put([0], [np.asarray(chunk)])[0]

        b = engine_for(cfg, params)
        lb = b.put([0], [np.asarray(prompt)])
        for t in chunk:
            lb = b.put([0], [np.asarray([t])])
        np.testing.assert_allclose(chunked, lb[0], rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(
            chunked, oracle_next_logits(params, cfg, prompt + chunk),
            rtol=2e-2, atol=2e-2)
        # the chunk is committed: one more decode continues correctly
        tok = int(np.argmax(chunked))
        la = a.put([0], [np.asarray([tok])])
        np.testing.assert_allclose(
            la[0], oracle_next_logits(params, cfg, prompt + chunk + [tok]),
            rtol=2e-2, atol=2e-2)

    def test_mixed_chunk_and_decode_batch(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        p0 = list(rng.integers(0, 128, 6))
        p1 = list(rng.integers(0, 128, 9))
        l = eng.put([0, 1], [np.asarray(p0), np.asarray(p1)])
        t1 = int(np.argmax(l[1]))
        chunk = [int(t) for t in rng.integers(0, 128, 4)]
        out = eng.put([0, 1], [np.asarray(chunk), np.asarray([t1])])
        np.testing.assert_allclose(
            out[0], oracle_next_logits(params, cfg, p0 + chunk),
            rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(
            out[1], oracle_next_logits(params, cfg, p1 + [t1]),
            rtol=2e-2, atol=2e-2)


class TestReviewRegressions:
    """Round-2 code-review findings."""

    def test_generate_does_not_hijack_inflight_uids(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        prompt = list(rng.integers(0, 128, 7))
        eng.put([0], [np.asarray(prompt)])  # uid 0 in flight
        outs = eng.generate([list(rng.integers(0, 128, 5))], max_new_tokens=3)
        assert len(outs[0]) == 3
        # the foreign sequence survives untouched
        assert eng.state.get(0) is not None
        assert eng.state.get(0).seen_tokens == 7
        ref = oracle_next_logits(params, cfg, prompt + [])
        tok = int(np.argmax(ref))
        out = eng.put([0], [np.asarray([tok])])
        np.testing.assert_allclose(
            out[0], oracle_next_logits(params, cfg, prompt + [tok]),
            rtol=2e-2, atol=2e-2)

    def test_gpt2_bucket_overflow_guard(self):
        cfg, params = small_model("gpt2", max_seq=100)
        with pytest.raises(ValueError):
            engine_for(cfg, params, max_seq_len=100, min_prefill_bucket=64)

    def test_failed_prefill_does_not_leak_descriptors(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params, num_kv_blocks=2, max_seq_len=16)
        eng.put([0], [np.asarray(rng.integers(0, 128, 14))])  # takes all
        for uid in (10, 11, 12):
            with pytest.raises(RuntimeError):
                eng.put([uid], [np.asarray(rng.integers(0, 128, 9))])
        assert eng.state.tracked_uids == [0]

    def test_allocator_rejects_duplicates_in_free_list_arg(self):
        a = BlockedAllocator(4)
        blocks = a.allocate(2)
        with pytest.raises(ValueError):
            a.free([blocks[0], blocks[0]])


class TestZeroInferenceQuantization:
    """Weight-only PTQ (ref: deepspeed/inference/quantization/ +
    zero-inference blog): int8/int4 resident weights, transient dequant."""

    def test_int8_memory_halves(self, rng):
        from deepspeed_tpu.inference.quantization import (
            QuantizedWeight, quantize_for_inference, quantized_nbytes)

        cfg, params = small_model()
        q = quantize_for_inference(
            jax.tree.map(lambda p: p.astype(jnp.bfloat16), params),
            bits=8, group_size=32)
        full = sum(l.nbytes for l in jax.tree.leaves(params)) / 2  # bf16
        assert quantized_nbytes(q) < 0.65 * full
        # norms stay full precision
        leaves = jax.tree.leaves(q, is_leaf=lambda x: isinstance(x, QuantizedWeight))
        assert any(isinstance(l, QuantizedWeight) for l in leaves)
        assert not isinstance(q["ln_f_scale"], QuantizedWeight)

    def test_int4_pack_roundtrip_shape(self):
        from deepspeed_tpu.inference.quantization import quantize_for_inference

        cfg, params = small_model()
        q4 = quantize_for_inference(params, bits=4, group_size=32)
        w = q4["layers"]["w_in"]
        assert w.q.shape[-1] == params["layers"]["w_in"].shape[-1] // 2
        deq = np.asarray(w.dequantize())
        orig = np.asarray(params["layers"]["w_in"])
        assert np.abs(deq - orig).max() < 0.2

    def test_quantized_generate_close_to_full(self, rng):
        cfg, params = small_model()
        full = engine_for(cfg, params)
        quant = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32, quantization={"bits": 8, "group_size": 32})
        prompt = list(rng.integers(0, 128, 8))
        lf = full.put([1], [np.asarray(prompt)])[0]
        lq = quant.put([1], [np.asarray(prompt)])[0]
        # int8 group-wise: logits track the full-precision model closely
        denom = np.abs(lf).max() + 1e-6
        assert np.abs(lq - lf).max() / denom < 0.1
        outs = quant.generate([prompt], max_new_tokens=4)
        assert len(outs[0]) == 4


class TestZeroInferenceOffload:
    """Full-offload serving (ref: docs/_posts/2022-09-10-zero-inference
    .md:52): layer weights park in pinned_host and stream into device
    memory inside the compiled step — HBM holds O(one layer) of weights
    plus the hot set (embed/head/norms)."""

    def _pair(self, rng, quant=None):
        cfg, params = small_model()
        plain = engine_for(cfg, params)
        off = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32, quantization=quant,
            offload={"device": "cpu"})
        return cfg, plain, off

    def test_layers_parked_host_top_resident(self, rng):
        _, plain, off = self._pair(rng)
        for lp in off.params["layers"]:
            for w in jax.tree.leaves(lp):
                assert w.sharding.memory_kind in _HOST_TIERS
        assert off.params["embed"].sharding.memory_kind != "pinned_host"

    def test_matches_resident_engine(self, rng):
        cfg, plain, off = self._pair(rng)
        prompts = [np.asarray(rng.integers(0, 128, n), np.int32)
                   for n in (9, 4)]
        l1 = plain.put([0, 1], [p.copy() for p in prompts])
        l2 = off.put([0, 1], [p.copy() for p in prompts])
        np.testing.assert_allclose(l2, l1, rtol=2e-5, atol=2e-5)
        for _ in range(3):
            nxt = [np.argmax(l1[i])[None].astype(np.int32) for i in range(2)]
            l1 = plain.put([0, 1], nxt)
            l2 = off.put([0, 1], nxt)
            np.testing.assert_allclose(l2, l1, rtol=2e-5, atol=2e-5)

    def test_generate_and_int8_compose(self, rng):
        cfg, plain, off8 = None, None, None
        cfg, params = small_model()
        plain = engine_for(cfg, params)
        off8 = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32,
            quantization={"bits": 8, "per_channel": True},
            offload={"device": "cpu"})
        from deepspeed_tpu.inference.quantization import ChannelQuantWeight

        lp0 = off8.params["layers"][0]
        assert isinstance(lp0["w_qkv"], ChannelQuantWeight)
        assert lp0["w_qkv"].q.sharding.memory_kind in _HOST_TIERS
        prompts = [list(rng.integers(0, 128, 6))]
        out = off8.generate(prompts, max_new_tokens=5)
        assert len(out[0]) == 5

    def test_exhausted_lazy_layers_raise(self, rng):
        """A single-use lazy layer generator fed to a SECOND engine must
        fail loudly, not serve a truncated model."""
        cfg, params = small_model()
        gen_params = dict(params)
        gen_params["layers"] = iter([])  # exhausted-generator stand-in
        with pytest.raises(ValueError, match="exhausted|layers"):
            init_inference(
                gen_params, cfg,
                dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                     min_prefill_bucket=8, max_batch_size=8),
                dtype=jnp.float32, offload={"device": "cpu"})

    def test_offload_guardrails(self, rng):
        """Round 5 lifted the nvme and cpu-x-TP refusals; the remaining
        guards: nvme needs a path, nvme under TP stays refused (the
        io_callback fetch is single-process), unknown devices raise."""
        cfg, params = small_model()
        with pytest.raises(ValueError, match="path"):
            init_inference(params, cfg, dict(max_seq_len=32),
                           offload={"device": "nvme"})
        with pytest.raises(ValueError, match="cpu.*nvme|nvme.*cpu"):
            init_inference(params, cfg, dict(max_seq_len=32),
                           offload={"device": "disk"})
        cfg2, params2 = small_model(n_heads=8)
        with pytest.raises(NotImplementedError, match="TP mesh"):
            init_inference(params2, cfg2,
                           dict(max_seq_len=64, kv_block_size=8,
                                num_kv_blocks=32, min_prefill_bucket=8,
                                max_batch_size=8, tp_size=2),
                           offload={"device": "nvme", "path": "/tmp/x"})


class TestDecodeMulti:
    def test_fused_matches_stepwise_greedy(self, rng):
        """decode_multi == argmax-fed loop of decode_step (exact)."""
        from functools import partial

        from deepspeed_tpu.inference import model as M

        cfg, params = small_model()
        eng = engine_for(cfg, params)
        prompt = list(rng.integers(0, 128, 10))
        eng.put([0], [np.asarray(prompt)])
        tables = eng.state.block_table([0], eng.config.blocks_per_seq)
        ctx = np.asarray([11], np.int32)
        tok = np.asarray([prompt[-1]], np.int32)

        gen, last_logits, _, _ = M.decode_multi(
            eng.params, eng.cache, tok, tables, ctx, cfg, n_steps=4,
            use_kernel=False)

        cache_b = eng.cache
        t, c = tok, ctx
        want = []
        for _ in range(4):
            logits, cache_b = M.decode_step(
                eng.params, cache_b, t, tables, c, cfg, use_kernel=False)
            t = np.argmax(np.asarray(logits), -1).astype(np.int32)
            c = c + 1
            want.append(int(t[0]))
        assert [int(x) for x in np.asarray(gen)[:, 0]] == want


class TestSparseServing:
    """Serving sparse-trained models: the engine reproduces the training
    block layout exactly (prefill token mask + decode layout rows)."""

    def _model(self, mode="fixed", **kw):
        return small_model(
            "llama", attention_impl="sparse", sparse_block=8,
            sparse_num_local_blocks=2, sparse_num_global_blocks=1,
            sparse_mode=mode, **kw)

    @staticmethod
    def _oracle(params, cfg, context):
        """Training sparse forward needs seq % block == 0: pad TRAILING
        tokens (causal — they can't affect earlier positions)."""
        blk = cfg.sparse_block
        n = len(context)
        padded = list(context) + [0] * ((-n) % blk)
        logits = T.forward(params, jnp.asarray([padded], jnp.int32), cfg)
        return np.asarray(logits[0, n - 1], np.float32)

    @pytest.mark.parametrize("mode,kw", [
        ("fixed", {}),
        ("fixed", {"n_kv_heads": 2}),  # GQA
        ("bigbird", {}),
        ("variable", {"sparse_local_window_blocks": (1, 2),
                      "sparse_global_block_indices": (0,),
                      "sparse_num_random_blocks": 1}),
    ])
    def test_matches_sparse_training_forward(self, rng, mode, kw):
        cfg, params = self._model(mode, **kw)
        eng = engine_for(cfg, params)
        prompt = list(rng.integers(0, 128, 11))
        context = list(prompt)
        logits = eng.put([0], [np.asarray(prompt)])
        np.testing.assert_allclose(
            logits[0], self._oracle(params, cfg, context),
            rtol=2e-2, atol=2e-2)
        # decode PAST the local window (block 8 x 2 local blocks = 16):
        # correctness now depends on the layout masking old tokens out
        for _ in range(10):
            tok = int(np.argmax(logits[0]))
            context.append(tok)
            logits = eng.put([0], [np.asarray([tok])])
            ref = self._oracle(params, cfg, context)
            np.testing.assert_allclose(logits[0], ref, rtol=2e-2, atol=2e-2)
            assert int(np.argmax(logits[0])) == int(np.argmax(ref))
        assert len(context) > 16

    def test_layout_actually_masks(self, rng):
        """A sparse-served model must NOT match the dense oracle once the
        context exceeds the window — guards against the mask being a
        no-op."""
        cfg, params = self._model()
        dense_cfg = T.TransformerConfig(**{
            **{f: getattr(cfg, f) for f in (
                "vocab_size", "n_layers", "n_heads", "d_model", "max_seq",
                "variant", "use_flash")},
        })
        eng = engine_for(cfg, params)
        prompt = list(rng.integers(0, 128, 31))
        sparse_logits = eng.put([0], [np.asarray(prompt)])[0]
        dense_ref = oracle_next_logits(params, dense_cfg, prompt)
        assert not np.allclose(sparse_logits, dense_ref, rtol=2e-2, atol=2e-2)


class TestMoEServing:
    """Mixtral-class serving: MoE models decode/prefill with exact
    capacity-free top-k expert mixing (tests vs the training forward at a
    capacity factor high enough that training drops nothing)."""

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_matches_moe_training_forward(self, rng, top_k):
        cfg, params = small_model(
            "llama", n_experts=4, moe_top_k=top_k,
            moe_capacity_factor=100.0)  # no train-time drops -> exact
        eng = engine_for(cfg, params)
        prompt = list(rng.integers(0, 128, 11))
        context = list(prompt)
        logits = eng.put([0], [np.asarray(prompt)])
        ref = oracle_next_logits(params, cfg, context)
        np.testing.assert_allclose(logits[0], ref, rtol=2e-2, atol=2e-2)
        for _ in range(5):
            tok = int(np.argmax(logits[0]))
            context.append(tok)
            logits = eng.put([0], [np.asarray([tok])])
            ref = oracle_next_logits(params, cfg, context)
            np.testing.assert_allclose(logits[0], ref, rtol=2e-2, atol=2e-2)
            assert int(np.argmax(logits[0])) == int(np.argmax(ref))

    def test_moe_generate(self, rng):
        cfg, params = small_model("llama", n_experts=4, moe_top_k=2)
        eng = engine_for(cfg, params)
        outs = eng.generate(
            [list(rng.integers(0, 128, 9)), list(rng.integers(0, 128, 5))],
            max_new_tokens=6)
        assert all(len(o) == 6 for o in outs)


class TestSlidingWindowServing:
    """Mistral-class sliding-window attention: training and serving agree,
    with the window actually excluding old positions."""

    def test_matches_training_forward_past_window(self, rng):
        cfg, params = small_model("llama", sliding_window=8, n_kv_heads=2)
        eng = engine_for(cfg, params)
        prompt = list(rng.integers(0, 128, 11))
        context = list(prompt)
        logits = eng.put([0], [np.asarray(prompt)])
        np.testing.assert_allclose(
            logits[0], oracle_next_logits(params, cfg, context),
            rtol=2e-2, atol=2e-2)
        for _ in range(8):  # context grows to 19 >> window 8
            tok = int(np.argmax(logits[0]))
            context.append(tok)
            logits = eng.put([0], [np.asarray([tok])])
            ref = oracle_next_logits(params, cfg, context)
            np.testing.assert_allclose(logits[0], ref, rtol=2e-2, atol=2e-2)
            assert int(np.argmax(logits[0])) == int(np.argmax(ref))

    def test_window_excludes_old_tokens(self, rng):
        """Perturbing a token OUTSIDE every live window must not change
        the next-token logits."""
        cfg, params = small_model("llama", sliding_window=4)
        ctx = list(rng.integers(0, 128, 16))
        a = oracle_next_logits(params, cfg, ctx)
        ctx2 = list(ctx)
        ctx2[0] = (ctx2[0] + 1) % 128  # outside the last-4 window... but
        # position 0 feeds early hidden states that stay in-window for
        # layer 2 — use a 1-layer config for a clean locality check
        cfg1 = T.TransformerConfig(
            vocab_size=128, n_layers=1, n_heads=4, d_model=64, max_seq=128,
            variant="llama", use_flash=False, sliding_window=4)
        p1 = T.init(cfg1, jax.random.PRNGKey(0))
        a1 = oracle_next_logits(p1, cfg1, ctx)
        b1 = oracle_next_logits(p1, cfg1, ctx2)
        np.testing.assert_allclose(a1, b1, rtol=1e-5, atol=1e-6)
        assert a is not None  # multi-layer ran fine too

    def test_mixtral_class_window_plus_moe(self, rng):
        cfg, params = small_model("llama", sliding_window=8, n_experts=4,
                                  moe_top_k=2, moe_capacity_factor=100.0)
        eng = engine_for(cfg, params)
        prompt = list(rng.integers(0, 128, 13))
        context = list(prompt)
        logits = eng.put([0], [np.asarray(prompt)])
        np.testing.assert_allclose(
            logits[0], oracle_next_logits(params, cfg, context),
            rtol=2e-2, atol=2e-2)
        for _ in range(4):
            tok = int(np.argmax(logits[0]))
            context.append(tok)
            logits = eng.put([0], [np.asarray([tok])])
            np.testing.assert_allclose(
                logits[0], oracle_next_logits(params, cfg, context),
                rtol=2e-2, atol=2e-2)


class TestTensorParallelServing:
    """Mesh-sharded (TP) serving vs the single-device engine
    (ref: inference/engine.py:254 _create_model_parallel_group +
    v2 sharding helpers model_implementations/sharding/qkv.py — here the
    mesh 'model' axis + the training rules table do the slicing)."""

    def _pair(self, rng, tp, variant="llama", quant=None, **kw):
        cfg, params = small_model(variant, n_heads=8, **kw)
        base = engine_for(cfg, params)
        tpe = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8,
                 tensor_parallel={"tp_size": tp}),
            dtype=jnp.float32, quantization=quant)
        return cfg, base, tpe

    def test_weights_and_cache_actually_sharded(self, rng):
        _, _, tpe = self._pair(rng, tp=4, n_kv_heads=4)
        wq = tpe.params["layers"][0]["wq"]  # prepared: per-layer list
        assert "model" in tuple(wq.sharding.spec), wq.sharding
        # per-device shard is H/tp of the heads dim (layer dim unstacked)
        shard_shape = wq.sharding.shard_shape(wq.shape)
        assert shard_shape[1] == wq.shape[1] // 4
        ck = tpe.cache.k[0]
        assert "model" in tuple(ck.sharding.spec), ck.sharding
        assert ck.sharding.shard_shape(ck.shape)[2] == ck.shape[2] // 4

    @pytest.mark.parametrize("tp,kw", [
        (4, {"n_kv_heads": 4}),   # full KV shard
        (8, {"n_kv_heads": 2}),   # GQA kv < tp: KV replicates, heads shard
        (2, {}),                  # MHA
    ])
    def test_logits_match_single_device(self, rng, tp, kw):
        cfg, base, tpe = self._pair(rng, tp=tp, **kw)
        prompts = [np.asarray(rng.integers(0, 128, 11), np.int32),
                   np.asarray(rng.integers(0, 128, 5), np.int32)]
        l1 = base.put([0, 1], [p.copy() for p in prompts])
        l2 = tpe.put([0, 1], [p.copy() for p in prompts])
        np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)
        for _ in range(4):
            nxt = np.argmax(l1, -1)
            assert (np.argmax(l2, -1) == nxt).all()
            l1 = base.put([0, 1], [nxt[0:1], nxt[1:2]])
            l2 = tpe.put([0, 1], [nxt[0:1], nxt[1:2]])
            np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)

    def test_tp_generate_matches(self, rng):
        cfg, base, tpe = self._pair(rng, tp=4, n_kv_heads=4)
        prompts = [list(rng.integers(0, 128, 7)), list(rng.integers(0, 128, 3))]
        assert base.generate(prompts, max_new_tokens=6) == tpe.generate(
            prompts, max_new_tokens=6)

    def test_tp_gpt2_matches(self, rng):
        cfg, base, tpe = self._pair(rng, tp=4, variant="gpt2")
        prompts = [list(rng.integers(0, 128, 7))]
        assert base.generate(prompts, max_new_tokens=5) == tpe.generate(
            prompts, max_new_tokens=5)

    def test_tp_moe_matches(self, rng):
        cfg, base, tpe = self._pair(rng, tp=4, n_experts=4, moe_top_k=2)
        prompts = [list(rng.integers(0, 128, 9))]
        assert base.generate(prompts, max_new_tokens=5) == tpe.generate(
            prompts, max_new_tokens=5)

    def test_tp_quantized_matches_tp_ptq(self, rng):
        """TP x ZeRO-Inference PTQ: the int codes shard like the weight."""
        cfg, base, tpe = self._pair(rng, tp=4, n_kv_heads=4,
                                    quant={"bits": 8, "group_size": 16})
        qbase = init_inference(
            base.params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32, quantization={"bits": 8, "group_size": 16})
        wq = tpe.params["layers"][0]["wq"]
        assert "model" in tuple(wq.q.sharding.spec)
        prompts = [np.asarray(rng.integers(0, 128, 9), np.int32)]
        l1 = qbase.put([0], [prompts[0].copy()])
        l2 = tpe.put([0], [prompts[0].copy()])
        np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)

    def test_heads_not_divisible_raises(self, rng):
        cfg, params = small_model(n_heads=6, d_model=96)
        with pytest.raises(ValueError, match="divisible"):
            init_inference(params, cfg, dict(tp_size=4))


class TestBatchedPrefill:
    """Cross-prompt prefill batching (VERDICT r2 W4): N concurrent
    prompts run in ONE compiled program, not N."""

    def test_wave_matches_sequential_prefill(self, rng):
        cfg, params = small_model()
        a = engine_for(cfg, params)
        b = engine_for(cfg, params)
        prompts = [np.asarray(rng.integers(0, 128, n), np.int32)
                   for n in (5, 11, 3)]
        # sequential puts (single-prompt path)
        seq = np.stack([a.put([i], [p.copy()])[0]
                        for i, p in enumerate(prompts)])
        # one put (batched path) — prompts GROUP BY TOKEN BUCKET so the
        # 11-token straggler no longer pads the 3/5-token prompts to its
        # bucket (r3 advisor finding): two compiled waves, (2,8) + (1,8
        # -> bucket 16)
        wave = b.put([0, 1, 2], [p.copy() for p in prompts])
        np.testing.assert_allclose(wave, seq, rtol=2e-5, atol=2e-5)
        assert sorted(b._prefill_batch_fns) == [(1, 16), (2, 8)]

    def test_non_strict_admits_per_uid(self, rng):
        """strict=False: prompts that fit run, the rest are REJECTED
        per-uid instead of failing the batch (r3 advisor finding; the
        v2 scheduler defers individual prompts)."""
        cfg, params = small_model()
        eng = engine_for(cfg, params, num_kv_blocks=4, kv_block_size=8,
                         max_seq_len=32)
        # capacity: 4 blocks = 32 tokens; three 16-token prompts -> only
        # the first two fit
        prompts = [np.asarray(rng.integers(0, 128, 16), np.int32)
                   for _ in range(3)]
        out, rejected = eng.put([0, 1, 2], [p.copy() for p in prompts],
                                strict=False)
        assert rejected == [2]
        assert eng.state.get(2) is None or eng.state.get(2).seen_tokens == 0
        for i in (0, 1):
            ref = oracle_next_logits(params, cfg, list(prompts[i]))
            np.testing.assert_allclose(out[i], ref, rtol=2e-2, atol=2e-2)
        assert not out[2].any()  # rejected row is zeros
        # strict default still refuses the whole batch, mutating nothing
        eng2 = engine_for(cfg, params, num_kv_blocks=4, kv_block_size=8,
                          max_seq_len=32)
        with pytest.raises(RuntimeError, match="insufficient KV blocks"):
            eng2.put([0, 1, 2], [p.copy() for p in prompts])
        assert eng2.state.free_blocks == 4

    def test_wave_then_decode_consistent(self, rng):
        """KV written by the batched prefill serves later decodes."""
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        prompts = [list(rng.integers(0, 128, n)) for n in (7, 4)]
        logits = eng.put([0, 1], [np.asarray(p, np.int32) for p in prompts])
        toks = [int(np.argmax(logits[i])) for i in range(2)]
        nxt = eng.put([0, 1], [np.asarray([t]) for t in toks])
        for i in range(2):
            ref = oracle_next_logits(params, cfg, prompts[i] + [toks[i]])
            np.testing.assert_allclose(nxt[i], ref, rtol=2e-2, atol=2e-2)

    def test_wave_capped_at_max_batch_size(self, rng):
        """A wave larger than max_batch_size splits into bounded
        programs instead of compiling one unbounded (bp, tp)."""
        cfg, params = small_model()
        eng = engine_for(cfg, params, max_batch_size=2, num_kv_blocks=32,
                         max_seq_len=16)
        prompts = [np.asarray(rng.integers(0, 128, 5), np.int32)
                   for _ in range(5)]
        wave = eng.put(list(range(5)), [p.copy() for p in prompts])
        seq = np.stack([engine_for(cfg, params).put([9], [p.copy()])[0]
                        for p in prompts])
        np.testing.assert_allclose(wave, seq, rtol=2e-5, atol=2e-5)
        # waves of 2,2,1: (2,8) batch program + the single-prompt path
        assert (2, 8) in eng._prefill_batch_fns
        assert all(bp <= 2 for bp, _ in eng._prefill_batch_fns)

    def test_insufficient_blocks_rejected_before_any_state_change(self, rng):
        """The wave is validated atomically: a put() that cannot be
        scheduled leaves no tracked uids / reserved blocks behind."""
        cfg, params = small_model()
        eng = engine_for(cfg, params, num_kv_blocks=3, kv_block_size=8,
                         max_seq_len=24)
        free0 = eng.state.free_blocks
        with pytest.raises(RuntimeError, match="insufficient KV blocks"):
            eng.put([0, 1, 2], [np.asarray(rng.integers(0, 128, 9), np.int32)
                                for _ in range(3)])
        assert eng.state.free_blocks == free0
        assert not eng.state.tracked_uids

    def test_tp_batched_prefill(self, rng):
        """Batched prefill under the serving mesh."""
        cfg, params = small_model(n_heads=8, n_kv_heads=4)
        base = engine_for(cfg, params)
        tpe = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8, tp_size=4),
            dtype=jnp.float32)
        prompts = [np.asarray(rng.integers(0, 128, n), np.int32)
                   for n in (6, 9)]
        l1 = base.put([0, 1], [p.copy() for p in prompts])
        l2 = tpe.put([0, 1], [p.copy() for p in prompts])
        np.testing.assert_allclose(l1, l2, rtol=2e-5, atol=2e-5)


class TestSampling:
    """Sampling knobs over put() logits (ref: inference/engine.py:613
    generate → HF LogitsProcessor semantics)."""

    def test_temperature_zero_is_greedy(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        prompts = [list(rng.integers(0, 128, 7))]
        greedy = eng.generate([list(prompts[0])], max_new_tokens=6)
        sampled = eng.generate([list(prompts[0])], max_new_tokens=6,
                               do_sample=True, temperature=0.0, seed=0)
        assert greedy == sampled

    def test_top_k_support(self):
        """Distribution support ⊆ top-k of the (penalized) logits."""
        logits = np.linspace(-1, 1, 64).astype(np.float32)
        gen = np.random.default_rng(0)
        draws = {
            InferenceEngine.sample_token(logits, temperature=1.0, top_k=5,
                                         rng=gen)
            for _ in range(300)
        }
        assert draws <= set(range(59, 64)), draws

    def test_top_p_keeps_nucleus_only(self):
        logits = np.full(32, -10.0, np.float32)
        logits[3] = 5.0   # p ~ .88 of the pair below
        logits[17] = 3.0
        gen = np.random.default_rng(1)
        draws = {
            InferenceEngine.sample_token(logits, temperature=1.0, top_p=0.5,
                                         rng=gen)
            for _ in range(200)
        }
        assert draws == {3}  # nucleus of mass .5 is just the top token

    def test_top_p_one_keeps_all(self):
        logits = np.zeros(8, np.float32)
        gen = np.random.default_rng(2)
        draws = {
            InferenceEngine.sample_token(logits, temperature=1.0, top_p=1.0,
                                         rng=gen)
            for _ in range(400)
        }
        assert draws == set(range(8))  # uniform logits, everything reachable

    def test_repetition_penalty_discourages_seen(self):
        logits = np.ones(16, np.float32)
        logits[4] = 2.0  # would win greedily
        # huge penalty on the seen winner drops it below the field of 1.0s
        tok = InferenceEngine.sample_token(
            logits, temperature=0.0, repetition_penalty=100.0,
            seen_tokens=[4])
        assert tok != 4
        # negative logits are multiplied (CTRL rule)
        neg = np.full(4, -1.0, np.float32)
        neg[2] = -0.5
        tok = InferenceEngine.sample_token(
            neg, temperature=0.0, repetition_penalty=4.0, seen_tokens=[2])
        assert tok != 2

    def test_seeded_draws_reproduce(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        p = list(rng.integers(0, 128, 5))
        a = eng.generate([list(p)], max_new_tokens=8, do_sample=True,
                         temperature=1.5, top_k=20, seed=7)
        b = eng.generate([list(p)], max_new_tokens=8, do_sample=True,
                         temperature=1.5, top_k=20, seed=7)
        c = eng.generate([list(p)], max_new_tokens=8, do_sample=True,
                         temperature=1.5, top_k=20, seed=8)
        assert a == b
        assert a != c  # overwhelmingly likely at temp 1.5

    def test_batch_sampling_runs(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        outs = eng.generate(
            [list(rng.integers(0, 128, 5)), list(rng.integers(0, 128, 3))],
            max_new_tokens=5, do_sample=True, temperature=0.8, top_p=0.9,
            repetition_penalty=1.2, seed=3)
        assert len(outs) == 2 and all(len(o) == 5 for o in outs)


class TestV1ConfigCompat:
    """Reference DeepSpeedInferenceConfig keys map onto the TPU engine
    (ref: inference/config.py) instead of failing as pydantic extras."""

    def test_dtype_and_noop_keys(self, rng):
        cfg, params = small_model()
        eng = init_inference(params, cfg, {
            "dtype": "fp16", "replace_with_kernel_inject": True,
            "enable_cuda_graph": True, "max_out_tokens": 48,
            "max_batch_size": 8, "kv_block_size": 8, "num_kv_blocks": 32,
            "min_prefill_bucket": 8})
        assert eng._dtype == jnp.bfloat16  # fp16 → bf16 on TPU
        assert eng.config.max_seq_len == 48
        out = eng.generate([list(rng.integers(0, 128, 5))], max_new_tokens=3)
        assert len(out[0]) == 3

    def test_int8_dtype_enables_ptq(self, rng):
        cfg, params = small_model()
        eng = init_inference(params, cfg, {
            "dtype": "int8", "max_batch_size": 8, "kv_block_size": 8,
            "num_kv_blocks": 32, "min_prefill_bucket": 8, "max_seq_len": 48})
        from deepspeed_tpu.inference.quantization import QuantizedWeight

        assert isinstance(eng.params["layers"][0]["w_qkv"], QuantizedWeight)

    def test_checkpoint_key_points_to_hf_import(self):
        cfg, params = small_model()
        with pytest.raises(NotImplementedError, match="init_inference_from_hf"):
            init_inference(params, cfg, {"checkpoint": "/some/path.json"})

    def test_injection_policy_points_to_rules(self):
        cfg, params = small_model()
        with pytest.raises(NotImplementedError, match="rules table"):
            init_inference(params, cfg, {"injection_policy": {"x": "y"}})


def test_empty_token_array_raises(rng):
    cfg, params = small_model()
    eng = engine_for(cfg, params)
    eng.put([0], [np.asarray(rng.integers(0, 128, 4))])
    with pytest.raises(ValueError, match="empty"):
        eng.put([0], [np.asarray([], np.int32)])


@pytest.mark.usefixtures("pallas_interpret")
class TestAlibiServing:
    """ALiBi (Bloom/falcon-rw class) through every decode path: the
    (S, NB)-grid kernel, the fused write+attend mode, the per-sequence
    manual-DMA kernel, and the engine end-to-end vs the training-forward
    oracle. ref: module_inject/containers/bloom.py (the reference's
    alibi serving path is a CUDA softmax variant; here the slope table
    rides into the Pallas kernels)."""

    def _slopes(self, cfg):
        return jnp.asarray(T.model_alibi_slopes(cfg))

    def _setup(self, rng, S=3, KV=2, G=2, D=64, bs=16, NBLK=32, NB=4,
               ctx_vals=(5, 33, 64)):
        H = KV * G
        q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
        tbl = jnp.asarray(rng.permutation(NBLK - 1)[: S * NB]
                          .reshape(S, NB).astype(np.int32))
        ctx = np.asarray(ctx_vals, np.int32)
        kn = jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
        slots = np.array([
            int(tbl[s, (ctx[s] - 1) // bs]) * bs + (ctx[s] - 1) % bs
            if ctx[s] > 0 else -1
            for s in range(S)
        ], np.int32)
        return q, kc, vc, tbl, jnp.asarray(ctx), kn, vn, jnp.asarray(slots)

    def test_grid_kernel_matches_oracle(self, rng):
        from deepspeed_tpu.ops.attention import alibi_slopes

        q, kc, vc, tbl, ctx, _, _, _ = self._setup(rng)
        ab = jnp.asarray(alibi_slopes(q.shape[1]))
        with jax.default_matmul_precision("highest"):
            out = paged_decode_attention(q, kc, vc, tbl, ctx,
                                         alibi_slopes=ab)
            ref = paged_decode_attention_xla(q, kc, vc, tbl, ctx,
                                             alibi_slopes=ab)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_fused_matches_oracle(self, rng):
        from deepspeed_tpu.inference.model import _write_kv_xla
        from deepspeed_tpu.ops.attention import alibi_slopes

        q, kc, vc, tbl, ctx, kn, vn, slots = self._setup(rng)
        ab = jnp.asarray(alibi_slopes(q.shape[1]))
        with jax.default_matmul_precision("highest"):
            out, ck, cv = paged_decode_attention(
                q, kc.copy(), vc.copy(), tbl, ctx,
                k_new=kn, v_new=vn, slots=slots, alibi_slopes=ab)
            rk, rv = _write_kv_xla(kc, vc, kn, vn, slots)
            ref = paged_decode_attention_xla(q, rk, rv, tbl, ctx,
                                             alibi_slopes=ab)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(ck, rk, rtol=1e-6, atol=1e-6)

    def test_v2_kernel_matches_oracle(self, rng):
        from deepspeed_tpu.inference.model import _write_kv_xla
        from deepspeed_tpu.ops.attention import alibi_slopes
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_fused, supports_fused_v2)

        assert supports_fused_v2(128)
        q, kc, vc, tbl, ctx, kn, vn, slots = self._setup(
            rng, S=4, D=128, ctx_vals=(1, 17, 33, 0))
        tbl = tbl.at[3].set(31)
        slots = slots.at[3].set(-1)
        ab = jnp.asarray(alibi_slopes(q.shape[1]))
        with jax.default_matmul_precision("highest"):
            out, ck, cv = paged_decode_fused(
                q, kc.copy(), vc.copy(), tbl, ctx, kn, vn, slots,
                alibi_slopes=ab)
            rk, rv = _write_kv_xla(kc, vc, kn, vn, slots)
            ref = paged_decode_attention_xla(q, rk, rv, tbl, ctx,
                                             alibi_slopes=ab)
        np.testing.assert_allclose(out[:3], ref[:3], rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(ck, rk, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_engine_decode_matches_training_forward(self, rng, use_kernel):
        """Engine prefill + 4 greedy decode steps on an alibi model ==
        the training forward on the growing context (both paths share
        model_alibi_slopes, neither shares attention code)."""
        cfg, params = small_model(variant="gpt2", alibi=True,
                                  embedding_layernorm=True)
        eng = engine_for(cfg, params, kv_block_size=8,
                         decode_impl="pallas" if use_kernel else "xla")
        prompt = list(np.asarray(rng.integers(0, 128, 11), np.int32))
        logits = eng.put([0], [np.asarray(prompt, np.int32)])
        ref = oracle_next_logits(params, cfg, prompt)
        np.testing.assert_allclose(logits[0], ref, rtol=3e-4, atol=3e-4)
        ctx = list(prompt)
        for _ in range(4):
            tok = int(np.argmax(logits[0]))
            ctx.append(tok)
            logits = eng.put([0], [np.asarray([tok], np.int32)])
            ref = oracle_next_logits(params, cfg, ctx)
            np.testing.assert_allclose(logits[0], ref, rtol=5e-4, atol=5e-4)


class TestNvmeOffloadServing:
    """NVMe-tier full-offload serving (ref: partitioned_param_swapper
    .py:36 + the OPT-30B-from-NVMe case, zero-inference post:52): layer
    weights live in per-leaf NVMe files; each step's layer fetch is an
    in-program io_callback over the aio read-ahead window."""

    def _nvme_engine(self, params, cfg, tmp_path, quant=None):
        return init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32, quantization=quant,
            offload={"device": "nvme", "path": str(tmp_path),
                     "read_ahead": 2})

    def test_layers_on_disk_not_in_memory(self, rng, tmp_path):
        cfg, params = small_model()
        off = self._nvme_engine(params, cfg, tmp_path)
        # the served tree carries only layer indices; bytes are on disk
        for lp in off.params["layers"]:
            assert lp == {}
        files = list((tmp_path / "ds_tpu_swap").rglob("l*_leaf*.bin"))
        assert len(files) >= cfg.n_layers * 5, files

    def test_matches_resident_engine(self, rng, tmp_path):
        cfg, params = small_model()
        plain = engine_for(cfg, params)
        off = self._nvme_engine(params, cfg, tmp_path)
        prompts = [np.asarray(rng.integers(0, 128, n), np.int32)
                   for n in (9, 4)]
        l1 = plain.put([0, 1], [p.copy() for p in prompts])
        l2 = off.put([0, 1], [p.copy() for p in prompts])
        np.testing.assert_allclose(l2, l1, rtol=2e-5, atol=2e-5)
        for _ in range(3):
            nxt = [np.argmax(l1[i])[None].astype(np.int32)
                   for i in range(2)]
            l1 = plain.put([0, 1], nxt)
            l2 = off.put([0, 1], nxt)
            np.testing.assert_allclose(l2, l1, rtol=2e-5, atol=2e-5)

    def test_int8_composes(self, rng, tmp_path):
        from deepspeed_tpu.inference.quantization import ChannelQuantWeight

        cfg, params = small_model()
        off8 = self._nvme_engine(params, cfg, tmp_path,
                                 quant={"bits": 8, "per_channel": True})
        specs = off8._nvme_store.layer_specs(0)
        assert isinstance(specs["w_qkv"], ChannelQuantWeight)
        out = off8.generate([list(rng.integers(0, 128, 6))],
                            max_new_tokens=5)
        assert len(out[0]) == 5

    def test_nvme_requires_path(self, rng):
        cfg, params = small_model()
        with pytest.raises(ValueError, match="path"):
            init_inference(params, cfg,
                           dict(max_seq_len=64, kv_block_size=8,
                                num_kv_blocks=32, max_batch_size=8),
                           offload={"device": "nvme"})


class TestTPOffloadServing:
    """cpu-tier offload under a TP mesh: each device's weight SHARD
    parks in pinned_host and streams to its own HBM inside the step
    (the per-device stream shrinks by 1/tp — offload TP scales the
    weight-stream roofline; the reference's multi-GPU ZeRO-Inference
    analog)."""

    def _mesh(self, n):
        from deepspeed_tpu.platform.mesh import build_mesh

        return build_mesh({"model": n}, devices=jax.devices()[:n])

    def test_shards_parked_pinned_and_serving_matches(self, rng):
        cfg, params = small_model()
        plain = engine_for(cfg, params)
        off = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8, tensor_parallel=2),
            dtype=jnp.float32, mesh=self._mesh(2),
            offload={"device": "cpu"})
        lp0 = off.params["layers"][0]
        assert "wq" in lp0  # TP keeps projections unfused
        assert lp0["wq"].sharding.memory_kind in _HOST_TIERS
        # head-dim sharded over 'model'
        assert "model" in str(lp0["wq"].sharding.spec)
        prompts = [np.asarray(rng.integers(0, 128, 9), np.int32)]
        l1 = plain.put([0], [prompts[0].copy()])
        l2 = off.put([0], [prompts[0].copy()])
        np.testing.assert_allclose(l2, l1, rtol=2e-4, atol=2e-4)
        for _ in range(2):
            nxt = [np.argmax(l1[0])[None].astype(np.int32)]
            l1 = plain.put([0], nxt)
            l2 = off.put([0], nxt)
            np.testing.assert_allclose(l2, l1, rtol=2e-4, atol=2e-4)


class TestSpeculativeDecoding:
    """Prompt-lookup self-speculative greedy decoding (the r4 profile's
    named policy lever for offload serving: more tokens per weight
    stream). Exactness contract: output == plain greedy, token for
    token; on repetitive text the verify program must accept multi-token
    runs (fewer weight streams than tokens)."""

    def _rep_prompt(self, rng):
        # strongly periodic prompt: n-gram lookup should fire constantly
        base = list(rng.integers(0, 128, 6))
        return (base * 4)[:22]

    def test_matches_plain_greedy(self, rng):
        cfg, params = small_model()
        a = engine_for(cfg, params)
        b = engine_for(cfg, params)
        prompt = self._rep_prompt(rng)
        want = a.generate([prompt], max_new_tokens=12)
        got = b.generate_speculative([prompt], max_new_tokens=12,
                                     ngram=2, draft_len=4)
        assert got == want

    def test_accepts_multi_token_runs(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params)
        calls = {"n": 0}
        orig = eng._verify_chunks

        def counting(uids, chunks):
            calls["n"] += 1
            return orig(uids, chunks)

        eng._verify_chunks = counting
        prompt = self._rep_prompt(rng)
        out = eng.generate_speculative([prompt], max_new_tokens=12,
                                       ngram=2, draft_len=4)
        assert len(out[0]) == 12
        # fewer verify steps than tokens = multi-token acceptance
        assert calls["n"] < 12, calls

    def test_offload_engine_speculative(self, rng):
        """The headline composition: bigger-than-HBM serving pays one
        weight stream per ACCEPTED RUN, not per token."""
        cfg, params = small_model()
        plain = engine_for(cfg, params)
        off = init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32, offload={"device": "cpu"})
        prompt = self._rep_prompt(rng)
        want = plain.generate([prompt], max_new_tokens=10)
        got = off.generate_speculative([prompt], max_new_tokens=10,
                                       ngram=2, draft_len=4)
        assert got == want

    def test_batched_prompts(self, rng):
        cfg, params = small_model()
        a = engine_for(cfg, params)
        b = engine_for(cfg, params)
        prompts = [self._rep_prompt(rng), list(rng.integers(0, 128, 9))]
        want = a.generate(prompts, max_new_tokens=8)
        got = b.generate_speculative(prompts, max_new_tokens=8,
                                     ngram=2, draft_len=3)
        assert got == want


class TestPrefixCacheEngine:
    """Automatic prefix caching end-to-end (the tentpole acceptance
    contract): a second put() of a prompt sharing a >= 1-block prefix
    prefills only the non-cached suffix — asserted via the hit/miss
    counters — and produces logits IDENTICAL to a cache-off engine."""

    def _pair(self, cfg, params, **ckw):
        on = engine_for(cfg, params, **ckw)
        off = engine_for(cfg, params,
                         prefix_cache={"enabled": False}, **ckw)
        assert on.state.enable_prefix_cache
        assert not off.state.enable_prefix_cache
        return on, off

    def test_shared_prefix_skips_prefill_same_logits(self, rng):
        cfg, params = small_model()
        on, off = self._pair(cfg, params)
        prefix = list(rng.integers(0, 128, 16))  # 2 full blocks
        a = np.asarray(prefix + list(rng.integers(0, 128, 5)), np.int32)
        b = np.asarray(prefix + list(rng.integers(0, 128, 3)), np.int32)
        l_on = on.put([0], [a.copy()])
        l_off = off.put([0], [a.copy()])
        np.testing.assert_allclose(l_on, l_off, rtol=1e-5, atol=1e-5)
        st = on.prefix_cache_stats()
        assert st["lookup_hits"] == 0 and st["lookup_misses"] == 1
        l_on = on.put([1], [b.copy()])
        l_off = off.put([1], [b.copy()])
        st = on.prefix_cache_stats()
        # the hit covered the shared 2-block prefix; only the 3-token
        # suffix ran a forward
        assert st["lookup_hits"] == 1 and st["cached_tokens"] == 16
        np.testing.assert_allclose(l_on, l_off, rtol=1e-5, atol=1e-5)
        # shared blocks are physically the same pages
        assert on.state.get(1).blocks[:2] == on.state.get(0).blocks[:2]
        assert off.state.get(1).blocks[0] != off.state.get(0).blocks[0]

    def test_identical_prompt_cows_and_decodes_divergent(self, rng):
        """Exact-multiple identical prompt: the full chain matches, the
        tail goes copy-on-write, and DIVERGENT continuations of the two
        sequences match a cache-off engine step for step (the COW page
        kept the owner's tail intact)."""
        cfg, params = small_model()
        on, off = self._pair(cfg, params)
        p = list(rng.integers(0, 128, 16))  # exactly 2 blocks
        arr = np.asarray(p, np.int32)
        l0 = on.put([0], [arr.copy()])
        l1 = on.put([1], [arr.copy()])
        st = on.prefix_cache_stats()
        assert st["cow_copies"] == 1 and st["cached_tokens"] == 15
        np.testing.assert_allclose(l1, l0, rtol=1e-4, atol=1e-4)
        r0 = off.put([0], [arr.copy()])
        r1 = off.put([1], [arr.copy()])
        np.testing.assert_allclose(l0, r0, rtol=1e-5, atol=1e-5)
        # the COW'd sequence shares block 0 but owns a private tail
        assert on.state.get(1).blocks[0] == on.state.get(0).blocks[0]
        assert on.state.get(1).blocks[1] != on.state.get(0).blocks[1]
        t0 = int(np.argmax(l0[0]))
        t1 = (t0 + 7) % 128  # force divergence
        toks = [np.asarray([t0]), np.asarray([t1])]
        d = on.put([0, 1], [t.copy() for t in toks])
        r = off.put([0, 1], [t.copy() for t in toks])
        np.testing.assert_allclose(d, r, rtol=1e-4, atol=1e-4)
        # another round: sequences keep diverging without cross-talk
        n0, n1 = int(np.argmax(d[0])), int(np.argmax(d[1]))
        toks = [np.asarray([n0]), np.asarray([n1])]
        d2 = on.put([0, 1], [t.copy() for t in toks])
        r2 = off.put([0, 1], [t.copy() for t in toks])
        np.testing.assert_allclose(d2, r2, rtol=1e-4, atol=1e-4)

    def test_flush_of_sharing_sequence_never_double_frees(self, rng):
        cfg, params = small_model()
        on, off = self._pair(cfg, params)
        prefix = list(rng.integers(0, 128, 8))
        a = np.asarray(prefix + [3, 4, 5], np.int32)
        b = np.asarray(prefix + [6, 7], np.int32)
        on.put([0], [a.copy()]); on.put([1], [b.copy()])
        off.put([0], [a.copy()]); off.put([1], [b.copy()])
        shared = on.state.get(0).blocks[0]
        assert on.state.allocator.refcount(shared) == 2
        on.flush(1); off.flush(1)
        assert on.state.allocator.refcount(shared) == 1
        # the survivor keeps decoding correctly on the shared page
        l = on.put([0], [np.asarray([9], np.int32)])
        r = off.put([0], [np.asarray([9], np.int32)])
        np.testing.assert_allclose(l, r, rtol=1e-4, atol=1e-4)
        on.flush(0)
        assert on.state.free_blocks == on.config.num_kv_blocks
        with pytest.raises(KeyError):
            on.flush(0)

    def test_lru_eviction_under_pressure_stays_correct(self, rng):
        """A tiny pool: parked prefix blocks are evicted by fresh
        allocations, counters record it, and logits stay exact."""
        cfg, params = small_model()
        eng = engine_for(cfg, params, num_kv_blocks=4, max_seq_len=32)
        p1 = list(rng.integers(0, 128, 14))
        eng.put([0], [np.asarray(p1, np.int32)])
        eng.flush(0)  # 1 full block parks
        assert eng.state.allocator.cached_blocks == 1
        p2 = list(rng.integers(0, 128, 30))  # 4 blocks: evicts the pool
        l = eng.put([1], [np.asarray(p2, np.int32)])
        assert eng.state.allocator.evictions >= 1
        ref = engine_for(cfg, params, num_kv_blocks=4, max_seq_len=32,
                         prefix_cache={"enabled": False})
        r = ref.put([1], [np.asarray(p2, np.int32)])
        np.testing.assert_allclose(l, r, rtol=1e-4, atol=1e-4)
        eng.flush(1)
        # the evicted chain is gone: re-putting p1 misses
        misses0 = eng.prefix_cache_stats()["lookup_misses"]
        eng.put([2], [np.asarray(p1, np.int32)])
        assert eng.prefix_cache_stats()["lookup_misses"] == misses0 + 1

    def test_can_schedule_counts_parked_blocks(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params, num_kv_blocks=4, max_seq_len=32)
        eng.put([0], [np.asarray(rng.integers(0, 128, 30), np.int32)])
        assert not eng.can_schedule([1], [20])
        eng.flush(0)  # 3 full blocks park + 1 frees
        assert eng.state.allocator.free_blocks < 4
        assert eng.query(1)["free_blocks"] == 4
        assert eng.can_schedule([1], [30])  # parked pool is capacity
        l = eng.put([1], [np.asarray(rng.integers(0, 128, 20), np.int32)])
        assert l.shape[0] == 1

    def test_generate_after_shared_prefill_matches_cache_off(self, rng):
        """generate() rides put() for its prefill, so prompts sharing a
        prefix with an earlier request reuse blocks mid-generation."""
        cfg, params = small_model()
        on, off = self._pair(cfg, params)
        prefix = list(rng.integers(0, 128, 8))
        on.put([0], [np.asarray(prefix + [1, 2], np.int32)])
        off.put([0], [np.asarray(prefix + [1, 2], np.int32)])
        prompts = [prefix + [9], prefix + [11, 12]]
        got_on = on.generate(prompts, max_new_tokens=4)
        got_off = off.generate(prompts, max_new_tokens=4)
        assert got_on == got_off
        assert on.prefix_cache_stats()["lookup_hits"] >= 2

    def test_speculative_stats_report_draft_collapse(self, rng):
        cfg, params = small_model()
        eng = engine_for(cfg, params, max_batch_size=2)
        base = list(rng.integers(0, 128, 4))
        prompts = [(base * 4)[:14], (base * 4)[:12]]
        # 2 live sequences / max_batch 2 -> per_seq=1, k=0 every step
        outs, stats = eng.generate_speculative(
            prompts, max_new_tokens=5, ngram=2, draft_len=4,
            return_stats=True)
        assert all(len(o) == 5 for o in outs)
        assert stats["draft_collapsed_steps"] == stats["steps"] > 0
        assert stats["draft_tokens"] == 0
        assert stats["mean_accepted"] == 1.0
        # plenty of room: no collapse, drafts actually fly
        eng2 = engine_for(cfg, params)
        outs2, stats2 = eng2.generate_speculative(
            [prompts[0]], max_new_tokens=8, ngram=2, draft_len=4,
            return_stats=True)
        assert stats2["draft_collapsed_steps"] == 0
        assert stats2["draft_tokens"] > 0
        assert outs2[0] == eng2.generate([prompts[0]], max_new_tokens=8)[0]
