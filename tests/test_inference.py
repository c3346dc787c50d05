"""Inference units: allocator/manager invariants, the paged decode
kernels vs their jnp oracle, per-channel int8, sampling, ALiBi serving
(ref strategy: tests/unit/inference/v2/ragged + kernels tests vs torch
references). The engine end to end is tests/test_inference_engine.py;
tensor-parallel, offload and cache-reuse serving is
tests/test_inference_parallel.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (
    BlockedAllocator,
    InferenceEngine,
    StateManager,
    init_inference,
)
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_xla,
)

from _serving_models import engine_for, oracle_next_logits, small_model


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

    def _oracle(self, q, kc, vc, tbl, ctx, kn, vn, slots, window=0):
        from deepspeed_tpu.inference.model import _write_kv_xla

        ck, cv = _write_kv_xla(kc, vc, kn, vn, slots)
        out = paged_decode_attention_xla(q, ck, cv, tbl, ctx, window=window)
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
