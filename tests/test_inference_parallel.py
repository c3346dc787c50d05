"""Serving beyond one plain replica: tensor-parallel meshes, ZeRO-Inference
quantization and offload tiers (host, NVMe, under TP), speculative
decoding and the engine's prefix cache (the units and kernels are
tests/test_inference.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import init_inference

from _serving_models import engine_for, small_model

# offload parking tier: pinned_host where the backend has distinct
# memory spaces; backends without them (CPU, jax 0.4.x) fall back to
# the default host memory (platform-compat fallback since the static-
# analysis PR) — a wrongly DEVICE-resident weight still fails either
# way (TPU device memory reports 'device')
_HOST_TIERS = ("pinned_host", "unpinned_host")


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
