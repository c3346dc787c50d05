"""The serving engine end to end: prefill + decode against the training
model's full-context forward for every variant, batched prefill,
sliding-window and MoE serving (one device; the units and kernels are
tests/test_inference.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.models import transformer as T

from _serving_models import engine_for, oracle_next_logits, small_model


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
