"""Hybrid engine tests: train + generate on shared weights (RLHF core).

Ref model: the DeepSpeed-Chat actor flow — generate a rollout, train,
generate again with the UPDATED weights (ref: runtime/hybrid_engine.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.runtime.hybrid_engine import HybridEngine

# interpreter-/compile-heavy: excluded from the fast lane (-m 'not slow')
import pytest  # noqa: E402

VOCAB = 128


def model_cfg():
    return T.TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=4,
                               d_model=64, max_seq=128, variant="llama",
                               use_flash=False)


def build_hybrid():
    mcfg = model_cfg()
    engine = ds.initialize(
        {"train_micro_batch_size_per_gpu": 2,
         "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
         "seed": 7, "steps_per_print": 1000},
        loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg))
    return HybridEngine(
        engine, mcfg,
        {"max_seq_len": 64, "kv_block_size": 8, "num_kv_blocks": 32,
         "min_prefill_bucket": 8, "max_batch_size": 8},
        dtype=jnp.float32)


def data(seed=0):
    r = np.random.default_rng(seed)
    return {"tokens": r.integers(0, VOCAB, (16, 33)).astype(np.int32)}


class TestHybridEngine:
    def test_generate_train_generate(self):
        hybrid = build_hybrid()
        r = np.random.default_rng(1)
        prompts = [list(r.integers(0, VOCAB, 6)) for _ in range(2)]

        out0 = hybrid.generate(prompts, max_new_tokens=4)
        assert all(len(o) == 4 for o in out0)
        # aggressive steps: weights move, generation must follow
        for _ in range(5):
            hybrid.train_batch(data())
        out1 = hybrid.generate(prompts, max_new_tokens=4)
        assert out1 != out0  # updated policy decodes differently

    def test_sampled_rollouts(self):
        """PPO exploration: sampled rollouts pass through the hybrid
        surface, reproducible under a seed (ref: DeepSpeed-Chat actor
        generate runs HF sampling)."""
        hybrid = build_hybrid()
        r = np.random.default_rng(2)
        prompts = [list(r.integers(0, VOCAB, 6)) for _ in range(2)]
        a = hybrid.generate(prompts, max_new_tokens=6, do_sample=True,
                            temperature=1.2, top_k=30, seed=5)
        b = hybrid.generate(prompts, max_new_tokens=6, do_sample=True,
                            temperature=1.2, top_k=30, seed=5)
        c = hybrid.generate(prompts, max_new_tokens=6, do_sample=True,
                            temperature=1.2, top_k=30, seed=6)
        assert a == b and a != c

    def test_generation_serves_current_weights(self):
        """Hybrid output == fresh inference engine over the same params."""
        from deepspeed_tpu.inference import init_inference

        hybrid = build_hybrid()
        hybrid.train_batch(data())
        r = np.random.default_rng(2)
        prompts = [list(r.integers(0, VOCAB, 5))]
        got = hybrid.generate(prompts, max_new_tokens=3)

        fresh = init_inference(
            hybrid.engine.state.params, model_cfg(),
            {"max_seq_len": 64, "kv_block_size": 8, "num_kv_blocks": 32,
             "min_prefill_bucket": 8, "max_batch_size": 8},
            dtype=jnp.float32)
        want = fresh.generate(prompts, max_new_tokens=3)
        assert got == want

    def test_refresh_only_on_param_change(self):
        """The serving tree is a PREPARED copy (per-layer unstacked,
        fused GEMMs — inference/model.prepare); the shared-weights
        contract is now 'refresh exactly when training params change',
        not pointer identity. _refresh with an unchanged training tree
        must not rebuild the serving tree."""
        hybrid = build_hybrid()
        eng = hybrid.inference_engine
        assert isinstance(eng.params["layers"], list)  # prepared layout
        before = eng.params["layers"][0]["w_qkv"]
        hybrid._refresh()  # params object unchanged -> no rebuild
        assert eng.params["layers"][0]["w_qkv"] is before
        # served values track the training tree contents
        np.testing.assert_allclose(
            np.asarray(eng.params["embed"]),
            np.asarray(hybrid.engine.state.params["embed"]),
            rtol=0, atol=0)
