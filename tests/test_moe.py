"""MoE / expert-parallelism tests.

Ref model: tests/unit/moe/test_moe.py (gating correctness, EP-size
invariance) — here layout-equivalence is trajectory equality on the
virtual 8-device mesh, and gating is unit-tested against the GShard
invariants (capacity enforcement, renormalization, aux loss at uniform).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.moe import compute_capacity, top1_gating, top2_gating

VOCAB = 128


def model_cfg(**kw):
    base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64, max_seq=32,
                variant="llama", use_flash=False, n_experts=4, moe_top_k=1,
                moe_capacity_factor=2.0)
    base.update(kw)
    return T.TransformerConfig(**base)


def ds_config(**kw):
    base = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "seed": 7,
        "steps_per_print": 1000,
    }
    base.update(kw)
    return base


def build_engine(mcfg, **cfg_kw):
    return ds.initialize(
        ds_config(**cfg_kw),
        loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
    )


def data(n=3, batch=16, seq=33, seed=0):
    r = np.random.default_rng(seed)
    return [{"tokens": r.integers(0, VOCAB, (batch, seq)).astype(np.int32)} for _ in range(n)]


class TestGating:
    def test_top1_capacity_enforced(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)
        combine, dispatch, _ = top1_gating(logits, capacity_factor=1.0, min_capacity=1)
        C = compute_capacity(64, 4, 1.0, 1)
        assert dispatch.shape == (64, 4, C)
        # No expert slot used twice.
        slot_use = jnp.sum(dispatch, axis=0)  # [X, C]
        assert int(slot_use.max()) <= 1
        # Per-expert token count <= capacity.
        assert int(jnp.sum(dispatch, axis=(0, 2)).max()) <= C

    def test_top1_skewed_logits_drop_tokens(self):
        # All tokens want expert 0 → only C survive, rest have zero combine.
        logits = jnp.tile(jnp.asarray([[10.0, 0.0, 0.0, 0.0]]), (32, 1))
        combine, dispatch, _ = top1_gating(logits, capacity_factor=1.0, min_capacity=1)
        C = compute_capacity(32, 4, 1.0, 1)
        kept = jnp.sum(dispatch)
        assert int(kept) == C
        dropped_rows = jnp.sum(combine, axis=(1, 2)) == 0
        assert int(jnp.sum(dropped_rows)) == 32 - C

    def test_top1_aux_loss_uniform_is_one(self):
        # Uniform gates and uniform assignment → l_aux == 1.0 exactly.
        logits = jnp.zeros((32, 4), jnp.float32)
        # break argmax ties round-robin by epsilon bumps
        bump = jax.nn.one_hot(jnp.arange(32) % 4, 4) * 1e-4
        _, _, l_aux = top1_gating(logits + bump, capacity_factor=4.0)
        np.testing.assert_allclose(float(l_aux), 1.0, rtol=1e-3)

    def test_top2_combine_renormalized(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        combine, dispatch, _ = top2_gating(logits, capacity_factor=4.0)
        # With ample capacity every token keeps 2 experts, weights sum to 1.
        per_token = jnp.sum(combine, axis=(1, 2))
        np.testing.assert_allclose(np.asarray(per_token), 1.0, atol=1e-5)
        assert int(jnp.sum(dispatch, axis=(1, 2)).min()) == 2

    def test_noisy_gate_policies(self):
        logits = jnp.zeros((16, 4), jnp.float32)
        for policy in ("RSample", "Jitter"):
            c, d, a = top1_gating(
                logits, capacity_factor=4.0, rng=jax.random.PRNGKey(0),
                noisy_gate_policy=policy,
            )
            assert np.isfinite(float(a))
        with pytest.raises(ValueError):
            top1_gating(logits, rng=jax.random.PRNGKey(0), noisy_gate_policy="bogus")


class TestMoETraining:
    @pytest.fixture(scope="class")
    def default(self):
        """The class's ONE engine of `model_cfg()` (top-1, the whole mesh
        as data, a batch of 16) and its trajectory, taken while new."""
        engine = build_engine(model_cfg())
        return engine, [engine.train_batch(b)["loss"] for b in data()]

    def test_loss_decreases(self, default):
        engine, _ = default
        batch = data(1)[0]
        ls = [engine.train_batch(batch)["loss"] for _ in range(8)]
        assert ls[-1] < ls[0]

    def test_expert_params_sharded(self):
        engine = build_engine(model_cfg(), mesh={"data": 4, "expert": 2})
        w = engine.state.params["layers"]["w_in"]  # [L, X, E, F]
        assert "expert" in str(w.sharding.spec)

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_ep_layout_equivalence(self, top_k, default):
        """EP=1 vs EP=2 is a layout change only — same trajectory
        (ref: the expert group is carved out of the DP world,
        utils/groups.py:113)."""
        mcfg = model_cfg(moe_top_k=top_k)
        if top_k == 1:  # EP=1 at top-1 is the default engine
            base_losses = default[1]
        else:
            base = build_engine(mcfg, mesh={"data": -1}, train_batch_size=16)
            base_losses = [base.train_batch(b)["loss"] for b in data()]
        ep = build_engine(mcfg, mesh={"data": 4, "expert": 2}, train_batch_size=16)
        ep_losses = [ep.train_batch(b)["loss"] for b in data()]
        np.testing.assert_allclose(ep_losses, base_losses, rtol=2e-4)

    def test_capacity_overflow_still_trains(self):
        # Tiny capacity factor: most tokens dropped, residual carries them.
        mcfg = model_cfg(moe_capacity_factor=0.25, moe_min_capacity=1)
        engine = build_engine(mcfg)
        out = engine.train_batch(data(1)[0])
        assert np.isfinite(out["loss"])

    def test_moe_gpt2_variant(self):
        mcfg = model_cfg(variant="gpt2", moe_top_k=2)
        engine = build_engine(mcfg)
        out = engine.train_batch(data(1)[0])
        assert np.isfinite(out["loss"])

    def test_aux_loss_contributes(self):
        """moe_aux_loss_coef shifts the total loss."""
        mcfg_on = model_cfg(moe_aux_loss_coef=10.0)
        mcfg_off = model_cfg(moe_aux_loss_coef=0.0)
        b = data(1)[0]
        on = build_engine(mcfg_on).train_batch(b)["loss"]
        off = build_engine(mcfg_off).train_batch(b)["loss"]
        assert on > off


class TestPRMoE:
    """PR-MoE / residual MoE (ref: moe/layer.py:29 use_residual, arXiv
    2201.05596): moe(h)*c0 + dense(h)*c1 with a learned softmax mix."""

    def _engine(self, **kw):
        mcfg = model_cfg(moe_use_residual=True, **kw)
        return mcfg, ds.initialize(
            ds_config(mesh={"expert": 2, "data": 4}),
            loss_fn=T.make_loss_fn(mcfg, loss_chunks=1),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))

    def test_residual_params_exist_and_train(self):
        mcfg, eng = self._engine()
        L = eng.state.params["layers"]
        for name in ("wr_in", "wr_out", "wr_gate", "w_coef", "b_coef"):
            assert name in L, name
        r = np.random.default_rng(0)
        b = {"tokens": r.integers(
            0, VOCAB, (eng.config.train_batch_size, 33)).astype(np.int32)}
        ls = [eng.train_batch(b)["loss"] for _ in range(8)]
        assert all(np.isfinite(l) for l in ls)
        assert min(ls[4:]) < ls[0]

    def test_residual_changes_forward(self):
        """With the coefficient biased toward the dense expert, the
        residual branch demonstrably participates (zeroing wr_out must
        change logits)."""
        mcfg = model_cfg(moe_use_residual=True)
        params = T.init(mcfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(np.random.default_rng(0)
                           .integers(0, VOCAB, (1, 8)))
        base = T.forward(params, toks, mcfg)
        p2 = dict(params)
        p2["layers"] = dict(params["layers"])
        p2["layers"]["wr_out"] = jnp.zeros_like(params["layers"]["wr_out"])
        alt = T.forward(p2, toks, mcfg)
        assert not np.allclose(np.asarray(base), np.asarray(alt))

    def test_serving_matches_training_forward(self):
        """PR-MoE serves: engine prefill logits == T.forward next-token
        logits (capacity-free serving == training where nothing drops;
        capacity_factor is high enough here that nothing does)."""
        from deepspeed_tpu.inference import init_inference

        mcfg = model_cfg(moe_use_residual=True, moe_capacity_factor=4.0)
        params = T.init(mcfg, jax.random.PRNGKey(1))
        eng = init_inference(
            params, mcfg,
            dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=16,
                 min_prefill_bucket=8, max_batch_size=4),
            dtype=jnp.float32)
        r = np.random.default_rng(0)
        prompt = r.integers(0, VOCAB, 9).astype(np.int32)
        out = eng.put([0], [prompt.copy()])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(
                T.forward(params, jnp.asarray(prompt[None]), mcfg)[0, -1])
        np.testing.assert_allclose(out[0], ref, rtol=2e-2, atol=2e-2)
