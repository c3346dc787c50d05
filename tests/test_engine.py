"""Engine end-to-end tests on the virtual 8-device mesh.

Ref model: tests/unit/runtime/zero/test_zero.py correctness strategy —
tiny models, loss-equality across configurations. Here the key
invariant is that every parallelism layout (ZeRO stage, TP, Ulysses,
GAS split) computes the SAME global training trajectory.
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T

VOCAB = 128


def model_cfg(**kw):
    base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64, max_seq=32,
                variant="llama", use_flash=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def ds_config(**kw):
    base = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "seed": 7,
        "steps_per_print": 1000,
    }
    base.update(kw)
    return base


def build_engine(mcfg=None, **cfg_kw):
    mcfg = mcfg or model_cfg()
    return ds.initialize(
        ds_config(**cfg_kw),
        loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
    )


def data(n=3, batch=16, seq=33, seed=0):
    r = np.random.default_rng(seed)
    return [{"tokens": r.integers(0, VOCAB, (batch, seq)).astype(np.int32)} for _ in range(n)]


def losses(engine, batches):
    return [engine.train_batch(b)["loss"] for b in batches]


@pytest.fixture(scope="module")
def default():
    """The module's ONE engine of `ds_config()` itself (stage 0, the
    whole mesh as data, a batch of 16: every baseline here, the same
    losses bit for bit) and its trajectory over `data()`, taken while it
    was new; a case that trains it on compares nothing to a new one."""
    engine = build_engine()
    return engine, losses(engine, data())


@pytest.fixture(scope="module")
def baseline(default):
    return default[1]


class TestTraining:
    def test_loss_decreases(self, default):
        engine, _ = default
        batch = data(1)[0]
        ls = [engine.train_batch(batch)["loss"] for _ in range(8)]
        assert ls[-1] < ls[0]

    def test_eval_batch(self, default):
        loss = default[0].eval_batch(data(1, batch=8)[0])
        assert np.isfinite(loss) and loss > 0


class TestZeroEquivalence:
    """Stages 0-3 must produce identical trajectories (fp32)."""

    @pytest.mark.parametrize("stage", [1, 2, 3])
    def test_stage_matches_baseline(self, baseline, stage):
        engine = build_engine(
            zero_optimization={"stage": stage, "param_persistence_threshold": 64}
        )
        ls = losses(engine, data())
        np.testing.assert_allclose(ls, baseline, rtol=2e-4)

    def test_stage3_actually_shards_params(self):
        engine = build_engine(
            zero_optimization={"stage": 3, "param_persistence_threshold": 64}
        )
        w = engine.state.params["layers"]["w_in"]
        assert "data" in str(w.sharding.spec)


class TestParallelismEquivalence:
    """Different mesh layouts, same global batch of 16 → same trajectory."""

    def test_tensor_parallel(self, baseline):
        engine = build_engine(mesh={"data": 4, "model": 2}, train_batch_size=16, gradient_accumulation_steps=2)
        np.testing.assert_allclose(losses(engine, data()), baseline, rtol=2e-4)

    def test_ulysses_sequence_parallel(self, baseline):
        engine = build_engine(mesh={"data": 4, "seq": 2}, train_batch_size=16, gradient_accumulation_steps=2)
        np.testing.assert_allclose(losses(engine, data()), baseline, rtol=2e-4)

    def test_tp_and_zero3_compose(self, baseline):
        engine = build_engine(
            mesh={"data": 4, "model": 2},
            train_batch_size=16,
            gradient_accumulation_steps=2,
            zero_optimization={"stage": 3, "param_persistence_threshold": 64},
        )
        np.testing.assert_allclose(losses(engine, data()), baseline, rtol=2e-4)

    def test_tp_params_sharded(self):
        engine = build_engine(mesh={"data": 4, "model": 2}, train_batch_size=16, gradient_accumulation_steps=2)
        w = engine.state.params["layers"]["w_in"]  # [L, E, F] → F over model
        assert "model" in str(w.sharding.spec)


class TestBatchHandling:
    def test_rank1_batch_leaf(self):
        # a per-microbatch scalar leaf [gas] must shard/reshape cleanly
        engine = build_engine(gradient_accumulation_steps=2,
                              train_micro_batch_size_per_gpu=1)
        r = np.random.default_rng(0)
        out = engine.shard_batch(
            {"tokens": r.integers(0, VOCAB, (2, 8, 33)).astype(np.int32),
             "weight": np.ones((2,), np.float32)},
            leading_accum_dim=True,
        )
        assert out["weight"].shape == (2,)


class TestGradientAccumulation:
    def test_gas_equivalence(self, baseline):
        # same global batch, different micro/gas split (2 x 1 is the
        # default's) → same trajectory
        e2 = build_engine(train_micro_batch_size_per_gpu=1, gradient_accumulation_steps=2)
        np.testing.assert_allclose(baseline, losses(e2, data()), rtol=2e-4)


class TestPrecisionModes:
    def test_bf16_trains(self):
        engine = build_engine(bf16={"enabled": True}, zero_optimization={"stage": 2})
        batch = data(1)[0]
        ls = [engine.train_batch(batch)["loss"] for _ in range(6)]
        assert ls[-1] < ls[0]
        # params stored bf16, master fp32
        assert engine.state.params["embed"].dtype == jax.numpy.bfloat16
        assert engine.state.master["embed"].dtype == jax.numpy.float32

    def test_fp16_loss_scaling(self):
        engine = build_engine(
            fp16={"enabled": True, "initial_scale_power": 8}, zero_optimization={"stage": 1}
        )
        batch = data(1)[0]
        m = engine.train_batch(batch)
        assert m["loss_scale"] >= 256.0
        assert m["skipped"] in (0.0, 1.0)

    def test_gpt2_variant(self):
        mcfg = model_cfg(variant="gpt2", tie_embeddings=True)
        engine = build_engine(mcfg=mcfg)
        batch = data(1)[0]
        ls = [engine.train_batch(batch)["loss"] for _ in range(5)]
        assert ls[-1] < ls[0]


class TestRound2Fixes:
    def test_pipe_axis_raises_until_pp(self):
        """VERDICT r1 W3: a pipe axis that nothing consumes must not
        silently waste devices."""
        with pytest.raises(NotImplementedError):
            build_engine(mesh={"pipe": 2, "data": 4})

    def test_eval_has_no_dropout(self):
        """VERDICT r1 W5 / ADVICE: eval must run with dropout disabled —
        repeated eval_batch calls return the identical loss."""
        mcfg = model_cfg(dropout=0.5)
        engine = build_engine(mcfg)
        b = data(1, batch=8)[0]
        assert engine.eval_batch(b) == engine.eval_batch(b)

    def test_activation_checkpointing_policy_changes_program(self, baseline):
        """VERDICT r1 item 6: the DeepSpeed-style activation_checkpointing
        block must actually drive rematerialization (remat shows up in the
        compiled step) without changing numerics."""
        batches = data(2)  # the first two of `data()`
        ref = baseline[:2]

        engine = build_engine(activation_checkpointing={"policy": "full"})
        got = losses(engine, batches)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

        jaxpr = str(jax.make_jaxpr(
            engine._build_train_step().__wrapped__
        )(engine.state, engine.shard_batch(
            engine._reshape_gas(batches[0]), leading_accum_dim=True)))
        assert "remat" in jaxpr or "checkpoint" in jaxpr


class TestActivationOffload:
    """cpu_checkpointing + partition_activations (ref: runtime/
    activation_checkpointing/checkpointing.py:989)."""

    def test_cpu_checkpointing_matches_dots_no_batch(self):
        batches = data(2)
        ref = losses(
            build_engine(activation_checkpointing={"policy": "dots_no_batch"}),
            batches,
        )
        engine = build_engine(activation_checkpointing={
            "policy": "dots_no_batch", "cpu_checkpointing": True})
        got = losses(engine, batches)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        # the saved-dot host transfers are in the traced program (XLA:CPU
        # may elide the placement custom-calls in the final HLO — host and
        # device memory coincide there; on TPU they lower to D2H/H2D)
        jaxpr = str(jax.make_jaxpr(
            engine._build_train_step().__wrapped__
        )(engine.state, engine.shard_batch(
            engine._reshape_gas(batches[0]), leading_accum_dim=True)))
        assert "<host>" in jaxpr  # offloaded residuals are host-typed

    def test_cpu_checkpointing_requires_dots_no_batch(self):
        with pytest.raises(ValueError, match="dots_no_batch"):
            build_engine(activation_checkpointing={
                "policy": "full", "cpu_checkpointing": True})

    def test_partition_activations_not_replicated_over_model_axis(self):
        """partition_activations is satisfied BY DESIGN under SPMD: remat-
        saved residuals stay sharded over the model axis. Evidence: at a
        fixed global batch, the per-device temp footprint with tp=4 stays
        ~equal to pure-dp (were activations replicated across the 4 model
        ranks — what the reference flag exists to prevent — it would be
        ~4x larger)."""
        def temp_bytes(micro, **mesh):
            engine = build_engine(
                activation_checkpointing={"policy": "dots_no_batch",
                                          "partition_activations": True},
                train_micro_batch_size_per_gpu=micro,
                mesh=mesh,
            )
            losses(engine, data(1))
            return engine._train_compiled.memory_analysis().temp_size_in_bytes

        tp = temp_bytes(8, model=4, data=2)   # global batch 16 = 8 x dp2
        dp = temp_bytes(2, model=1, data=8)   # global batch 16 = 2 x dp8
        assert tp < 2.5 * dp
