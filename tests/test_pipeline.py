"""Pipeline-parallelism tests on the virtual 8-device mesh.

Ref model: tests/unit/runtime/pipe/test_pipe.py — the reference trains
the same net with and without PipelineModule and compares losses. Here
the invariant is stronger: the pipelined engine reproduces the flat
engine's trajectory exactly (same microbatch decomposition, fp32).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.runtime.pipe import (

    partition_layers,
    pipeline_apply,
    unpartition_layers,
)

VOCAB = 128


def model_cfg(**kw):
    base = dict(vocab_size=VOCAB, n_layers=4, n_heads=4, d_model=64, max_seq=32,
                variant="llama", use_flash=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def ds_config(**kw):
    base = {
        "train_batch_size": 16,
        "gradient_accumulation_steps": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "seed": 7,
        "steps_per_print": 1000,
    }
    base.update(kw)
    return base


def data(n=3, batch=16, seq=33, seed=0):
    r = np.random.default_rng(seed)
    return [{"tokens": r.integers(0, VOCAB, (batch, seq)).astype(np.int32)} for _ in range(n)]


def losses(engine, batches):
    return [engine.train_batch(b)["loss"] for b in batches]


class TestPipelineApply:
    """Pure-function correctness: P-stage pipeline == sequential layers."""

    def test_matches_sequential(self):
        L, D, M, mb = 4, 8, 3, 2
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (L, D, D)) * 0.5
        x = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, D))

        def seq_apply(h):
            def body(c, wl):
                return jnp.tanh(c @ wl), None

            out, _ = jax.lax.scan(body, h, w)
            return out

        expected = jax.vmap(seq_apply)(x)

        for n_stages in (1, 2, 4):
            stage_w = partition_layers(w, n_stages)

            def stage_fn(wst, h, key, sid):
                def body(c, wl):
                    return jnp.tanh(c @ wl), None

                out, _ = jax.lax.scan(body, h, wst)
                return out

            got = pipeline_apply(stage_fn, stage_w, x)
            np.testing.assert_allclose(got, expected, rtol=1e-6, atol=1e-6)

    def test_pytree_state_and_aux_channel(self):
        """Aux values accumulate across stages like MoE load-balance loss."""
        L, D, M, mb = 4, 8, 2, 2
        w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.5
        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))

        def stage_fn(wst, carry, key, sid):
            h, aux = carry

            def body(c, wl):
                return jnp.tanh(c @ wl), jnp.sum(c)

            h, per_layer = jax.lax.scan(body, h, wst)
            return h, aux + jnp.sum(per_layer)

        out2 = pipeline_apply(stage_fn, partition_layers(w, 2),
                              (x, jnp.zeros((M,), jnp.float32)))
        out1 = pipeline_apply(stage_fn, partition_layers(w, 1),
                              (x, jnp.zeros((M,), jnp.float32)))
        np.testing.assert_allclose(out2[0], out1[0], rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(out2[1], out1[1], rtol=1e-6, atol=1e-6)

    def test_partition_roundtrip(self):
        w = jnp.arange(24.0).reshape(4, 3, 2)
        assert (unpartition_layers(partition_layers(w, 2)) == w).all()

    def test_indivisible_raises(self):
        with pytest.raises(ValueError, match="not divisible"):
            partition_layers(jnp.zeros((3, 2)), 2)


class TestPipelineEngine:
    """pipe=2 trajectory == flat engine trajectory (VERDICT r1 item 3)."""

    @pytest.fixture(scope="class")
    def baseline(self):
        engine = ds.initialize(
            ds_config(mesh={"data": 4, "model": 2}),
            loss_fn=T.make_loss_fn(model_cfg()),
            param_init_fn=lambda k: T.init(model_cfg(), k),
            param_logical_specs=T.logical_specs(model_cfg()),
        )
        return losses(engine, data())

    def _pipelined_engine(self, **cfg_kw):
        mcfg = model_cfg(pipeline_stages=2)
        base = ds_config(mesh={"pipe": 2, "data": 4})
        base.update(cfg_kw)
        return ds.initialize(
            base,
            loss_fn=T.make_pipelined_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg),
            pipelined=True,
        )

    def test_pipe2_matches_flat(self, baseline):
        engine = self._pipelined_engine()
        np.testing.assert_allclose(losses(engine, data()), baseline, rtol=2e-4)

    def test_pipe2_zero1_matches_flat(self, baseline):
        engine = self._pipelined_engine(zero_optimization={"stage": 1})
        np.testing.assert_allclose(losses(engine, data()), baseline, rtol=2e-4)

    def test_layers_sharded_over_pipe(self):
        engine = self._pipelined_engine()
        w = engine.state.params["layers"]["w_in"]
        assert w.shape[0] == 2  # [P, L/P, ...]
        assert "pipe" in str(w.sharding.spec)

    def test_eval_batch(self):
        engine = self._pipelined_engine()
        loss = engine.eval_batch(data(1)[0])
        assert np.isfinite(loss) and loss > 0

    def test_eval_partial_batch(self):
        """Partial validation batches run as one pipeline microbatch."""
        engine = self._pipelined_engine()
        loss = engine.eval_batch(data(1, batch=6)[0])
        assert np.isfinite(loss) and loss > 0

    def test_flat_forward_on_pipelined_params(self):
        """Generation path: T.forward works on stage-partitioned params."""
        mcfg = model_cfg(pipeline_stages=2)
        params = T.init(mcfg, jax.random.PRNGKey(0))
        flat = T.init(model_cfg(), jax.random.PRNGKey(0))
        toks = jnp.zeros((2, 8), jnp.int32)
        np.testing.assert_allclose(
            T.forward(params, toks, mcfg), T.forward(flat, toks, model_cfg()),
            rtol=1e-6, atol=1e-6,
        )

    def test_pipe_mesh_without_pipelined_loss_raises(self):
        mcfg = model_cfg()
        with pytest.raises(NotImplementedError, match="pipelined"):
            ds.initialize(
                ds_config(mesh={"pipe": 2, "data": 4}),
                loss_fn=T.make_loss_fn(mcfg),
                param_init_fn=lambda k: T.init(mcfg, k),
                param_logical_specs=T.logical_specs(mcfg),
            )


class TestCircularPipeline:
    """Interleaved (virtual-stage) schedule: circular pipe reproduces the
    flat trajectory and its chunk-step count obeys the bubble math
    (VERDICT r2 item 7; ref: Megatron interleaved 1F1B via
    runtime/pipe/schedule.py)."""

    def test_circular_apply_matches_sequential(self):
        from deepspeed_tpu.runtime.pipe import pipeline_apply_circular

        L, D, M, mb = 8, 8, 6, 2
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (L, D, D)) * 0.5
        x = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, D))

        def seq_apply(h):
            def body(c, wl):
                return jnp.tanh(c @ wl), None

            out, _ = jax.lax.scan(body, h, w)
            return out

        expected = jax.vmap(seq_apply)(x)
        for P_, v in ((2, 2), (4, 2), (2, 4)):
            stage_w = partition_layers(w, P_, virtual=v)

            def chunk_fn(wst, h, key, sid, rnd):
                r = jnp.minimum(rnd, v - 1)
                wc = jax.lax.dynamic_index_in_dim(wst, r, 0, keepdims=False)

                def body(c, wl):
                    return jnp.tanh(c @ wl), None

                out, _ = jax.lax.scan(body, h, wc)
                return out

            got = pipeline_apply_circular(chunk_fn, stage_w, x)
            np.testing.assert_allclose(got, expected, rtol=1e-6, atol=1e-6,
                                       err_msg=f"P={P_} v={v}")

    def test_schedule_len_bubble_math(self):
        from deepspeed_tpu.runtime.pipe import (
            bubble_fraction,
            circular_schedule_len,
            simulate_schedule,
        )

        # plain schedule: M + P - 1 full-stage steps; circular: each
        # chunk-step is tau/v, every one of the T steps computes (the
        # output is collected at slot P-1 post-compute), so wall-clock
        # is (Mv + P - 1) chunk-steps = M*tau + (P-1)*tau/v — bubble
        # divided by v
        M, P_ = 8, 4
        for v in (1, 2, 4):
            T_ = circular_schedule_len(M, P_, v)
            assert T_ == v * P_ * (M // P_) + P_ - 1
            wall_in_tau = T_ / v
            bubble = wall_in_tau - M
            np.testing.assert_allclose(bubble, (P_ - 1) / v)
            # the measured (iteration-count) accounting agrees with the
            # closed form at M = k*P
            sim = simulate_schedule(M, P_, v)
            np.testing.assert_allclose(sim["bubble_fraction"],
                                       bubble_fraction(M, P_, v))
            np.testing.assert_allclose(sim["wall_tau"], wall_in_tau)

    def test_partition_circular_roundtrip(self):
        w = jnp.arange(48.0).reshape(8, 3, 2)
        got = unpartition_layers(partition_layers(w, 2, virtual=2), virtual=2)
        assert (got == w).all()

    def test_circular_engine_matches_flat(self):
        """pipe=4 x virtual=2 trajectory == flat engine (fp32)."""
        flat = ds.initialize(
            ds_config(mesh={"data": 4, "model": 2}),
            loss_fn=T.make_loss_fn(model_cfg(n_layers=8)),
            param_init_fn=lambda k: T.init(model_cfg(n_layers=8), k),
            param_logical_specs=T.logical_specs(model_cfg(n_layers=8)),
        )
        base = losses(flat, data())
        mcfg = model_cfg(n_layers=8, pipeline_stages=4,
                         pipeline_virtual_stages=2)
        eng = ds.initialize(
            ds_config(mesh={"pipe": 4, "data": 2}),
            loss_fn=T.make_pipelined_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg),
            pipelined=True,
        )
        w = eng.state.params["layers"]["w_in"]
        assert w.shape[:2] == (2, 4)  # [v, P, lc, ...]
        assert "pipe" in str(w.sharding.spec)
        np.testing.assert_allclose(losses(eng, data()), base, rtol=2e-4)

    def test_embed_sharded_over_pipe(self):
        """Stage placement of embedding/head, SPMD-style: the vocab dim
        shards over 'pipe' so no stage pays the full table (the
        TiedLayerSpec analog)."""
        mcfg = model_cfg(pipeline_stages=2)
        eng = ds.initialize(
            ds_config(mesh={"pipe": 2, "data": 4}),
            loss_fn=T.make_pipelined_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg),
            pipelined=True,
        )
        embed = eng.state.params["embed"]
        assert "pipe" in str(embed.sharding.spec), embed.sharding
        assert embed.sharding.shard_shape(embed.shape)[0] == VOCAB // 2

    def test_circular_dropout_matches_flat_pipeline(self):
        """Per-layer dropout keys are chunk-sliced from the SAME global
        split — circular reproduces plain-pipeline numerics."""
        def build(v):
            mcfg = model_cfg(n_layers=8, dropout=0.1, pipeline_stages=2,
                             pipeline_virtual_stages=v)
            return ds.initialize(
                ds_config(mesh={"pipe": 2, "data": 4}),
                loss_fn=T.make_pipelined_loss_fn(mcfg),
                param_init_fn=lambda k: T.init(mcfg, k),
                param_logical_specs=T.logical_specs(mcfg),
                pipelined=True,
                pipeline_virtual_stages=v,
            )

        np.testing.assert_allclose(
            losses(build(2), data()), losses(build(1), data()), rtol=2e-4)


class TestPipelineDropout:
    """Dropout numerics: pipe=2 == pipe=1 (same per-microbatch keys)."""

    def test_dropout_trajectory_matches(self):
        def build(stages):
            mcfg = model_cfg(dropout=0.1, pipeline_stages=stages)
            mesh = {"pipe": stages, "data": 4, "model": 2 // stages}
            return ds.initialize(
                ds_config(mesh=mesh),
                loss_fn=T.make_pipelined_loss_fn(mcfg),
                param_init_fn=lambda k: T.init(mcfg, k),
                param_logical_specs=T.logical_specs(mcfg),
                pipelined=True,
            )

        l1 = losses(build(1), data())
        l2 = losses(build(2), data())
        np.testing.assert_allclose(l2, l1, rtol=2e-4)
