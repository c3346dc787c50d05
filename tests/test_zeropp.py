"""ZeRO++ (qwZ quantized weight gather) and MiCS/hpZ sub-group tests.

Ref model: tests/unit/runtime/zero/test_zeropp.py — the reference trains
tiny models with qwZ/hpZ on and checks convergence; here additionally
the sub-group sharding layout is asserted directly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import quantization as Q

VOCAB = 128


def model_cfg(**kw):
    base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64, max_seq=32,
                variant="llama", use_flash=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def ds_config(**kw):
    base = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "seed": 7,
        "steps_per_print": 1000,
    }
    base.update(kw)
    return base


def build_engine(**cfg_kw):
    mcfg = model_cfg()
    return ds.initialize(
        ds_config(**cfg_kw),
        loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
    )


def data(n=4, batch=16, seq=33, seed=0):
    r = np.random.default_rng(seed)
    return [{"tokens": r.integers(0, VOCAB, (batch, seq)).astype(np.int32)} for _ in range(n)]


def losses(engine, batches):
    return [engine.train_batch(b)["loss"] for b in batches]


def trained(n=4, **cfg_kw):
    """A class's ONE engine of a configuration and its trajectory over
    `data(n)`, taken while it was new; layout and program are read after."""
    engine = build_engine(**cfg_kw)
    return engine, losses(engine, data(n))


class TestQuantizationKernels:
    def test_blockwise_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
        q, s = Q.quantize_blockwise(x, block=128)
        y = Q.dequantize_blockwise(q, s, x.shape)
        assert float(jnp.max(jnp.abs(x - y))) <= float(jnp.max(s)) / 2 + 1e-6

    def test_per_axis_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
        q, s = Q.quantize_per_axis(x, 0)
        y = Q.dequantize_per_axis(q, s, 0)
        # per-channel int8: max error is half a quantization step per row
        err = jnp.max(jnp.abs(x - y), axis=1)
        assert (np.asarray(err) <= np.asarray(s) * 0.5 + 1e-6).all()

    def test_int4_pack_roundtrip(self):
        q = jnp.array([[-7, 3, 0, 7, -1, 5]], jnp.int8)
        assert (Q.unpack_int4(Q.pack_int4(q)) == q).all()

    def test_zero_block_stays_zero(self):
        x = jnp.zeros((256,))
        q, s = Q.quantize_blockwise(x, block=64)
        assert (Q.dequantize_blockwise(q, s, x.shape) == 0).all()


class TestHpZ:
    """zero_hpz_partition_size=k → data factored into data×zero."""

    @pytest.fixture(scope="class")
    def full(self):
        return trained(
            zero_optimization={"stage": 3, "param_persistence_threshold": 64})

    @pytest.fixture(scope="class")
    def hpz(self):
        return trained(zero_optimization={
            "stage": 3, "param_persistence_threshold": 64,
            "zero_hpz_partition_size": 2,
        })

    def test_hpz_matches_full_sharding_trajectory(self, full, hpz):
        engine, trajectory = hpz
        assert engine.mesh.shape["zero"] == 2
        assert engine.mesh.shape["data"] == 4
        np.testing.assert_allclose(trajectory, full[1], rtol=2e-4)

    def test_hpz_shards_within_subgroup_only(self, full, hpz):
        engine, full = hpz[0], full[0]
        spec = str(engine.state.params["layers"]["w_in"].sharding.spec)
        assert "zero" in spec and "data" not in spec
        # replicated across the 2 groups of 4: each device holds 1/2, not 1/8
        w_h = engine.state.params["layers"]["w_in"]
        w_f = full.state.params["layers"]["w_in"]
        assert (w_h.addressable_shards[0].data.size
                == 4 * w_f.addressable_shards[0].data.size)

    def test_explicit_mesh_zero_axis(self):
        """MiCS style: user sets mesh.zero directly."""
        engine = build_engine(
            mesh={"data": 4, "zero": 2},
            zero_optimization={"stage": 3, "param_persistence_threshold": 64})
        spec = str(engine.state.params["layers"]["w_in"].sharding.spec)
        assert "zero" in spec and "data" not in spec

    def test_hpz_indivisible_raises(self):
        with pytest.raises(ValueError, match="divisible"):
            build_engine(mesh={"data": 3},
                         zero_optimization={"stage": 3,
                                            "zero_hpz_partition_size": 2})


class TestQwZ:
    """zero_quantized_weights: int8 weight gather, convergence parity."""

    @pytest.fixture(scope="class")
    def qwz(self):
        return trained(
            8, bf16={"enabled": True},
            zero_optimization={"stage": 3, "param_persistence_threshold": 64,
                               "zero_quantized_weights": True})

    @pytest.fixture(scope="class")
    def qgz(self):
        return trained(8, zero_optimization={
            "stage": 2, "zero_quantized_gradients": True})

    def test_qwz_converges_with_parity(self, qwz):
        _, lb = trained(
            8, bf16={"enabled": True},
            zero_optimization={"stage": 3, "param_persistence_threshold": 64})
        lq = qwz[1]
        assert lq[-1] < lq[0]  # training works
        # ≤1% loss delta over the run (the ZeRO++ convergence-parity bar)
        for a, b in zip(lb, lq):
            assert abs(a - b) / a < 0.01, (lb, lq)

    def test_qwz_with_hpz(self):
        batches = data(6)
        engine = build_engine(
            bf16={"enabled": True},
            zero_optimization={"stage": 3, "param_persistence_threshold": 64,
                               "zero_quantized_weights": True,
                               "zero_hpz_partition_size": 2})
        ls = losses(engine, batches)
        assert ls[-1] < ls[0]

    def test_qwz_reduces_allgather_bytes(self, qwz):
        """What crosses the gather boundary: with qwZ every zero-sharded
        weight reaches its gathered layout as int8 codes (1 byte an
        element, plus one f32 scale per slice of the sharded dim) where
        the plain step gathers the bf16 copy (2 bytes). Read from the
        LOWERED step: the CPU backend gathers the codes as f32, and a
        static count of compiled collectives attributes a loop body's
        gather once however often it runs, so the compiled bytes of two
        differently-looped programs do not compare (this test did
        compare them until PR 24, and passed on the head's double
        count)."""
        import re

        from deepspeed_tpu.runtime.zero import zero_sharded_dims

        engine = qwz[0]
        batch = engine.shard_batch(engine._reshape_gas(data(1)[0]))
        with jax.sharding.set_mesh(engine.mesh):
            text = engine._build_train_step().lower(engine.state, batch).as_text()
        codes = sorted(
            tuple(int(d) for d in m.group(1).split("x"))
            for line in text.splitlines() if "sharding_constraint" in line
            for m in [re.search(r"tensor<([0-9x]+)xi8>\s*$", line)] if m)
        shapes = jax.tree.map(lambda p: tuple(p.shape), engine.state.params)
        dims = zero_sharded_dims(engine.param_specs, engine.tp_specs, shapes,
                                 engine.mesh)
        sharded = [(shp, k) for shp, k in zip(
            jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple)),
            jax.tree.leaves(dims)) if k >= 0]
        assert sharded and codes == sorted(shp for shp, _ in sharded)
        quantized = sum(int(np.prod(shp)) + 4 * shp[k] for shp, k in sharded)
        base = sum(2 * int(np.prod(shp)) for shp, _ in sharded)
        assert quantized < 0.6 * base, (quantized, base)

    def test_qgz_converges_with_parity(self, qgz):
        """zero_quantized_gradients: int8 two-hop grad reduce, ≤1% loss
        delta vs exact reduction (the ZeRO++ qgZ bar)."""
        _, lb = trained(8, zero_optimization={"stage": 2})
        lq = qgz[1]
        assert lq[-1] < lq[0]
        for a, b in zip(lb, lq):
            assert abs(a - b) / a < 0.01, (lb, lq)

    def test_qgz_int8_on_wire(self, qgz):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        recs = parse_hlo_collectives(qgz[0]._train_compiled.as_text())
        assert any(
            r["op"] in ("all-to-all", "all-gather", "collective-permute")
            and ("s8" in r["dtypes"] or "u8" in r["dtypes"])
            for r in recs
        ), recs

    def test_qgz_stage3_raises(self):
        with pytest.raises(NotImplementedError, match="stage"):
            build_engine(zero_optimization={"stage": 3,
                                            "zero_quantized_gradients": True})

    def test_qwz_noop_without_sharded_leaves(self):
        """stage<3 has no zero-sharded params → qwZ is an exact no-op."""
        batches = data(3)
        base = build_engine(zero_optimization={"stage": 1})
        qwz = build_engine(zero_optimization={"stage": 1,
                                              "zero_quantized_weights": True})
        np.testing.assert_allclose(losses(qwz, batches), losses(base, batches),
                                   rtol=1e-6)


class TestQgzCompositions:
    """qgZ x expert / pipeline (r3 VERDICT item 6): the guards are gone;
    the expert reduction happens natively inside the worker shard, the
    pipelined loss runs whole-batch in the worker accumulator."""

    def test_qgz_expert_axis_parity(self):
        """MoE + qgZ (expert=2 x data=2) tracks the UNquantized MoE
    engine within the block-quantization tolerance."""
        mcfg = model_cfg(n_experts=2, moe_top_k=1)
        mk = lambda **z: ds.initialize(
            ds_config(gradient_clipping=0,
                      mesh={"expert": 2, "data": 4},
                      zero_optimization=z or {"stage": 0}),
            loss_fn=T.make_loss_fn(mcfg, loss_chunks=1),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))
        e0 = mk()
        batches = data(8, batch=e0.config.train_batch_size)
        base = losses(e0, batches)
        lq = losses(mk(stage=2, zero_quantized_gradients=True), batches)
        assert all(np.isfinite(l) for l in lq)
        np.testing.assert_allclose(lq, base, rtol=0.02)

    def test_qgz_pipeline_trains(self):
        mcfg = model_cfg(n_layers=4, pipeline_stages=2)
        eng = ds.initialize(
            ds_config(gradient_clipping=0,
                      train_micro_batch_size_per_gpu=1,
                      gradient_accumulation_steps=4,
                      mesh={"pipe": 2, "data": 4},
                      zero_optimization={"stage": 1,
                                         "zero_quantized_gradients": True}),
            loss_fn=T.make_pipelined_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg),
            pipelined=True)
        b = data(1, batch=eng.config.train_batch_size)[0]
        ls = [eng.train_batch(b)["loss"] for _ in range(8)]
        assert all(np.isfinite(l) for l in ls)
        assert min(ls[4:]) < ls[0]
