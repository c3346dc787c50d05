"""jax.profiler trace capture + divergence-hash + 1-bit LAMB tests."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.runtime.debug import (
    check_cross_host_divergence,
    params_fingerprint,
)
from deepspeed_tpu.utils.profiler import trace

# interpreter-/compile-heavy: excluded from the fast lane (-m 'not slow')
pytestmark = pytest.mark.slow

VOCAB = 128


def build_engine(**cfg_kw):
    mcfg = T.TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=4,
                               d_model=64, max_seq=32, variant="llama",
                               use_flash=False)
    base = {"train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "seed": 7, "steps_per_print": 1000}
    base.update(cfg_kw)
    return ds.initialize(
        base, loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg))


def data(batch=16, seq=33, seed=0):
    r = np.random.default_rng(seed)
    return {"tokens": r.integers(0, VOCAB, (batch, seq)).astype(np.int32)}


class TestProfilerTrace:
    def test_trace_ctx(self, tmp_path):
        with trace(str(tmp_path / "t")):
            jnp.ones((8,)).sum().block_until_ready()
        assert os.path.exists(str(tmp_path / "t"))


class TestDivergenceHash:
    def test_fingerprint_deterministic_and_sensitive(self):
        p = {"a": jnp.arange(16, dtype=jnp.float32),
             "b": jnp.ones((4, 4), jnp.bfloat16)}
        f1 = params_fingerprint(p)
        f2 = params_fingerprint(jax.tree.map(lambda x: x + 0, p))
        np.testing.assert_array_equal(f1, f2)
        p2 = dict(p, a=p["a"].at[3].add(1e-3))
        assert not np.array_equal(params_fingerprint(p2), f1)

    def test_bit_exact_not_just_magnitude(self):
        # |x| identical but signs swapped -> a magnitude hash would pass;
        # the position-weighted bit checksum must differ
        p = {"a": jnp.asarray([1.0, -2.0, 3.0])}
        q = {"a": jnp.asarray([-1.0, 2.0, 3.0])}
        assert not np.array_equal(params_fingerprint(p), params_fingerprint(q))

    def test_fingerprint_compile_cached(self):
        from deepspeed_tpu.runtime import debug as D

        p = {"a": jnp.arange(8, dtype=jnp.float32)}
        before = D._FP._cache_size()
        params_fingerprint(p)
        once = D._FP._cache_size()
        params_fingerprint(jax.tree.map(lambda x: x * 2, p))
        assert D._FP._cache_size() == once >= before  # same signature: no retrace
        # scalar/int leaves tolerated (jit promotes; fp skips dtype-less)
        params_fingerprint({"w": jnp.ones(3), "step": 3})

    def test_single_process_check_passes(self):
        engine = build_engine()
        engine.train_batch(data())
        check_cross_host_divergence(engine.state.params)


class TestOnebitLamb:
    def test_warmup_is_exact_lamb(self):
        mcfg = T.TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=4,
                                   d_model=64, max_seq=32, variant="llama",
                                   use_flash=False)

        def build(opt_type, params):
            return ds.initialize(
                {"train_micro_batch_size_per_gpu": 2,
                 "optimizer": {"type": opt_type, "params": params},
                 "seed": 7, "steps_per_print": 1000},
                loss_fn=T.make_loss_fn(mcfg),
                param_init_fn=lambda k: T.init(mcfg, k),
                param_logical_specs=T.logical_specs(mcfg))

        la = [build("lamb", {"lr": 1e-3}).train_batch(data())["loss"]]
        lo = [build("OneBitLamb", {"lr": 1e-3, "freeze_step": 100}
                    ).train_batch(data())["loss"]]
        np.testing.assert_allclose(lo, la, rtol=1e-5)

    def test_compressed_phase_trains(self):
        engine = build_engine(
            train_micro_batch_size_per_gpu=2,
            gradient_accumulation_steps=1,
            optimizer={"type": "OneBitLamb",
                       "params": {"lr": 1e-3, "freeze_step": 3}})
        batch = data()
        ls = [engine.train_batch(batch)["loss"] for _ in range(10)]
        assert ls[-1] < ls[0]
        assert all(np.isfinite(l) for l in ls)