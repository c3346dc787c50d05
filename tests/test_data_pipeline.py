"""Curriculum learning, random-LTD and Megatron indexed-dataset tests.

Ref model: tests/unit/runtime (curriculum scheduler math) and the
random-LTD invariant: dropped tokens bypass the LTD layers unchanged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.runtime.data_pipeline import (
    CurriculumScheduler,
    RandomLTDScheduler,
    truncate_to_seqlen,
)
from deepspeed_tpu.runtime.indexed_dataset import (
    MMapIndexedDataset,
    MMapIndexedDatasetBuilder,
)

# interpreter-/compile-heavy: excluded from the fast lane (-m 'not slow')
pytestmark = pytest.mark.slow

VOCAB = 128


class TestCurriculumScheduler:
    def test_fixed_linear(self):
        s = CurriculumScheduler({
            "min_difficulty": 8, "max_difficulty": 64,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 100,
                                "difficulty_step": 8},
        })
        assert s.get_difficulty(0) == 8
        assert s.get_difficulty(50) == 32  # 8 + 0.5*56 = 36 → floor to 8-step
        assert s.get_difficulty(100) == 64
        assert s.get_difficulty(10**6) == 64

    def test_fixed_root(self):
        s = CurriculumScheduler({
            "min_difficulty": 8, "max_difficulty": 64,
            "schedule_type": "fixed_root",
            "schedule_config": {"total_curriculum_step": 100,
                                "difficulty_step": 8, "root_degree": 2},
        })
        # sqrt schedule grows faster early than linear
        assert s.get_difficulty(25) >= 8 + (64 - 8) // 4

    def test_fixed_discrete(self):
        s = CurriculumScheduler({
            "min_difficulty": 8, "max_difficulty": 32,
            "schedule_type": "fixed_discrete",
            "schedule_config": {"difficulty": [8, 16, 32],
                                "max_step": [10, 20, 30]},
        })
        assert s.get_difficulty(5) == 8
        assert s.get_difficulty(15) == 16
        assert s.get_difficulty(99) == 32

    def test_custom(self):
        s = CurriculumScheduler({
            "min_difficulty": 1, "max_difficulty": 100,
            "schedule_type": "custom",
        })
        s.set_custom_get_difficulty(lambda step: min(step, 100))
        assert s.update_difficulty(42) == 42

    def test_state_roundtrip(self):
        s = CurriculumScheduler({
            "min_difficulty": 8, "max_difficulty": 64,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 100,
                                "difficulty_step": 8},
        })
        s.update_difficulty(50)
        st = s.get_state()
        s2 = CurriculumScheduler({
            "min_difficulty": 8, "max_difficulty": 64,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 100,
                                "difficulty_step": 8},
        })
        s2.set_state(st)
        assert s2.current == s.current


class TestCurriculumEngine:
    def test_seqlen_curriculum_truncates(self):
        mcfg = T.TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=4,
                                   d_model=64, max_seq=64, variant="llama",
                                   use_flash=False)
        engine = ds.initialize(
            {
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "curriculum_learning": {
                    "enabled": True, "curriculum_type": "seqlen",
                    "min_difficulty": 8, "max_difficulty": 32,
                    "schedule_type": "fixed_discrete",
                    "schedule_config": {"difficulty": [8, 32],
                                        "max_step": [2, 4]},
                },
                "steps_per_print": 1000,
            },
            loss_fn=T.make_loss_fn(mcfg, loss_chunks=1),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg),
        )
        r = np.random.default_rng(0)
        batch = {"tokens": r.integers(0, VOCAB, (16, 65)).astype(np.int32)}
        for _ in range(4):
            assert np.isfinite(engine.train_batch(batch)["loss"])
        # two difficulty levels → two compiled programs
        assert len(engine._train_compiled_cache) == 2


class TestRandomLTD:
    def test_dropped_tokens_bypass_ltd_layers(self):
        """With zeroed LTD-layer weights, kept tokens change only via the
        residual path; dropped tokens must be EXACTLY unchanged."""
        cfg = T.TransformerConfig(vocab_size=VOCAB, n_layers=4, n_heads=4,
                                  d_model=64, max_seq=32, variant="llama",
                                  use_flash=False,
                                  random_ltd_layer_range=(1, 3))
        params = T.init(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, VOCAB)
        idx = jnp.stack([jnp.array([0, 2, 5, 9, 12, 15]),
                         jnp.array([1, 3, 4, 8, 10, 14])]).astype(jnp.int32)

        full = T.forward_hidden(params, toks, cfg)
        ltd = T.forward_hidden(params, toks, cfg, ltd_idx=idx)
        assert ltd.shape == full.shape
        assert not np.allclose(np.asarray(ltd), np.asarray(full))

        # zero the LTD layers' output projections → LTD segment is a no-op
        z = jax.tree.map(lambda x: x, params)
        for name in ("wo", "w_out"):
            z["layers"][name] = z["layers"][name].at[1:3].set(0.0)
        a = T.forward_hidden(z, toks, cfg, ltd_idx=idx)
        b = T.forward_hidden(z, toks, cfg)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_scheduler_and_training(self):
        cfg = T.TransformerConfig(vocab_size=VOCAB, n_layers=4, n_heads=4,
                                  d_model=64, max_seq=32, variant="llama",
                                  use_flash=False,
                                  random_ltd_layer_range=(1, 3))
        engine = ds.initialize(
            {"train_micro_batch_size_per_gpu": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "steps_per_print": 1000},
            loss_fn=T.make_loss_fn(cfg, loss_chunks=1),
            param_init_fn=lambda k: T.init(cfg, k),
            param_logical_specs=T.logical_specs(cfg),
        )
        sched = RandomLTDScheduler(min_tokens=16, max_tokens=32,
                                   total_steps=4, step_size=16)
        r = np.random.default_rng(0)
        for step in range(4):
            batch = {"tokens": r.integers(0, VOCAB, (16, 33)).astype(np.int32)}
            batch = sched.apply(batch, step)
            if step < 2:
                assert batch["random_ltd"].shape == (16, 16)
            loss = engine.train_batch(batch)["loss"]
            assert np.isfinite(loss)

    def test_truncate_to_seqlen(self):
        b = truncate_to_seqlen({"tokens": np.zeros((4, 65), np.int32)}, 16)
        assert b["tokens"].shape == (4, 17)


class TestProgressiveLayerDrop:
    """PLD (ref: runtime/progressive_layer_drop.py, arXiv 2010.13369)."""

    def _build(self, **cfg_kw):
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import transformer as T

        mcfg = T.TransformerConfig(vocab_size=128, n_layers=4, n_heads=4,
                                   d_model=64, max_seq=32, variant="llama",
                                   use_flash=False)
        cfg = {"train_micro_batch_size_per_gpu": 2,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
               "seed": 7, "steps_per_print": 1000}
        cfg.update(cfg_kw)
        return ds.initialize(cfg, loss_fn=T.make_loss_fn(mcfg),
                             param_init_fn=lambda k: T.init(mcfg, k),
                             param_logical_specs=T.logical_specs(mcfg))

    def _data(self, n=6):
        r = np.random.default_rng(0)
        return [{"tokens": r.integers(0, 128, (16, 33)).astype(np.int32)}
                for _ in range(n)]

    def test_gamma_zero_keeps_every_layer(self):
        """Behavioral check of the engine's theta schedule: with gamma=0,
        theta(t) = (1-θ)·e^0 + θ = 1 forever — keep prob 1 for every
        layer, so the PLD engine's trajectory must EQUAL the dense
        engine's. A sign/argument regression in the schedule breaks
        this."""
        batches = self._data(4)
        dense = self._build()
        pld = self._build(progressive_layer_drop={
            "enabled": True, "theta": 0.5, "gamma": 0.0})
        ld = [dense.train_batch(b)["loss"] for b in batches]
        lp = [pld.train_batch(b)["loss"] for b in batches]
        np.testing.assert_allclose(lp, ld, rtol=1e-6)

    def test_pld_trains_and_differs_from_dense(self):
        batches = self._data()
        dense = self._build()
        pld = self._build(progressive_layer_drop={
            "enabled": True, "theta": 0.3, "gamma": 1.0})  # fast decay
        ld = [dense.train_batch(b)["loss"] for b in batches]
        lp = [pld.train_batch(b)["loss"] for b in batches]
        assert all(np.isfinite(l) for l in lp)
        assert lp[-1] < lp[0]  # still converges with dropped layers
        # after theta decays, layers ARE being dropped -> trajectories split
        assert any(abs(a - b) > 1e-6 for a, b in zip(ld[1:], lp[1:]))

    def test_eval_keeps_all_layers(self):
        """rng=None in eval disables PLD — eval losses are deterministic
        and equal a dense engine's eval at identical params."""
        pld = self._build(progressive_layer_drop={
            "enabled": True, "theta": 0.3, "gamma": 1.0})
        dense = self._build()
        b = self._data(1)[0]
        assert pld.eval_batch(b) == pld.eval_batch(b)
        np.testing.assert_allclose(pld.eval_batch(b), dense.eval_batch(b),
                                   rtol=1e-6)

    def test_pld_incompatible_paths_raise(self):
        import pytest as _pytest

        with _pytest.raises(NotImplementedError, match="progressive"):
            self._build(progressive_layer_drop={"enabled": True},
                        optimizer={"type": "OneBitAdam",
                                   "params": {"lr": 1e-3, "freeze_step": 5}})


class TestIndexedDataset:
    def test_build_read_roundtrip(self, tmp_path):
        prefix = str(tmp_path / "corpus")
        b = MMapIndexedDatasetBuilder(prefix, dtype=np.int32)
        docs = [np.arange(10), np.arange(5) + 100, np.arange(17) * 3]
        for d in docs:
            b.add_item(d)
            b.end_document()
        b.finalize()

        ds = MMapIndexedDataset(prefix)
        assert len(ds) == 3
        for i, d in enumerate(docs):
            np.testing.assert_array_equal(ds[i], d.astype(np.int32))
        np.testing.assert_array_equal(ds.sizes, [10, 5, 17])
        np.testing.assert_array_equal(ds.doc_idx, [0, 1, 2, 3])
        # partial reads (the sampler's window access pattern)
        np.testing.assert_array_equal(ds.get(2, offset=4, length=3),
                                      (np.arange(17) * 3)[4:7].astype(np.int32))

    def test_uint16_tokens(self, tmp_path):
        """GPT-2-vocab datasets use uint16 (the Megatron convention)."""
        prefix = str(tmp_path / "u16")
        b = MMapIndexedDatasetBuilder(prefix, dtype=np.uint16)
        b.add_item(np.array([1, 2, 50000], np.uint16))
        b.end_document()
        b.finalize()
        ds = MMapIndexedDataset(prefix)
        assert ds.dtype == np.uint16
        np.testing.assert_array_equal(ds[0], [1, 2, 50000])

    def test_bad_magic_raises(self, tmp_path):
        p = tmp_path / "bad.idx"
        p.write_bytes(b"NOTMAGIC0" + b"\x00" * 64)
        (tmp_path / "bad.bin").write_bytes(b"")
        with pytest.raises(ValueError, match="magic"):
            MMapIndexedDataset(str(tmp_path / "bad"))
