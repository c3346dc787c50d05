"""Checkpoint save/load tests (ref model: tests/unit/checkpoint —
zero-sharded save/restore correctness incl. resharding)."""

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T

VOCAB = 64


def build_engine(stage=2, mesh=None, **extra):
    mcfg = T.TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=2, d_model=32,
                               max_seq=16, variant="llama", use_flash=False)
    return ds.initialize(
        {
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage, "param_persistence_threshold": 32},
            "mesh": mesh or {"data": -1},
            "steps_per_print": 1000,
            **extra,
        },
        loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
    )


def batch(seed=0):
    r = np.random.default_rng(seed)
    return {"tokens": r.integers(0, VOCAB, (8, 17)).astype(np.int32)}


@pytest.fixture(scope="module")
def saver():
    """The module's ONE fp32 ZeRO-2 engine that trains and saves, on
    from case to case: a case holds a load to the state at its save."""
    return build_engine()


@pytest.fixture(scope="module")
def loader():
    """The ONE engine of that configuration that loads: a load replaces
    the whole state, whatever the last case left."""
    return build_engine()


def test_save_load_roundtrip(tmp_path, saver, loader):
    e1, e2 = saver, loader
    b = batch()
    for _ in range(3):
        e1.train_batch(b)
    tag = e1.save_checkpoint(str(tmp_path), client_state={"note": "hello"})
    saved_at = e1.global_steps
    loss_before = e1.train_batch(b)["loss"]

    loaded_tag, client = e2.load_checkpoint(str(tmp_path))
    assert loaded_tag == tag
    assert client["note"] == "hello"
    assert e2.global_steps == saved_at
    loss_after = e2.train_batch(b)["loss"]
    np.testing.assert_allclose(loss_after, loss_before, rtol=1e-5)


def test_latest_tag_written(tmp_path, saver):
    e = saver
    e.train_batch(batch())
    e.save_checkpoint(str(tmp_path), tag="mytag")
    assert (tmp_path / "latest").read_text() == "mytag"


def test_cross_precision_load_bf16_to_fp32(tmp_path, loader):
    """bf16 checkpoint (has fp32 master) → fp32 engine (no master): the
    master is the authoritative fp32 copy."""
    e1 = build_engine(bf16={"enabled": True})
    b = batch()
    for _ in range(2):
        e1.train_batch(b)
    e1.save_checkpoint(str(tmp_path))

    e2 = loader  # fp32
    e2.load_checkpoint(str(tmp_path))
    assert e2.state.master is None
    import jax.numpy as jnp

    assert e2.state.params["embed"].dtype == jnp.float32
    m1 = np.asarray(e1.state.master["embed"])
    m2 = np.asarray(e2.state.params["embed"])
    np.testing.assert_allclose(m1, m2, rtol=1e-6)


def test_cross_precision_load_fp32_to_bf16(tmp_path, saver):
    e1 = saver
    b = batch()
    e1.train_batch(b)
    e1.save_checkpoint(str(tmp_path))

    e2 = build_engine(bf16={"enabled": True})
    e2.load_checkpoint(str(tmp_path))
    import jax.numpy as jnp

    assert e2.state.master is not None
    assert e2.state.params["embed"].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(e1.state.params["embed"]), np.asarray(e2.state.master["embed"]), rtol=1e-6
    )


def test_fp16_checkpoint_into_fp32_engine(tmp_path, loader):
    e1 = build_engine(fp16={"enabled": True})
    e1.train_batch(batch())
    e1.save_checkpoint(str(tmp_path))
    e2 = loader
    e2.load_checkpoint(str(tmp_path))
    assert e2.state.loss_scale is None


def test_corrupt_latest_falls_back_to_verified_tag(tmp_path, saver, loader):
    """The crash-consistent resume path the elastic agent rides
    (docs/fault_tolerance.md): an engine whose newest checkpoint is
    corrupt (injected bitrot) must resume from the previous VERIFIED
    tag instead of wedging — engine.load_checkpoint goes through
    CheckpointEngine.resolve_verified_tag."""
    import os

    from deepspeed_tpu.resilience import corrupt_file

    e1 = saver
    b = batch()
    e1.train_batch(b)
    e1.save_checkpoint(str(tmp_path), tag="step1")
    e1.train_batch(b)
    e1.save_checkpoint(str(tmp_path), tag="step2")
    state_dir = tmp_path / "step2" / "state"
    victims = [os.path.join(r, n)
               for r, _, ns in os.walk(state_dir) for n in ns]
    corrupt_file(max(victims, key=os.path.getsize))

    e2 = loader
    tag, _ = e2.load_checkpoint(str(tmp_path))
    assert tag == "step1"
    assert e2.global_steps == e1.global_steps - 1


def test_injected_commit_crash_resumes_from_previous(tmp_path, loader):
    """PR-7 satellite regression: a crash in the async-save commit
    window (state durable, markers unwritten) must leave 'latest' on
    the previous tag and resume from it."""
    from deepspeed_tpu.resilience import (
        CheckpointCrashError, FaultPlan, armed)

    e1 = build_engine(checkpoint={"async_save": True})
    b = batch()
    e1.train_batch(b)
    e1.save_checkpoint(str(tmp_path), tag="step1")
    e1.checkpoint_engine.wait()
    e1.train_batch(b)
    plan = FaultPlan([{"point": "checkpoint.commit", "kind": "raise",
                       "error": "ckpt_crash", "where": {"tag": "step2"}}])
    with armed(plan):
        with pytest.raises(CheckpointCrashError):
            e1.save_checkpoint(str(tmp_path), tag="step2")
            e1.checkpoint_engine.wait()
    assert (tmp_path / "latest").read_text() == "step1"

    tag, _ = loader.load_checkpoint(str(tmp_path))
    assert tag == "step1"


def test_reshard_zero_stage_across_load(tmp_path, saver):
    """Save under ZeRO-2, load under ZeRO-3 with a different layout —
    the universal-checkpoint property (ref: deepspeed/checkpoint
    ds_to_universal.py) is native here because saved arrays are logical."""
    e1 = saver  # stage 2
    b = batch()
    for _ in range(2):
        e1.train_batch(b)
    e1.save_checkpoint(str(tmp_path))
    losses_src = e1.train_batch(b)["loss"]

    e2 = build_engine(stage=3)
    e2.load_checkpoint(str(tmp_path))
    losses_dst = e2.train_batch(b)["loss"]
    np.testing.assert_allclose(losses_dst, losses_src, rtol=1e-4)
