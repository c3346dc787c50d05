"""Config tests (ref model: tests/unit/runtime test of config parsing +
batch triangle assertions in runtime/config.py)."""

import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.config import DeepSpeedTPUConfig, parse_config


def test_defaults():
    cfg = parse_config({})
    assert cfg.zero_stage == 0
    assert not cfg.bf16.enabled
    assert cfg.gradient_clipping == 0.0


def test_batch_triangle_all_given():
    cfg = parse_config(
        {"train_batch_size": 32, "train_micro_batch_size_per_gpu": 2,
         "gradient_accumulation_steps": 2}
    )
    cfg.resolve_batch_sizes(dp_world_size=8)
    assert cfg.train_batch_size == 32


def test_batch_triangle_derive_gas():
    cfg = parse_config({"train_batch_size": 32, "train_micro_batch_size_per_gpu": 2})
    cfg.resolve_batch_sizes(dp_world_size=8)
    assert cfg.gradient_accumulation_steps == 2


def test_batch_triangle_derive_micro():
    cfg = parse_config({"train_batch_size": 32, "gradient_accumulation_steps": 2})
    cfg.resolve_batch_sizes(dp_world_size=8)
    assert cfg.train_micro_batch_size_per_gpu == 2


def test_batch_triangle_derive_train():
    cfg = parse_config({"train_micro_batch_size_per_gpu": 4})
    cfg.resolve_batch_sizes(dp_world_size=8)
    assert cfg.train_batch_size == 32
    assert cfg.gradient_accumulation_steps == 1


def test_batch_triangle_inconsistent():
    cfg = parse_config(
        {"train_batch_size": 30, "train_micro_batch_size_per_gpu": 2,
         "gradient_accumulation_steps": 2}
    )
    with pytest.raises(ValueError):
        cfg.resolve_batch_sizes(dp_world_size=8)


def test_batch_triangle_nothing_given():
    cfg = parse_config({})
    with pytest.raises(ValueError):
        cfg.resolve_batch_sizes(dp_world_size=8)


def test_precision_exclusive():
    with pytest.raises(Exception):
        parse_config({"bf16": {"enabled": True}, "fp16": {"enabled": True}})


def test_json_file_roundtrip(tmp_path):
    p = tmp_path / "ds_config.json"
    p.write_text(json.dumps({
        "train_micro_batch_size_per_gpu": 1,
        "zero_optimization": {"stage": 3, "param_persistence_threshold": 100},
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 2e-4, "betas": [0.9, 0.95]}},
    }))
    cfg = parse_config(str(p))
    assert cfg.zero_optimization.stage == 3
    assert cfg.zero_optimization.param_persistence_threshold == 100
    assert cfg.optimizer.type == "AdamW"


def test_reference_legacy_keys_tolerated():
    cfg = parse_config({"train_micro_batch_size_per_gpu": 1,
                        "zero_allow_untested_optimizer": True,
                        "communication_data_type": "fp16"})
    assert cfg.train_micro_batch_size_per_gpu == 1


def test_unknown_key_rejected():
    with pytest.raises(Exception):
        parse_config({"train_micro_batch_sized_per_gpu": 1})


def test_mesh_config():
    cfg = parse_config({"train_micro_batch_size_per_gpu": 1,
                        "mesh": {"data": 2, "model": 4}})
    sizes = cfg.mesh.axis_sizes()
    assert sizes["model"] == 4 and sizes["data"] == 2 and sizes["pipe"] == 1


def test_stock_reference_config_parses():
    """ADVICE r1 (medium): a stock reference DeepSpeed JSON must parse,
    with no-op keys warned and dropped."""
    cfg = parse_config({
        "train_batch_size": 8,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 10}},
        "gradient_clipping": 1.0,
        "fp16": {"enabled": True, "auto_cast": False, "hysteresis": 2},
        "zero_optimization": {
            "stage": 3,
            "allgather_partitions": True,
            "allgather_bucket_size": 2e8,
            "overlap_comm": True,
            "reduce_scatter": True,
            "reduce_bucket_size": 2e8,
            "contiguous_gradients": True,
            "stage3_prefetch_bucket_size": 5e7,
            "stage3_param_persistence_threshold": 1e5,
            "stage3_max_live_parameters": 1e9,
            "stage3_max_reuse_distance": 1e9,
            "stage3_gather_16bit_weights_on_model_save": True,
            "sub_group_size": 1e9,
            "round_robin_gradients": True,
        },
        "gradient_predivide_factor": 1.0,
        "wall_clock_breakdown": False,
    })
    assert cfg.zero_optimization.stage == 3
    # renamed reference key lands on our field
    assert cfg.zero_optimization.param_persistence_threshold == 1e5


def test_unimplemented_knobs_raise():
    import pytest as _pytest
    base = {"train_micro_batch_size_per_gpu": 1}
    for extra in (
        {"checkpoint": {"use_node_local_storage": True}},
        {"zero_optimization": {"stage": 3,
                               "zero_quantized_nontrainable_weights": True}},
        {"prescale_gradients": True},
        {"sparse_attention": {"mode": "fixed"}},
        {"data_efficiency": {"enabled": True,
                             "data_routing": {"enabled": True,
                                              "random_ltd": {"enabled": True}}}},
    ):
        with _pytest.raises(NotImplementedError):
            parse_config({**base, **extra})


def test_activation_checkpointing_policy_validated():
    import pytest as _pytest
    with _pytest.raises(Exception):
        parse_config({"train_micro_batch_size_per_gpu": 1,
                      "activation_checkpointing": {"policy": "bogus"}})
    cfg = parse_config({"train_micro_batch_size_per_gpu": 1,
                        "activation_checkpointing": {"policy": "dots"}})
    assert cfg.activation_checkpointing.policy == "dots"


def test_disabled_unimplemented_blocks_parse():
    """Review finding: stock configs carry disabled feature blocks."""
    cfg = parse_config({
        "train_micro_batch_size_per_gpu": 1,
        "autotuning": {"enabled": False},
        "data_efficiency": {"enabled": False},
    })
    assert cfg.train_micro_batch_size_per_gpu == 1
    # data_efficiency is implemented now (runtime/data_analyzer.py):
    # an enabled block parses into the typed config
    cfg2 = parse_config({"train_micro_batch_size_per_gpu": 1,
                         "data_efficiency": {"enabled": True}})
    assert cfg2.data_efficiency.enabled


def test_gradient_predivide_factor_guard():
    cfg = parse_config({"train_micro_batch_size_per_gpu": 1,
                        "gradient_predivide_factor": 1.0})  # no-op value ok
    with pytest.raises(NotImplementedError):
        parse_config({"train_micro_batch_size_per_gpu": 1,
                      "gradient_predivide_factor": 2.0})


# -- the harness's own configuration (tests/conftest.py) ------------------

_COUNT = "--xla_force_host_platform_device_count"
_LEVEL = "--xla_backend_optimization_level"


@pytest.mark.parametrize("lane, named, want", [
    ("cpu", "", [f"{_COUNT}=8", f"{_LEVEL}=1"]),
    ("cpu", f"{_COUNT}=2 {_LEVEL}=3", [f"{_COUNT}=2", f"{_LEVEL}=3"]),
    ("tpu", "", []),
], ids=["cpu-bare", "cpu-both-named", "tpu-bare"])
def test_the_lanes_xla_flags(lane, named, want):
    """The CPU lane names the device count and the backend's level once
    each and keeps what the caller named; DS_TPU_TESTS=1 adds neither.
    Read in a child that takes the lane's decision (importing conftest
    touches no backend, so the hardware lane's needs no chip)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("DS_TPU_TESTS", "JAX_PLATFORMS")}
    env["XLA_FLAGS"] = named
    if lane == "tpu":
        env["DS_TPU_TESTS"] = "1"
    out = subprocess.run(
        [sys.executable, "-c", "import os, conftest; print(os.environ.get("
         "'JAX_PLATFORMS', '-'), os.environ['XLA_FLAGS'])"],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    assert out.returncode == 0, out.stderr
    platforms, *flags = out.stdout.splitlines()[-1].split()
    assert sorted(flags) == sorted(want)
    assert platforms == ("cpu" if lane == "cpu" else "-")
