"""What tests/test_trinity.py and tests/test_trinity_step.py share: the
benchmark's files, the small `afmoe` configurations and seeded trees."""

import pathlib

import jax
import numpy as np
import pytest

from benchmarks import harness
from deepspeed_tpu.models import transformer as T

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks"
ref = harness.load_module(BENCH / "reference" / "afmoe.py")
arith = harness.load_module(BENCH / "kernels" / "afmoe.py")
CUT = harness.load_json(BENCH / "configs/trinity-mini-train-l5-ep8.json")
PUBLISHED = {k: v for k, v in harness.load_json(
    BENCH / "configs/published/trinity-mini.json").items()
    if not k.startswith("_")}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def highest():
    """float32 matmuls at full precision, for a module's comparisons."""
    with jax.default_matmul_precision("highest"):
        yield


def tiny(**over):
    """A dense layer + one whole period, 8 experts top-2 of which this
    chip holds 4 (experts 2..5), a window of 8."""
    hf = {"model_type": "afmoe", "global_attn_every_n_layers": 4,
          "head_dim": 8, "hidden_act": "silu", "hidden_size": 32,
          "intermediate_size": 48, "layer_types": PERIOD + PERIOD[:1],
          "load_balance_coeff": 0.001, "max_position_embeddings": 512,
          "moe_intermediate_size": 16, "mup_enabled": True, "n_group": 1,
          "num_attention_heads": 4, "num_dense_layers": 1,
          "num_expert_groups": 1, "num_experts": 4,
          "num_experts_per_tok": 2, "num_hidden_layers": 5,
          "num_key_value_heads": 2, "num_limited_groups": 1,
          "num_shared_experts": 1, "rms_norm_eps": 1e-5,
          "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
          "route_scale": 2.826, "score_func": "sigmoid",
          "sliding_window": 8, "tie_word_embeddings": False,
          "topk_group": 1, "use_grouped_mm": True, "vocab_size": 128,
          "reduced": {"num_experts": {"published": 8, "here": 4}},
          "experts_held": {"start": 2, "count": 4, "of": 8}}
    hf.update(over)
    return hf


def seeded(mcfg, seed=1):
    """init's tree with norm scales off 1, a bias off 0 and a router
    sharp enough for the choice, the bias and the scale to matter."""
    key = jax.random.PRNGKey(seed + 100)

    def jig(path, x):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, sum(map(ord, name)))
        if "scale" in name:
            return x + 0.1 * jax.random.normal(k, x.shape)
        if "expert_bias" in name:  # init's is 0: a step has moved it
            return 0.02 * jax.random.normal(k, x.shape)
        return x * 20 if "w_router" in name else x

    # ONE program: leaf by leaf it is a hundred small compiles a tree
    return jax.jit(lambda: jax.tree_util.tree_map_with_path(
        jig, T.init(mcfg, jax.random.PRNGKey(seed))))()


def tokens_of(hf, shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, hf["vocab_size"], shape).astype(np.int32)


def tiny3(**over):
    """Three layers that hold every mechanism: a dense windowed layer,
    a full routed one (no positions), a windowed routed one."""
    return tiny(num_hidden_layers=3, global_attn_every_n_layers=2,
                layer_types=["sliding_attention", "full_attention",
                             "sliding_attention"], **over)


# float32 on both sides: the largest read is 2e-6 of a leaf's largest
# gradient (summation order); the mildest control reads 1e-3
LOSS_ATOL, GRAD_RTOL = 2e-5, 5e-5
