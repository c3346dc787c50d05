"""Sharding-rule + ZeRO spec derivation tests (ref model:
tests/unit/runtime/zero partitioning checks — here specs are the whole
mechanism, so the tests assert the derived PartitionSpecs directly)."""

import json
import math
import pathlib

import jax
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.config.config import ZeroConfig
from deepspeed_tpu.parallel.sharding import (
    logical_to_mesh_spec,
    make_rules,
    tree_logical_to_mesh,
)
from deepspeed_tpu.platform.mesh import build_mesh
from deepspeed_tpu.runtime.zero import (
    derive_optimizer_specs,
    derive_param_storage_specs,
    zero_layout_report,
    zero_shard_spec,
    zero_sharded_dims,
)

def _trains(path):
    from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

    return not config_from_hf(json.loads(path.read_text())).serving_only


# the benchmark's configurations that TRAIN here: one whose training
# forward is refused (`TransformerConfig.serving_only`) has no ZeRO
# layout to pin
BENCH_CONFIGS = sorted(
    p for p in (pathlib.Path(__file__).parent.parent / "benchmarks"
                / "configs").glob("*.json") if _trains(p))


def mesh_dp8():
    return build_mesh({"data": 8})


def mesh_dp4_tp2():
    return build_mesh({"data": 4, "model": 2})


def mesh_of(axes):
    """A mesh over as many of the host devices as the axes need."""
    n = math.prod(axes.values())
    return build_mesh(axes, devices=jax.devices()[:n])


class TestLogicalRules:
    def test_basic_mapping(self):
        rules = make_rules()
        spec = logical_to_mesh_spec(("embed", "mlp"), rules, mesh_dp4_tp2())
        assert spec == P(None, "model")

    def test_size1_axis_dropped(self):
        rules = make_rules()
        spec = logical_to_mesh_spec(("embed", "mlp"), rules, mesh_dp8())
        assert spec == P()  # model axis is size 1 → replicated

    def test_no_duplicate_axis(self):
        rules = make_rules()
        # heads and mlp both map to model; a spec using both must not
        # produce a duplicate mesh axis
        spec = logical_to_mesh_spec(("heads", "mlp"), rules, mesh_dp4_tp2())
        used = [s for s in spec if s is not None]
        assert len(used) == 1

    def test_override(self):
        rules = make_rules({"mlp": None})
        spec = logical_to_mesh_spec(("embed", "mlp"), rules, mesh_dp4_tp2())
        assert spec == P()

    def test_tree(self):
        rules = make_rules()
        tree = {"a": ("embed", "mlp"), "b": ("vocab", "embed")}
        out = tree_logical_to_mesh(tree, rules, mesh_dp4_tp2())
        assert out["a"] == P(None, "model")
        assert out["b"] == P("model")


class TestZeroShardSpec:
    def test_picks_largest_divisible_dim(self):
        spec = zero_shard_spec(P(), (4, 256), mesh_dp8())
        assert spec == P(None, "data")

    def test_respects_existing_tp(self):
        # dim1 is sharded by model(2): local 256/2=128, a ZeRO shard of
        # 32 lanes, a quarter of a lane tile; dim0's shard of 64/4=16
        # rows is whole bf16 tiles, so the tile rule takes dim0 and the
        # model axis stays where it was
        spec = zero_shard_spec(P(None, "model"), (64, 256), mesh_dp4_tp2(), axes=("data",))
        assert spec == P("data", "model")

    def test_stacks_on_tp_dim_when_it_keeps_the_tile(self):
        # the same leaf four times wider: 2048/2/4 = 256 lanes keep the
        # tile and dim1 is the largest, so data stacks on model as before
        spec = zero_shard_spec(P(None, "model"), (64, 2048), mesh_dp4_tp2(), axes=("data",))
        assert spec == P(None, ("model", "data"))

    @pytest.mark.parametrize("spec, shape, axes, want", [
        # the head of Mistral-7B: 32000/4 = 8000 = 62.5 lane tiles
        (P(), (4096, 32000), {"data": 4}, P("data")),
        (P(), (4096, 32000), {"data": 8}, P("data")),
        # its embedding: both dims keep the tile, the largest wins
        (P(), (32000, 4096), {"data": 4}, P("data")),
        # stacked MLP weight: 14336/4 = 28 x 128
        (P(), (8, 4096, 14336), {"data": 4}, P(None, None, "data")),
        # stacked q/k/v: the two tiled dims (32 heads, 128) would break,
        # E is further out and always whole tiles
        (P(), (8, 4096, 32, 128), {"data": 4}, P(None, "data")),
        # Llama-3's head: 128256/4 = 250.5 lane tiles
        (P(), (4096, 128256), {"data": 4}, P("data")),
        # vocabulary on 'model': the ZeRO shard of it would be 4000 wide
        (P(None, "model"), (4096, 32000), {"data": 4, "model": 2},
         P("data", "model")),
        # no candidate keeps the tile: the largest, as before the rule
        (P(), (4, 256), {"data": 8}, P(None, "data")),
        # nothing to shard over: untouched
        (P(None, "model"), (4096, 32000), {"data": 1, "model": 8},
         P(None, "model")),
    ])
    def test_tile_rule(self, spec, shape, axes, want):
        assert zero_shard_spec(spec, shape, mesh_of(axes)) == want

    def test_small_leaf_stays_replicated(self):
        spec = zero_shard_spec(P(), (4,), mesh_dp8(), min_size=100)
        assert spec == P()

    def test_indivisible_stays_replicated(self):
        spec = zero_shard_spec(P(), (3, 5), mesh_dp8())
        assert spec == P()

    def test_noop_on_size1_axis(self):
        mesh = build_mesh({"data": 1, "model": 8})
        assert zero_shard_spec(P(), (256, 256), mesh) == P()


class TestStageDerivation:
    def shapes(self):
        return {"w": (128, 256), "b": (7,)}

    def specs(self):
        return {"w": P(), "b": P()}

    def test_stage0_keeps_specs(self):
        z = ZeroConfig(stage=0)
        out = derive_optimizer_specs(self.specs(), self.shapes(), mesh_dp8(), z)
        assert out == self.specs()

    def test_stage1_shards_opt_only(self):
        z = ZeroConfig(stage=1)
        opt = derive_optimizer_specs(self.specs(), self.shapes(), mesh_dp8(), z)
        par = derive_param_storage_specs(self.specs(), self.shapes(), mesh_dp8(), z)
        # (128, 256) on data=8: dim1's shard is 32 lanes, a quarter of a
        # lane tile; dim0's 16 rows are whole bf16 tiles → dim0
        assert opt["w"] == P("data")
        assert opt["b"] == P()  # 7 elements, indivisible → replicated
        assert par["w"] == P()

    def test_stage3_shards_params(self):
        z = ZeroConfig(stage=3, param_persistence_threshold=1000)
        par = derive_param_storage_specs(self.specs(), self.shapes(), mesh_dp8(), z)
        assert par["w"] == P("data")  # dim0 keeps the tile (see stage 1)
        assert par["b"] == P()  # below persistence threshold


def _bench_model(path):
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

    mcfg = config_from_hf(json.loads(path.read_text()))
    shapes = jax.tree.map(
        lambda a: tuple(a.shape),
        jax.eval_shape(lambda k: T.init(mcfg, k), jax.random.PRNGKey(0)))
    return T.logical_specs(mcfg), shapes


@pytest.mark.parametrize("path", BENCH_CONFIGS, ids=lambda p: p.stem)
class TestBenchmarkLayouts:
    """The rule at the widths the benchmark runs (Mistral-7B; OLMoE at
    8 layers)."""

    # leaves the tile rule moves off their last dim, and their bf16
    # bytes: the head alone (32000 / 8 and 50304 / 8 lanes break the
    # tile), and for OLMoE the two [8, 16, 128] QK-norm scales besides
    # (128 lanes cannot be split: they shard on the layer dim)
    MOVED = {"mistral": (1, 4096 * 32000 * 2),
             "olmoe": (3, 2048 * 50304 * 2 + 2 * 8 * 16 * 128 * 2)}
    # Trinity's cut (PR 55; its own cell trains under ZeRO-1 on ONE chip,
    # where no spec moves): a head of an eighth of the vocabulary,
    # 25,024 / 4 = 6,256 lanes, breaks the tile and moves; over 8 ways
    # (3,128 rows) the embedding moves too. By the data axis's size.
    MOVED_BY_DATA = {"afmoe": {4: (1, 2048 * 25024 * 2),
                               8: (2, 2 * 2048 * 25024 * 2)}}
    # leaves ZeRO shards OFF the tile: Trinity's five [layers, 128]
    # leaves under 1 KiB each (the per-head QK-norm scales of the 4
    # stacked and the 1 dense layer, the 4 layers' expert_bias), whose
    # 128 lanes cannot be split and whose layer dim no axis divides
    OFF_TILE = {"afmoe": (5, (3 * 4 + 2) * 128 * 2)}

    def test_storage_dim_is_optimizer_dim(self, path):
        from deepspeed_tpu.parallel.sharding import pipe3d_specs

        logical, shapes = _bench_model(path)
        for axes in ({"data": 4}, {"data": 8}, {"data": 4, "model": 2}):
            mesh = mesh_of(axes)
            s = pipe3d_specs(logical, shapes, mesh, ZeroConfig(
                stage=3, param_persistence_threshold=100_000))
            store = zero_sharded_dims(s["storage"], s["tp"], shapes, mesh)
            opt = zero_sharded_dims(s["opt"], s["tp"], shapes, mesh)
            both = [(a, b) for a, b in zip(jax.tree.leaves(store),
                                           jax.tree.leaves(opt)) if a >= 0]
            assert both and all(a == b for a, b in both)
            assert s["storage"]["lm_head"] == s["opt"]["lm_head"] == (
                P("data", "model") if "model" in axes else P("data"))
            rep = zero_layout_report(s["tp"], s["opt"], shapes, mesh, 2)
            family = json.loads(path.read_text())["model_type"]
            moved, nbytes = (self.MOVED[family] if family in self.MOVED
                             else self.MOVED_BY_DATA[family][
                                 axes["data"] * axes.get("model", 1)])
            assert rep["zero_leaves_moved"] == moved
            assert rep["zero_bytes_moved"] == nbytes
            assert (rep["zero_leaves_off_tile"],
                    rep["zero_bytes_off_tile"]) == self.OFF_TILE.get(
                        family, (0, 0))

    def test_data1_leaves_every_spec_alone(self, path):
        # with no live ZeRO axis the function returns before any choice,
        # as it did before the rule: all four trees are the TP specs
        from deepspeed_tpu.parallel.sharding import pipe3d_specs

        logical, shapes = _bench_model(path)
        for axes in ({"data": 1}, {"data": 1, "model": 8}):
            mesh = mesh_of(axes)
            tp = tree_logical_to_mesh(logical, make_rules(), mesh, shapes=shapes)
            s = pipe3d_specs(logical, shapes, mesh, ZeroConfig(stage=3))
            assert s["tp"] == s["storage"] == s["opt"] == s["grads"] == tp
            assert not any(zero_layout_report(
                s["tp"], s["opt"], shapes, mesh, 2).values())


class TestTileRuleOnTheMesh:
    """ZeRO-3 on four host devices with a head whose vocabulary shard
    (1088 / 4 = 272 lanes) breaks the lane tile: the head alone moves,
    to dim 0, and nothing about the numbers changes."""

    V, E = 1088, 512

    def engine(self, seed=0, bf16=False):
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import transformer as T

        self.mcfg = T.TransformerConfig(
            vocab_size=self.V, n_layers=1, n_heads=4, d_model=self.E,
            d_ff=2048, max_seq=16, variant="llama", use_flash=False,
            tie_embeddings=False)
        self.loss_fn = T.make_loss_fn(self.mcfg, loss_chunks=2)
        return ds.initialize(
            {"train_micro_batch_size_per_gpu": 1,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 3,
                                   "param_persistence_threshold": 64},
             "bf16": {"enabled": bf16}, "seed": seed,
             "steps_per_print": 10**9},
            loss_fn=self.loss_fn,
            param_init_fn=lambda k: T.init(self.mcfg, k),
            param_logical_specs=T.logical_specs(self.mcfg),
            mesh=mesh_of({"data": 4}))

    def batch(self, n=4):
        import numpy as np

        return {"tokens": np.random.default_rng(0).integers(
            0, self.V, (n, 17)).astype(np.int32)}

    def test_loss_and_grads_equal_the_unsharded_reference(self):
        import jax.numpy as jnp
        import numpy as np

        eng = self.engine()
        assert eng.zero_layout["zero_leaves_moved"] == 1
        assert eng.zero_layout["zero_bytes_moved"] == self.E * self.V * 4
        assert eng.zero_layout["zero_leaves_off_tile"] == 0
        assert eng.param_specs["lm_head"] == eng.opt_specs["lm_head"] \
            == eng.grad_specs["lm_head"] == P("data")
        batch = self.batch()
        sharded = eng.shard_batch(eng._reshape_gas(batch))
        with jax.sharding.set_mesh(eng.mesh):
            grads, loss, _ = eng._build_grad_step()(
                eng.state.params, eng.state.step, sharded)
        assert grads["lm_head"].sharding.spec == P("data")
        # the reference: the same weights whole on one device, the
        # engine's rng for step 0, plain value_and_grad
        one = jax.devices()[0]
        params = jax.device_put(jax.device_get(eng.state.params), one)
        rng = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(eng.config.seed), 0), 0)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: self.loss_fn(p, jax.device_put(batch, one), rng)))(params)
        # tests/test_overlap.py's tolerance
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-6, atol=1e-6)
        for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-6, atol=1e-6)

    def test_compiled_step_gathers_the_head_along_dim0(self):
        import re

        eng = self.engine()
        eng.train_batch(self.batch())
        head = f"[{self.E},{self.V}]"
        dims = [m.group(1) for line in eng._train_compiled.as_text().splitlines()
                if " all-gather" in line and head in line.split(" all-gather")[0]
                for m in [re.search(r"dimensions=\{(\d+)\}", line)] if m]
        assert dims and set(dims) == {"0"}

    def test_checkpoint_from_the_old_layout_loads(self, tmp_path):
        import dataclasses

        import numpy as np
        from jax.sharding import NamedSharding

        a = self.engine(seed=1, bf16=True)  # bf16: an fp32 master too
        a.train_batch(self.batch())
        old = NamedSharding(a.mesh, P(None, "data"))  # the largest-dim choice

        def head_to_old(tree):
            if not isinstance(tree, dict):
                return tree  # the optimizer's step count
            return {**tree, "lm_head": jax.device_put(tree["lm_head"], old)}

        a.state = dataclasses.replace(
            a.state, params=head_to_old(a.state.params),
            master=head_to_old(a.state.master),
            opt={k: head_to_old(v) for k, v in a.state.opt.items()})
        assert a.state.params["lm_head"].sharding.spec == P(None, "data")
        a.save_checkpoint(str(tmp_path))
        b = self.engine(seed=2, bf16=True)
        assert not np.array_equal(np.asarray(b.state.params["lm_head"]),
                                  np.asarray(a.state.params["lm_head"]))
        b.load_checkpoint(str(tmp_path))
        for got, want in zip(jax.tree.leaves(b.state), jax.tree.leaves(a.state)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for tree in (b.state.params, b.state.master, b.state.opt["mu"]):
            assert tree["lm_head"].sharding.spec == P("data")
