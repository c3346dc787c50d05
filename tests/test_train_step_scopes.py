"""The device scopes of the compiled train step and its collective
manifest (docs/tracing.md "Device scopes of the train step").

Every step builder of runtime/engine.py is compiled at a tiny size on
the CPU lane and its optimized HLO's `op_name`s are read the way an
operator's profile reads them (profiling/latency.py): each scope of
utils/profiler.TRAIN_STEP_SCOPES that applies is there, the optimizer
never sits inside the model, and the share of the program's own
instructions that some scope names stays at or above a recorded
figure. The scopes are metadata: with `jax.named_scope` patched to a
null context (here, in the test: the program has no switch) the
optimized step is the same program label for label. The manifest is
held to `collective_volumes`, to the compiled text, and, compiled for a
described v5e:2x2, to booking a collective inside a fusion to the
fusion's name.
"""

import contextlib
import functools
import gzip
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jcore
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.comm.logger import comms_logger
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.platform.mesh import build_mesh
from deepspeed_tpu.profiling.hlo import (
    collective_manifest,
    collective_volumes,
    manifest_ids,
)
from deepspeed_tpu.profiling.latency import hlo_scope_map, scope_path
from deepspeed_tpu.utils import profiler
from deepspeed_tpu.utils.profiler import (
    EXPERT_BIAS_UPDATE,
    GRAD_CLIP,
    GRAD_REDUCE,
    LAYER_STACK,
    MODEL_SCOPES,
    OPTIMIZER,
    PARAM_CAST,
    TRAIN_STEP_SCOPES,
    ZERO_GATHER,
)

VOCAB = 128
ALL = MODEL_SCOPES + TRAIN_STEP_SCOPES
# (the 1-bit optimizers refuse gradient clipping)
ONEBIT = {"type": "OneBitAdam", "params": {"lr": 1e-3, "freeze_step": 1}}
ZOADAM = {"type": "ZeroOneAdam",
          "params": {"lr": 1e-3, "var_freeze_step": 1,
                     "local_step_scaler": 100, "local_step_clipper": 16}}

# step builder -> (config over the base, mesh, model overrides, the
# scopes of TRAIN_STEP_SCOPES its step must hold, the least share of
# its own instructions some scope names: recorded from this tree less
# two points)
# (a dense model's step keeps no state of its own: `expert_bias_update`
# is a routed model's, tests/test_trinity.py)
DENSE_STEP_SCOPES = set(TRAIN_STEP_SCOPES) - {EXPERT_BIAS_UPDATE}
BUILDERS = {
    "plain": ({}, {}, {},
              {PARAM_CAST, GRAD_CLIP, OPTIMIZER, LAYER_STACK}, 0.95),
    "fp16": ({"bf16": {"enabled": False}, "fp16": {"enabled": True}}, {}, {},
             {PARAM_CAST, GRAD_REDUCE, GRAD_CLIP, OPTIMIZER, LAYER_STACK},
             0.95),
    "gas2": ({"gradient_accumulation_steps": 2}, {}, {},
             {PARAM_CAST, GRAD_REDUCE, GRAD_CLIP, OPTIMIZER, LAYER_STACK},
             0.93),
    "zero1": ({"zero_optimization": {"stage": 1},
               "gradient_accumulation_steps": 2}, {"data": 4}, {},
              DENSE_STEP_SCOPES, 0.94),
    "zero2": ({"zero_optimization": {"stage": 2},
               "gradient_accumulation_steps": 2}, {"data": 4}, {},
              DENSE_STEP_SCOPES, 0.94),
    "zero3": ({"zero_optimization": {"stage": 3},
               "gradient_accumulation_steps": 2}, {"data": 4}, {},
              DENSE_STEP_SCOPES, 0.94),
    "pipelined": ({"gradient_accumulation_steps": 2}, {"pipe": 2, "data": 2},
                  {"pipeline_stages": 2},
                  # bf16 unscales by 1.0: the multiply folds away
                  {PARAM_CAST, GRAD_CLIP, OPTIMIZER, LAYER_STACK}, 0.97),
    "onebit": ({"optimizer": ONEBIT, "gradient_clipping": 0.0,
                "gradient_accumulation_steps": 2}, {"data": 4}, {},
               {PARAM_CAST, GRAD_REDUCE, GRAD_CLIP, OPTIMIZER, LAYER_STACK},
               0.95),
    "zoadam-full": ({"optimizer": ZOADAM, "gradient_clipping": 0.0,
                     "gradient_accumulation_steps": 2},
                    {"data": 4}, {},
                    {PARAM_CAST, GRAD_REDUCE, GRAD_CLIP, OPTIMIZER,
                     LAYER_STACK}, 0.93),
    "zoadam-local": ({"optimizer": ZOADAM, "gradient_clipping": 0.0,
                      "gradient_accumulation_steps": 2},
                     {"data": 4}, {},
                     {PARAM_CAST, GRAD_REDUCE, GRAD_CLIP, OPTIMIZER,
                      LAYER_STACK}, 0.93),
}
# opcodes that compute nothing
TRIVIAL = ("parameter", "param_", "constant", "get-tuple-element", "tuple",
           "bitcast")


def build(name, **extra):
    over, mesh, model, _, _ = BUILDERS[name]
    mcfg = T.TransformerConfig(**dict(
        dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64, max_seq=32,
             variant="llama", use_flash=False), **model))
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "bf16": {"enabled": True}, "gradient_clipping": 1.0,
           "seed": 7, "steps_per_print": 10**9}
    cfg.update(over, **extra)
    pipelined = "pipeline_stages" in model
    n = int(np.prod(list(mesh.values()))) if mesh else 1
    return ds.initialize(
        cfg,
        loss_fn=(T.make_pipelined_loss_fn if pipelined
                 else T.make_loss_fn)(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
        mesh=build_mesh(mesh, devices=jax.devices()[:n]),
        pipelined=pipelined)


def batch_of(engine):
    r = np.random.default_rng(0)
    return {"tokens": r.integers(
        0, VOCAB, (engine.config.train_batch_size, 33)).astype(np.int32)}


def compile_step(name, **extra):
    """(the engine, the optimized text of the step `name` builds)."""
    engine = build(name, **extra)
    batch = batch_of(engine)
    if name.startswith("zoadam"):
        sb = engine.shard_batch(engine._reshape_gas(batch),
                                leading_accum_dim=True)
        with jax.sharding.set_mesh(engine.mesh):
            step = engine._build_zoadam_step(name.split("-")[1])
            return engine, step.lower(engine.state, sb).compile().as_text()
    # 1-bit Adam compiles its compressed-momentum step once the warm-up
    # (freeze_step 1) is over
    for _ in range(2 if name == "onebit" else 1):
        engine.train_batch(batch)
    return engine, engine._train_compiled.as_text()


@functools.lru_cache(maxsize=None)
def compiled(name):
    return compile_step(name)


def own_instructions(text):
    """name -> path of the instructions the program itself wrote that
    compute something: an `op_name` under `jit(...)` (a parameter's is
    its key path, a reduction's region has a bare one)."""
    return {n: p for n, p in hlo_scope_map(text).items()
            if p.startswith("jit(") and not n.startswith(TRIVIAL)}


@pytest.mark.parametrize("name", BUILDERS)
def test_the_step_holds_its_scopes(name):
    _, _, _, want, floor = BUILDERS[name]
    _, text = compiled(name)
    paths = {n: scope_path(p, ALL) for n, p in own_instructions(text).items()}
    found = {s for path in paths.values() for s in path}
    assert want <= found, sorted(want - found)
    # the optimizer is outside the model, and no new scope is an
    # ancestor of the model's six but `layer_stack`
    for n, path in paths.items():
        if OPTIMIZER in path:
            assert not set(path) & set(MODEL_SCOPES), (n, path)
        inside = [s for s in path if s in MODEL_SCOPES]
        if inside:
            before = path[:path.index(inside[0])]
            assert set(before) <= {LAYER_STACK}, (n, path)
    named = sum(1 for path in paths.values() if path)
    assert named / len(paths) >= floor, (named, len(paths))


def canonical(text):
    """The optimized text without what labels it: metadata, the table
    of source locations, and the numbers XLA gives its names (renamed
    in order of appearance)."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = "\n".join(
        line for line in text.splitlines()
        if not re.match(r"^(FileNames|FunctionNames|FileLocations|StackFrames"
                        r"|\d+ [\"{])", line))
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  text)


@pytest.mark.parametrize(
    "name", ["plain", "fp16", "gas2", "zero3", "pipelined", "onebit",
             "zoadam-local"])
def test_the_scopes_are_metadata_alone(name, monkeypatch):
    """Rule (b): the step with every `jax.named_scope` a null context
    (the model's six too) is the same optimized program."""
    _, with_scopes = compiled(name)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, without = compile_step(name)
    assert not any(scope_path(p, ALL)
                   for p in hlo_scope_map(without).values())
    assert "metadata=" in with_scopes
    assert canonical(with_scopes) == canonical(without)


def test_the_program_gained_no_switch():
    """No flag, config key or environment variable turns the scopes or
    the manifest off: the names are in one tuple beside the facility."""
    import inspect

    from deepspeed_tpu.config import config as C
    from deepspeed_tpu.runtime import engine as E

    assert TRAIN_STEP_SCOPES == (PARAM_CAST, GRAD_REDUCE, GRAD_CLIP,
                                 OPTIMIZER, ZERO_GATHER, LAYER_STACK,
                                 EXPERT_BIAS_UPDATE)
    assert not set(TRAIN_STEP_SCOPES) & set(MODEL_SCOPES)
    src = inspect.getsource(E) + inspect.getsource(C)
    for word in ("named_scope", "scopes", "manifest"):
        assert not re.search(rf"(environ|getenv)[^\n]*{word}", src, re.I)
        assert not re.search(rf"^\s+\w*{word}\w*\s*:\s*bool", src,
                             re.I | re.M)


@pytest.mark.parametrize("name", ["plain", "zero1", "zero3", "pipelined",
                                  "onebit"])
def test_the_manifest_is_the_compiled_steps_collectives(name):
    engine, text = compiled(name)
    man = engine.collective_manifest()
    assert man == collective_manifest(text)
    # the totals collective_volumes reads off the same text
    assert man["kinds"] == collective_volumes(engine._train_compiled)
    assert sum(b for _, _, b in man["sites"]) == sum(
        v["bytes"] for v in man["kinds"].values())
    for site, kind, nbytes in man["sites"]:
        assert re.search(rf"%{re.escape(site)} = ", text), site
        assert kind in man["kinds"] and nbytes > 0
    ids = manifest_ids(man)
    assert ids["sites"] == ",".join(f"{n}:{k}:{b}" for n, k, b in man["sites"])
    if name == "plain":
        assert man["sites"] == [] and ids["sites"] == ""
        assert ids["all_gather_n"] == ids["all_reduce_n"] == 0
    else:
        assert ids["all_gather_n"] + ids["all_reduce_n"] > 0
    assert len(ids["sites"]) < 4096


def test_the_manifest_is_a_span_whatever_the_comms_logger_says():
    """`train.compile.collectives`, always kept, a child of
    `train.compile`, with the engine's table as ids; the logger's flag
    only decides whether ITS summary holds the step."""
    assert not comms_logger.enabled
    profiler.clear()
    engine, _ = compile_step("zero2")
    spans = profiler.spans()
    parent = [s for s in spans if s.name == "train.compile"]
    got = [s for s in spans if s.name == "train.compile.collectives"]
    assert len(parent) == len(got) == 1
    assert got[0].parent == parent[0].sid
    assert parent[0].t0_ns <= got[0].t0_ns and got[0].t1_ns <= parent[0].t1_ns
    want = manifest_ids(engine.collective_manifest())
    assert {k: got[0].ids[k] for k in want} == want
    assert want["all_gather_n"] > 0
    assert comms_logger.summary() == {}
    # the engine sets the logger from its config: with the flag on, the
    # same table is also in the logger's summary
    try:
        engine2, _ = compile_step("zero2", comms_logger={"enabled": True})
        kinds = engine2.collective_manifest()["kinds"]
        assert {k: v["count"] for k, v in comms_logger.summary().items()} \
            == {f"{k}@hlo": v["count"] for k, v in kinds.items()}
    finally:
        comms_logger.configure(enabled=False)
        comms_logger.reset()


def test_a_hand_made_fusion_is_booked_to_its_caller():
    text = """HloModule m, is_scheduled=true

%fused_ar (p: f32[64]) -> f32[16] {
  %p = f32[64]{0} parameter(0)
  %all-reduce.9 = f32[64]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %slice.1 = f32[16]{0} slice(%all-reduce.9), slice={[0:16]}
}

%body (t: (f32[64])) -> (f32[64]) {
  %t = (f32[64]{0}) parameter(0)
  %g = f32[64]{0} get-tuple-element(%t), index=0
  %all-gather.3 = f32[256]{0} all-gather(%g), dimensions={0}
  %fusion.7 = f32[16]{0} fusion(%g), kind=kCustom, calls=%fused_ar
  ROOT %r = (f32[64]{0}) tuple(%g)
}

ENTRY %main (a: f32[64]) -> f32[64] {
  %a = f32[64]{0} parameter(0)
  %w = (f32[64]{0}) while(%a), condition=%cond, body=%body
  ROOT %o = f32[64]{0} get-tuple-element(%w), index=0
}
"""
    man = collective_manifest(text)
    assert sorted(man["sites"]) == [("all-gather.3", "all-gather", 1024),
                                    ("fusion.7", "all-reduce", 256)]
    assert man["in_fusion"] == {"count": 1, "bytes": 256}
    assert man["kinds"]["all-reduce"] == {"count": 1, "bytes": 256}


# -- AOT for a described v5e:2x2 ---------------------------------------------

@pytest.fixture(scope="module")
def v5e_mesh():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    return jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("data",))


def test_on_the_chip_a_gradients_all_reduce_sits_in_a_fusion(v5e_mesh):
    """What no scope can catch: the gradient of a weight gathered from
    its ZeRO shards is all-reduced whole and sliced, and the TPU
    compiler fuses the two into one `fusion.<n>` (an
    `all-reduce-scatter` computation) whose own name is no
    collective's. The manifest books the bytes to that name."""
    mesh = v5e_mesh
    shard, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    w = jax.ShapeDtypeStruct((4096, 1024), jnp.bfloat16, sharding=shard)
    x = jax.ShapeDtypeStruct((64, 4096), jnp.bfloat16, sharding=shard)

    def step(w, x):
        def loss(w):
            with jax.named_scope(ZERO_GATHER):
                full = jax.lax.with_sharding_constraint(w, rep)
            return jnp.sum(jnp.square((x @ full).astype(jnp.float32)))

        g = jax.grad(loss)(w)
        with jax.named_scope(GRAD_REDUCE):
            return jax.lax.with_sharding_constraint(g, shard)

    text = jax.jit(step).lower(w, x).compile().as_text()
    man = collective_manifest(text)
    gathers = [s for s in man["sites"] if s[1] == "all-gather"]
    assert gathers and all(s[0].startswith("all-gather") for s in gathers)
    fused = [s for s in man["sites"]
             if s[1] in ("all-reduce", "reduce-scatter")
             and not s[0].startswith(s[1])]
    assert fused and all(s[0].startswith("fusion") for s in fused), man
    assert man["in_fusion"]["count"] == len(fused)
    assert man["in_fusion"]["bytes"] == sum(s[2] for s in fused)
    # the fusion's instruction is in the text under that very name, and
    # it calls the computation that holds the collective
    for name, _, _ in fused:
        assert re.search(rf"%{re.escape(name)} = [^\n]* fusion\([^\n]*calls=",
                         text)


def test_a_hand_made_async_chain_is_booked_once_and_flagged():
    """The TPU compiler's async collective fusion: one all-gather as a
    chain of `chain_id` links inside `async-collective-start`, a
    compute fusion and `async-collective-done`. The manifest counts it
    once, at its start, as asynchronous; a `-start` / `-done` pair is
    asynchronous too; a gather the compiler turned back (it keeps the
    name it had, `async_collective_name`) and a plain one are not."""
    text = """HloModule m, is_scheduled=true

%fused_computation.1 (p: bf16[64,16]) -> bf16[64,64] {
  %p = bf16[64,16]{1,0} parameter(0)
  ROOT %all-gather.10 = bf16[64,64]{1,0} all-gather(%p), channel_id=14, replica_groups=[1,4]<=[4], dimensions={1}, frontend_attributes={chain_id="0"}
}

%async_collective_fusion.2 (p: bf16[64,16], x: bf16[8,64]) -> bf16[8,64] {
  %p = bf16[64,16]{1,0} parameter(0)
  %x = bf16[8,64]{1,0} parameter(1)
  %all-gather.11 = bf16[64,64]{1,0} all-gather(%p), channel_id=14, replica_groups=[1,4]<=[4], dimensions={1}, frontend_attributes={chain_id="0"}
  ROOT %convolution.1 = bf16[8,64]{1,0} convolution(%x, %x), dim_labels=bf_io->bf
}

%fused_computation.3 (p: bf16[64,16]) -> bf16[64,64] {
  %p = bf16[64,16]{1,0} parameter(0)
  ROOT %all-gather.12 = bf16[64,64]{1,0} all-gather(%p), channel_id=14, replica_groups=[1,4]<=[4], dimensions={1}, frontend_attributes={chain_id="0"}
}

%body (t: (bf16[64,16], bf16[8,64])) -> (bf16[64,16], bf16[8,64]) {
  %t = (bf16[64,16]{1,0}, bf16[8,64]{1,0}) parameter(0)
  %w = bf16[64,16]{1,0} get-tuple-element(%t), index=0
  %x = bf16[8,64]{1,0} get-tuple-element(%t), index=1
  %all-gather.20 = bf16[64]{0} all-gather(%x), channel_id=3, replica_groups=[1,4]<=[4], dimensions={0}, frontend_attributes={async_collective_name="all-gather-start.1"}
  %async-collective-start = bf16[64,64]{1,0} fusion(%w), kind=kCustom, calls=%fused_computation.1
  %fusion.2 = bf16[8,64]{1,0} fusion(%w, %x), kind=kOutput, calls=%async_collective_fusion.2
  %async-collective-done = bf16[64,64]{1,0} fusion(%w), kind=kCustom, calls=%fused_computation.3
  %collective-permute-start.4 = (bf16[8,64]{1,0}, bf16[8,64]{1,0}) collective-permute-start(%x), channel_id=5, source_target_pairs={{0,1},{1,0}}
  %collective-permute-done.4 = bf16[8,64]{1,0} collective-permute-done(%collective-permute-start.4)
  ROOT %r = (bf16[64,16]{1,0}, bf16[8,64]{1,0}) tuple(%w, %fusion.2)
}

ENTRY %main (a: (bf16[64,16], bf16[8,64])) -> (bf16[64,16], bf16[8,64]) {
  %a = (bf16[64,16]{1,0}, bf16[8,64]{1,0}) parameter(0)
  ROOT %w = (bf16[64,16]{1,0}, bf16[8,64]{1,0}) while(%a), condition=%cond, body=%body
}
"""
    man = collective_manifest(text)
    assert sorted(man["sites"]) == [
        ("all-gather.20", "all-gather", 128),
        ("async-collective-start", "all-gather", 8192),
        ("collective-permute-start.4", "collective-permute", 1024)]
    assert man["kinds"]["all-gather"] == {"count": 2, "bytes": 8320}
    assert man["async"] == {"all-gather": 1, "collective-permute": 1}
    assert man["in_fusion"] == {"count": 1, "bytes": 8192}
    ids = manifest_ids(man)
    assert (ids["all_gather_n"], ids["all_gather_async_n"]) == (2, 1)
    assert ids["collective_permute_async_n"] == 1
    assert ids["all_reduce_async_n"] == ids["reduce_scatter_async_n"] == 0


# -- ZeRO-3's layer gathers, compiled for the chip (docs/overlap.md) ---------

def zero3_layer_stack(mesh, **model):
    """(step, params, tokens): the gradient of the model's own loss
    under a ZeRO-3 overlap plan on `mesh`, with abstract bf16 parameters
    in their store layout: what `forward_hidden`'s layer scan traces
    for the engine, without an engine (a described device holds no
    array)."""
    from deepspeed_tpu.config.config import ZeroConfig
    from deepspeed_tpu.runtime import zero
    from deepspeed_tpu.runtime.overlap import OverlapPlan, overlap_scope

    mcfg = T.TransformerConfig(**dict(
        dict(vocab_size=VOCAB, n_layers=3, n_heads=4, d_model=64, d_ff=160,
             max_seq=32, variant="llama", use_flash=False), **model))
    shapes = jax.eval_shape(lambda k: T.init(mcfg, k), jax.random.PRNGKey(0))
    tp = jax.tree.map(lambda p: P(), shapes)
    store = zero.derive_param_storage_specs(
        tp, jax.tree.map(lambda p: tuple(p.shape), shapes), mesh,
        ZeroConfig(stage=3, param_persistence_threshold=64))
    plan = OverlapPlan(mesh=mesh, layer_store_specs=store["layers"],
                       layer_tp_specs=tp["layers"])
    loss_fn = T.make_loss_fn(mcfg)

    def step(params, tokens):
        with overlap_scope(plan):
            return jax.grad(
                lambda p: loss_fn(p, {"tokens": tokens}, None))(params)

    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16, sharding=NamedSharding(mesh, spec)),
        shapes, store)
    tokens = jax.ShapeDtypeStruct(
        (mesh.shape["data"], mcfg.max_seq + 1), jnp.int32,
        sharding=NamedSharding(mesh, P("data")))
    return step, params, tokens


def _holds_zero_gather(jaxpr):
    return any(
        (e.primitive.name == "sharding_constraint"
         and ZERO_GATHER in str(e.source_info.name_stack))
        or any(_holds_zero_gather(s)
               for s in jcore.jaxprs_in_params(e.params))
        for e in jaxpr.eqns)


def barriers_over_gathers(jaxpr, gathered_in=()):
    """(the `optimization_barrier`s of `jaxpr`, nested ones too, that
    take a GATHERED leaf, how many barriers there are): a gathered leaf
    is what a custom-vjp call holding a `zero_gather` constraint
    returns, followed through converts and reshapes and into the
    jaxprs an equation calls."""
    forward = {"convert_element_type", "reshape", "squeeze", "transpose",
               "copy", "sharding_constraint"}
    gathered = {jaxpr.invars[i] for i in gathered_in}
    bad, n = [], 0
    for e in jaxpr.eqns:
        hit = [i for i, v in enumerate(e.invars)
               if not isinstance(v, jcore.Literal) and v in gathered]
        name = e.primitive.name
        if name == "optimization_barrier":
            n += 1
            bad += [e] if hit else []
        subs = list(jcore.jaxprs_in_params(e.params))
        if name.startswith("custom_vjp_call") and any(
                _holds_zero_gather(s) for s in subs):
            gathered.update(e.outvars)
            continue
        for s in subs:
            off = len(e.invars) - len(s.invars)
            b, m = barriers_over_gathers(
                s, [i - off for i in hit if i >= off])
            bad, n = bad + b, n + m
        if name in forward and hit:
            gathered.update(e.outvars)
    return bad, n


@pytest.mark.parametrize("remat", ["none", "save_attn_qkv"])
def test_no_barrier_stands_over_a_gathered_leaf(remat):
    """An `optimization_barrier`'s outputs exist once ALL its inputs
    do: one that takes a gathered leaf beside the layer's input makes
    the layer wait for the gather to be done (what the carried, pinned
    prefetch did: runtime/overlap.py). The traced step holds none; and
    the walker finds one where one is put."""
    from deepspeed_tpu.runtime.overlap import barrier, make_prefetch_gather

    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])
    step, params, tokens = zero3_layer_stack(mesh, remat=remat)
    with jax.sharding.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(step)(params, tokens).jaxpr
    assert _holds_zero_gather(jaxpr)
    assert barriers_over_gathers(jaxpr) == ([], 0)

    gather = make_prefetch_gather({"w": P(None, "data")}, {"w": P()}, mesh)

    def pinned(w, x):
        g, x = barrier((gather({"w": w})["w"], x))
        return x @ g

    with jax.sharding.set_mesh(mesh):
        seeded = jax.make_jaxpr(pinned)(jnp.ones((8, 8)), jnp.ones((2, 8)))
    bad, n = barriers_over_gathers(seeded.jaxpr)
    assert (len(bad), n) == (1, 1)


def test_on_the_chip_the_layer_gathers_run_beside_the_matmuls(v5e_mesh):
    """(b) The model's ZeRO-3 layer stack at Mistral-7B's widths (4096
    x 14336, 4096 tokens a chip), compiled for a described v5e:2x2:
    every MLP gather of the two loop bodies is an
    `async-collective-start` .. `-done` chain with a matmul fusion
    between the two, and the manifest says so."""
    from deepspeed_tpu.profiling.hlo import parse_hlo_computations

    mesh = build_mesh({"data": 4}, devices=list(v5e_mesh.devices.flat))
    step, params, tokens = zero3_layer_stack(
        mesh, n_layers=4, n_heads=32, n_kv_heads=8, d_model=4096,
        d_ff=14336, max_seq=4096, remat="save_attn_qkv")
    with jax.sharding.set_mesh(mesh):
        text = jax.jit(step).lower(params, tokens).compile().as_text()
    comps, _ = parse_hlo_computations(text)
    mlp_bytes = 4096 * 14336 * 2

    def inside(ins, op):
        """The instructions of kind `op` in what `ins` calls."""
        return [j for c in ins["called"] for j in comps.get(c, ())
                if j["op"] == op]

    bodies = {c for body in comps.values() for ins in body
              if ins["op"] == "while" for c in ins["called"]}
    chains = 0
    for body in (comps[c] for c in bodies):
        # a synchronous MLP gather would stand in the body under its own name
        assert not [i for i in body if i["op"] == "all-gather"
                    and i["nbytes"] == mlp_bytes]
        starts = [(pos, ins) for pos, ins in enumerate(body)
                  if ins["name"].startswith("async-collective-start")
                  and any(g["nbytes"] == mlp_bytes
                          for g in inside(ins, "all-gather"))]
        for pos, start in starts:
            suffix = start["name"][len("async-collective-start"):]
            done = next(p for p, ins in enumerate(body)
                        if ins["name"] == "async-collective-done" + suffix)
            assert any(inside(ins, "convolution")
                       for ins in body[pos + 1:done]), start["name"]
            chains += 1
    # w_in, w_gate, w_out: forward, and again in the backward body
    assert chains == 6, chains
    man = collective_manifest(text)
    ids = manifest_ids(man)
    assert ids["all_gather_async_n"] == man["async"]["all-gather"] >= chains
    assert ids["all_gather_n"] > ids["all_gather_async_n"]
    started = [s for s in man["sites"] if s[1] == "all-gather"
               and s[0].startswith("async-collective-start")]
    assert len(started) == man["async"]["all-gather"]
    # a chain's links are one gather: 117.4 MB, not three times that
    assert sum(1 for s in started if s[2] == mlp_bytes) == chains


# -- the operator's reader, the one clock, the docs --------------------------

def test_the_measured_profile_reads_the_same_names(tmp_path, capsys):
    """profiling/latency.py on the CPU lane: the train step's rows are
    there with time in them, a nested scope is reported under its
    parent, `coverage` is the share inside ANY scope (above what the
    model's scopes alone cover on the same trace), and the manifest's
    kinds follow with their bytes and rate."""
    from deepspeed_tpu.profiling import latency

    engine = build("zero1")
    batch = batch_of(engine)
    trace_dir = str(tmp_path / "tr")
    m = latency.measure_module_latency(engine, batch, trace_dir, steps=2)
    rows = {b for b in m["fwd"] if m["fwd"][b] + m["bwd"].get(b, 0.0) > 0}
    assert {"attention", "mlp", OPTIMIZER, GRAD_CLIP} <= rows, rows
    assert LAYER_STACK in rows  # the scan's own slicing, nothing inside it
    assert any(" > " in b for b in rows), rows
    assert not any(b.startswith(LAYER_STACK + " > ") for b in rows), rows
    parts = sum(m["fwd"].values()) + sum(m["bwd"].values()) + m["other"]
    np.testing.assert_allclose(parts, m["total"], rtol=1e-6)
    old = latency.attribute_trace(
        trace_dir, engine._train_compiled.as_text(),
        buckets=("attention", "mlp", "norm1", "norm2", "embed", "lm_head"),
        steps=2)
    assert m["coverage"] > old["coverage"] + 0.05, (m["coverage"], old["coverage"])
    # How much is inside is held as a COUNT. The share of op TIMES on the
    # CPU follows what XLA's own unnamed converts and copies cost beside
    # the dots, which the backend's level and the neighbours move (0.89-
    # 0.98 at level 3, 0.85-0.92 at level 1, beside five other steps, 30
    # readings each: CHANGES.md PR 66). The executions do not: 7,384 of
    # the 7,632 whose instruction carries a name are inside, 0.967, and
    # without `grad_clip`, `grad_reduce`, `optimizer` 0.948, 0.935, 0.930.
    scope_of = hlo_scope_map(engine._train_compiled.as_text())
    with gzip.open(latency._latest_trace_json(trace_dir)) as f:
        ran = [scope_of.get((e.get("args") or {}).get("hlo_op"))
               for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]
    named = [name for name in ran if name]
    inside = sum(1 for name in named if scope_path(name, ALL))
    assert inside > 0.95 * len(named), (inside, len(named))
    man = engine.collective_manifest()
    assert set(m["collectives"]) == set(man["kinds"])
    gathers = m["collectives"]["all-gather"]
    assert gathers["sites"] == man["kinds"]["all-gather"]["count"]
    assert gathers["bytes"] >= man["kinds"]["all-gather"]["bytes"]
    latency.print_measured_profile(m)
    out = capsys.readouterr().out
    for word in (OPTIMIZER, GRAD_CLIP, "all-gather:", "MB a step", "coverage"):
        assert word in out, word


def test_one_clock_for_a_steps_host_numbers():
    """The log's `samples/s` (ThroughputTimer) and `time: step=`
    (BATCH_TIMER) are booked from the same phase stamps."""
    from deepspeed_tpu.utils.timers import BATCH_TIMER

    engine = build("plain")
    batch = batch_of(engine)
    for _ in range(5):
        engine.train_batch(batch)
    steps = engine.timers(BATCH_TIMER)._record
    assert len(steps) == engine.tput.global_step_count == 5
    skipped = engine.tput.start_step
    assert engine.tput.total_elapsed_time == pytest.approx(
        sum(steps[skipped:]), rel=1e-12)
    assert engine.tput.avg_samples_per_sec == pytest.approx(
        engine.config.train_batch_size * (5 - skipped) / sum(steps[skipped:]))
    assert not hasattr(engine.tput, "start")


def test_a_loss_functions_shape_ids_ride_train_init_shapes():
    """What `models/transformer.make_loss_fn` says of its model
    (`shape_ids`: the flash kernels' tile census) is on the engine's
    always-kept span, beside the ZeRO layout's."""
    from deepspeed_tpu.utils import profiler

    mcfg = T.TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=4,
                               d_model=64, max_seq=32, variant="llama",
                               use_flash=False)
    loss_fn = T.make_loss_fn(mcfg)
    assert loss_fn.shape_ids == {}  # no flash kernel: nothing to say
    loss_fn.shape_ids = {"flash_windows": "0", "flash_tiles_interior": "6"}
    ds.initialize(
        {"train_micro_batch_size_per_gpu": 1, "bf16": {"enabled": True},
         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
        loss_fn=loss_fn, param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
        mesh=build_mesh({}, devices=jax.devices()[:1]))
    ids = [s for s in profiler.spans()
           if s.name == "train.init.shapes"][-1].ids
    assert ids["flash_windows"] == "0" and ids["flash_tiles_interior"] == "6"
    assert "zero_leaves_moved" in ids


def test_the_docs_name_every_scope_and_id():
    import pathlib

    doc = (pathlib.Path(__file__).resolve().parents[1]
           / "docs" / "tracing.md").read_text()
    assert "## Device scopes of the train step" in doc
    for scope in TRAIN_STEP_SCOPES + MODEL_SCOPES:
        assert f"`{scope}`" in doc, scope
    ids = manifest_ids({"kinds": {}, "in_fusion": {"count": 0, "bytes": 0},
                        "sites": [], "async": {}})
    for key in ids:
        assert f"`{key}`" in doc or f"`{key.rsplit('_', 1)[0]}_*`" in doc, key
    for name in ("train.compile.collectives", "engine.collective_manifest()",
                 "ThroughputTimer"):
        assert name in doc, name
