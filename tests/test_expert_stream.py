"""The streamed pass over a layer's expert stack
(ops/pallas/expert_stream.py) and the one place that chooses it
(inference/model.py expert_path), on the CPU with the kernel in
interpret mode:

- the pass against the scan it replaces and against a plain float32
  sum over experts, by tokens, top-k and weight rule, on a held share
  and beside a shared expert;
- every input the pass cannot take keeps the scan and the scan's
  numbers;
- the scheduler's `moe_stream_steps` counter and the set-up spans' ids;
- AOT compiles for a DESCRIBED v5e at both routed cells' shapes (no
  chip; the topology is described inside a module-scoped fixture, as
  benchmarks/tests/test_aot_latent.py does).

The pass's second entry ('grouped') and the table of expert_path's
answers by cell are in tests/test_expert_grouped.py, which takes this
file's helpers.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference import (
    ServingScheduler,
    ServingSchedulerConfig,
    init_inference,
)
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import expert_stream as ES
from deepspeed_tpu.utils import profiler

E, F = 128, 256
BF = jnp.bfloat16
# every expert whatever the rows, by one way of streaming or the other
ALWAYS = (0.0, float("inf"))
# bf16 operands against float32 ones, as a share of the block's largest
# output: the pass rounds `inner` once (2^-9 relative) and sums in
# float32; the scan rounds each dot, each product and each of its X
# additions (measured here: 0.28-0.43% and 0.50-0.74%)
BLOCK_RTOL = 0.012


def _cfg(**kw):
    base = dict(vocab_size=128, n_layers=2, n_heads=4, d_model=E, d_ff=F,
                max_seq=128, variant="llama", use_flash=False, n_experts=8,
                moe_top_k=3, moe_norm_topk_prob=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def _layer(cfg, seed=0, dtype=BF, d_ff=None):
    """One routed layer's leaves as serving's prepare() hands them to
    _mlp: the router in float32, the stacks in the serving dtype."""
    r = np.random.default_rng(seed)
    Xh, f = cfg.n_experts_held, d_ff or cfg.d_ff
    w = lambda *s: jnp.asarray(r.normal(size=s) * 0.1, dtype)
    lp = {"w_router": jnp.asarray(r.normal(size=(E, cfg.n_experts)),
                                  jnp.float32),
          "w_gate": w(Xh, E, f), "w_in": w(Xh, E, f), "w_out": w(Xh, f, E)}
    if cfg.n_shared_experts:
        lp.update(ws_gate=w(E, f), ws_in=w(E, f), ws_out=w(f, E))
    return lp


def _tokens(n, seed=3):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, E)), BF)


def _reference(h, lp, cfg):
    """The block in float64, one expert at a time: the chosen experts'
    scores as weights (renormalised or not, scaled), the held share's
    experts alone, the shared expert beside them."""
    f = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    silu = lambda x: x / (1 + np.exp(-x))
    n = f(h)
    logits = np.asarray(h.astype(jnp.float32) @ lp["w_router"], np.float64)
    if cfg.moe_scoring == "sigmoid":
        s = 1 / (1 + np.exp(-logits))
    else:
        s = np.exp(logits - logits.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
    chosen = np.argsort(-s, axis=-1, kind="stable")[:, :cfg.moe_top_k]
    w = np.zeros_like(s)
    np.put_along_axis(w, chosen, np.take_along_axis(s, chosen, -1), -1)
    if cfg.moe_norm_topk_prob:
        w /= w.sum(-1, keepdims=True)
    w *= cfg.routed_scaling_factor
    start, held = cfg.experts_held or (0, cfg.n_experts)
    out = np.zeros_like(n)
    for x in range(held):
        inner = silu(n @ f(lp["w_gate"][x])) * (n @ f(lp["w_in"][x]))
        out += w[:, start + x, None] * (inner @ f(lp["w_out"][x]))
    if cfg.n_shared_experts:
        out += (silu(n @ f(lp["ws_gate"])) * (n @ f(lp["ws_in"]))) \
            @ f(lp["ws_out"])
    return out


def _both(h, lp, cfg, monkeypatch):
    """(the streamed pass, the scan) on the same inputs, as float64."""
    monkeypatch.setattr(M, "_STREAM_ROWS_PER_EXPERT", ALWAYS)
    monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", ALWAYS)
    assert M.expert_path(h.shape[0], cfg, lp, True) == "stream"
    assert M.expert_path(h.shape[0], cfg, lp, False) == "scan"
    f = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    # (each path ONE program, as a step holds it; op by op the layer is
    # fifteen small compiles a case)
    return tuple(f(jax.jit(lambda h, lp, kernels=kernels: M._mlp(
        h, lp, cfg, None, kernels, None))(h, lp)) for kernels in (True, False))


# 13: off the 16-row sublane tile of a 16-bit type
@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("norm", [False, True], ids=["raw", "renormalised"])
@pytest.mark.parametrize("top_k", [1, 3, 8])
@pytest.mark.parametrize("n_tokens", [8, 24, 128, 13])
def test_the_pass_matches_the_scan_and_a_float32_sum(
        monkeypatch, n_tokens, top_k, norm):
    cfg = _cfg(moe_top_k=top_k, moe_norm_topk_prob=norm)
    lp, h = _layer(cfg), _tokens(n_tokens)
    stream, scan = _both(h, lp, cfg, monkeypatch)
    want = _reference(h, lp, cfg)
    top = np.abs(want).max()
    assert top > 0.3
    assert np.abs(stream - want).max() < BLOCK_RTOL * top
    assert np.abs(scan - want).max() < BLOCK_RTOL * top
    # float32 across experts: nearer the sum than the scan is
    assert np.abs(stream - want).max() < np.abs(scan - want).max()


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("n_tokens", [8, 24])
def test_a_held_share_drops_the_pairs_routed_elsewhere(monkeypatch, n_tokens):
    """4 of 16 experts are held: the router chooses among all 16, the
    pass streams the 4, and a held expert no token reached adds
    nothing however large its weights."""
    cfg = _cfg(n_experts=16, moe_top_k=4, moe_scoring="sigmoid",
               moe_norm_topk_prob=True, routed_scaling_factor=2.5,
               experts_held=(4, 4))
    lp, h = _layer(cfg), _tokens(n_tokens)
    # expert 6 (held, the share's third) is never chosen, and is large
    lp["w_router"] = lp["w_router"].at[:, 6].set(0).at[0, 6].set(-50.0)
    h = h.at[:, 0].set(1.0)
    lp["w_out"] = lp["w_out"].at[2].multiply(1e3)
    assert M.expert_path(n_tokens, cfg, lp, True) == "stream"  # by no bound
    stream, scan = _both(h, lp, cfg, monkeypatch)
    want = _reference(h, lp, cfg)
    top = np.abs(want).max()
    assert 0.1 < top < 100
    assert np.abs(stream - want).max() < BLOCK_RTOL * top
    assert np.abs(scan - want).max() < BLOCK_RTOL * top
    # some pairs stayed and some went elsewhere
    elsewhere = dataclasses.replace(cfg, experts_held=(8, 4))
    assert np.abs(_reference(h, lp, elsewhere) - want).max() > 0.1 * top


@pytest.mark.usefixtures("pallas_interpret")
def test_a_shared_expert_runs_beside_the_pass(monkeypatch):
    cfg = _cfg(n_shared_experts=1)
    lp, h = _layer(cfg), _tokens(24)
    stream, scan = _both(h, lp, cfg, monkeypatch)
    want = _reference(h, lp, cfg)
    top = np.abs(want).max()
    assert np.abs(stream - want).max() < BLOCK_RTOL * top
    assert np.abs(scan - want).max() < BLOCK_RTOL * top
    alone = _reference(h, lp, dataclasses.replace(cfg, n_shared_experts=0))
    assert np.abs(alone - want).max() > 0.1 * top


def _refusals():
    gated = _cfg()
    plain = _layer(gated)
    biased_cfg = _cfg(variant="gpt2", gated_mlp=False, mlp_bias=True)
    biased = dict(_layer(biased_cfg), b_in=jnp.ones((8, F), BF) * 0.1,
                  b_out=jnp.ones((8, E), BF) * 0.1)
    biased.pop("w_gate")
    return {
        "int8_stack": (gated, M.quantize_layer(dict(plain), gated), True, None),
        "float32_stack": (gated, _layer(gated, dtype=jnp.float32), True, None),
        "biases": (biased_cfg, biased, True, None),
        "f_off_the_lane_tile": (gated, _layer(gated, d_ff=192), True, None),
        "two_device_mesh": (gated, plain, True, "mesh"),
        "decode_impl_xla": (gated, plain, False, None),
    }


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("what", sorted(_refusals()))
def test_what_the_pass_cannot_take_keeps_the_scan(monkeypatch, what):
    cfg, lp, use_kernel, mesh = _refusals()[what]
    if mesh:
        mesh = jax.make_mesh((2,), ("model",))
    monkeypatch.setattr(M, "_STREAM_ROWS_PER_EXPERT", ALWAYS)
    monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", ALWAYS)
    h = _tokens(24)
    if what == "float32_stack":
        h = h.astype(jnp.float32)
    assert M.expert_path(24, cfg, lp, use_kernel, mesh) == "scan"
    got = np.asarray(M._mlp(h, lp, cfg, None, use_kernel, mesh)
                     .astype(jnp.float32))
    want = np.asarray(M._mlp(h, lp, cfg).astype(jnp.float32))  # the scan
    assert np.abs(want).max() > 0.1
    np.testing.assert_array_equal(got, want)
    # ... and a mesh of one device is no mesh
    if what == "two_device_mesh":
        assert M.expert_path(24, cfg, lp, True,
                             jax.make_mesh((1,), ("model",))) == "stream"


def test_the_tile_comes_from_the_shapes():
    sds = lambda *s: jax.ShapeDtypeStruct(s, BF)
    olmoe = (sds(64, 2048, 1024), sds(64, 2048, 1024), sds(64, 1024, 2048))
    pangu = (sds(8, 7680, 2048), sds(8, 7680, 2048), sds(8, 2048, 7680))
    assert ES.stream_f_tile(128, *olmoe) == 512
    assert ES.stream_f_tile(128, *pangu) == 128
    # tokens whose float32 accumulator and buffers outgrow VMEM
    assert ES.stream_f_tile(1024, *olmoe) == 512
    assert ES.stream_f_tile(8192, *olmoe) is None
    assert ES.stream_f_tile(2320, *pangu) is None
    # stacks that do not belong together
    assert ES.stream_f_tile(128, olmoe[0], olmoe[1], pangu[2]) is None


# -- the counter and the ids ------------------------------------------------

ENGINE = dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
              min_prefill_bucket=8, max_batch_size=8, decode_impl="pallas")


def _served(cfg):
    profiler.clear()
    eng = init_inference(T.init(cfg, jax.random.PRNGKey(0)), cfg,
                         dict(ENGINE), dtype=BF)
    eng.warmup(widths=[8], footprint=False)
    sched = ServingScheduler(
        eng, ServingSchedulerConfig(max_num_batched_tokens=16,
                                    prefill_chunk=4, warmup=False), seed=0)
    rng = np.random.default_rng(0)
    for n in (11, 5):
        sched.submit(rng.integers(0, 128, n).astype(np.int32),
                     max_new_tokens=4)
    sched.run()
    return eng, sched, profiler.spans()


@pytest.mark.usefixtures("pallas_interpret")
def test_every_step_of_a_routed_model_streams_and_says_so(monkeypatch):
    monkeypatch.setattr(M, "_STREAM_ROWS_PER_EXPERT", ALWAYS)
    eng, sched, spans = _served(_cfg(d_ff=128))
    assert eng.resolved_impl == "pallas" and eng.expert_path(8) == "stream"
    assert sched.counters["moe_stream_steps"] == sched.counters["steps"] > 0
    assert sched.counters["moe_grouped_steps"] == 0
    (init,) = [s for s in spans if s.name == "init.inference"]
    assert init.ids["moe_expert_path"] == "stream"
    assert init.ids["n_experts"] == 8 and init.ids["moe_top_k"] == 3
    programs = [s.ids for s in spans if s.name == "warmup.program"]
    decode = [p for p in programs if p["kind"] == "decode"]
    assert decode and all(p["moe_expert_path"] == "stream" for p in decode)
    assert all("moe_expert_path" not in p for p in programs
               if p["kind"] not in ("decode", "fused"))
    assert all("moe_grouped_rows" not in p for p in programs)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("what", ["dense", "by_rows", "xla"])
def test_what_does_not_stream_counts_no_stream_step(what):
    cfg = _cfg(d_ff=128, **({"n_experts": 0} if what == "dense" else {}))
    profiler.clear()
    eng = init_inference(
        T.init(cfg, jax.random.PRNGKey(0)), cfg,
        dict(ENGINE, **({"decode_impl": "xla"} if what == "xla" else {})),
        dtype=BF)
    # 8 rows x top-3 / 8 experts = 3 rows an expert: streams by the
    # measured bounds where kernels run; 1 row an expert does not
    want = {"dense": None, "by_rows": "stream", "xla": "scan"}[what]
    assert eng.expert_path(8) == want
    if what == "by_rows":
        one = dataclasses.replace(cfg, moe_top_k=1)
        assert M.expert_path(8, one, eng.params["layers"][0], True) == "ragged"
    (init,) = [s for s in profiler.spans() if s.name == "init.inference"]
    assert init.ids.get("moe_expert_path") == want
    sched = ServingScheduler(
        eng, ServingSchedulerConfig(max_num_batched_tokens=16,
                                    prefill_chunk=4, warmup=False), seed=0)
    sched.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
    sched.run()
    assert sched.counters["steps"] > 0
    assert sched.counters["moe_stream_steps"] == (
        sched.counters["steps"] if want == "stream" else 0)
    assert sched.counters["moe_grouped_steps"] == 0


# -- AOT for a described v5e ------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
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
    return SingleDeviceSharding(topo.devices[0])


# the 128-row program of serve-olmoe-chat-saturated (64 experts of
# 2048 x 1024) and of serve-pangu-longchat-saturated (8 held experts of
# 7680 x 2048), and the widest program the pass takes at OLMoE's widths
@pytest.mark.parametrize("rows,X,e,f", [
    (128, 64, 2048, 1024), (128, 8, 7680, 2048), (1024, 64, 2048, 1024)])
def test_the_pass_compiles_at_the_cells_shapes(one_chip, rows, X, e, f):
    sds = lambda *s: jax.ShapeDtypeStruct(s, BF, sharding=one_chip)
    args = (sds(rows, e), sds(X, e, f), sds(X, e, f), sds(X, f, e),
            sds(X, rows))
    assert ES.stream_f_tile(rows, *args[1:4]) is not None
    text = jax.jit(ES.expert_stream_mlp).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # one kernel a layer, and no loop over experts around it
    assert len(calls) == 1 and "expert_stream" in calls[0]
    assert " while(" not in text

