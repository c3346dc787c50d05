"""OLMoE (OlmoeForCausalLM) on the normal path at a tiny size on the CPU,
against the plain float32 reference of benchmarks/reference/olmoe.py:
training forward and eval loss, serving through the paged cache, the
routed block alone, three mutants that must fail, the import round
trip, and the rules this family added (QK-norm over the whole
projected vector, raw top-k weights, the expert path by shape).

Everything is float32 with seeded weights: 4 layers, d 64, 4 heads,
8 experts top-3, norm_topk_prob false.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import pytest
from _family import engines, family  # noqa: F401 - the contract's fixtures

from benchmarks.reference import olmoe as ref
from deepspeed_tpu.inference import (
    ServingScheduler,
    ServingSchedulerConfig,
    init_inference,
)
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.moe.dropless import dropless_topk_gating
from deepspeed_tpu.moe.sharded_moe import topk_gating
from deepspeed_tpu.utils import profiler
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf, import_external

PUBLISHED = F.ROOT / "benchmarks/configs/published/olmoe-1b-7b-0125-instruct.json"
HF = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
      "hidden_size": 64, "intermediate_size": 32,
      "max_position_embeddings": 256, "model_type": "olmoe",
      "norm_topk_prob": False, "num_attention_heads": 4, "num_experts": 8,
      "num_experts_per_tok": 3, "num_hidden_layers": 4,
      "num_key_value_heads": 4, "rms_norm_eps": 1e-05, "rope_scaling": None,
      "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 256}

# float32 on both sides. The system reassociates (fused QKV, the expert
# scan's running sum or the ragged wire's segment-sum, chunked CE),
# which moves a logit of order 1 by ~1e-5 (measured here: 1.2e-5 in
# training, 3e-6 in serving); a router tie flipped by that noise would
# move one by ~0.1, and none is at these seeds. The mutants move them by
# 2.0 (weights renormalised), 3.5 (no QK-norm) and 2.4 (top-2 of 3):
# four orders of magnitude above the limit.
LOGITS_ATOL = 2e-4
LOSS_ATOL = 2e-5
# the routed block alone, outputs up to 0.07: float32 reassociation
# (measured 2e-8); the mutants move them by more than 1e-3
BLOCK_ATOL = 1e-6
# always every expert (the scan; with kernels on, the streamed pass) /
# always the ragged wire, whatever the shape
ALWAYS = {"scan": (0.0, float("inf")), "ragged": (float("inf"),) * 2}
ALWAYS["stream"] = ALWAYS["scan"]
# the four paths on 16-bit stacks, as a share of the block's largest
# output (tests/test_expert_stream.py measures 0.3-0.7% against float32)
BLOCK_RTOL_BF16 = 0.015
ENGINE = dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=32,
              max_batch_size=8, min_prefill_bucket=32)
FAMILY = F.Family(hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE)


@pytest.fixture(scope="module")
def model():
    mcfg = config_from_hf(HF, use_flash=False)
    params = T.init(mcfg, jax.random.PRNGKey(1))
    # spread the logits (the 0.02 init gives nearly flat ones) and make
    # the QK-norm scales matter (T.init gives ones)
    params = jax.tree.map(lambda x: x * 4 if x.ndim > 1 else x, params)
    for i, name in enumerate(("q_norm_scale", "k_norm_scale")):
        shape = params["layers"][name].shape
        params["layers"][name] = 1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(2 + i), shape)
    return mcfg, params


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, HF["vocab_size"], (2, 65))


# -- the configuration ---------------------------------------------------

def test_the_published_config_builds_the_model_with_max_seq_alone():
    hf = {k: v for k, v in json.loads(PUBLISHED.read_text()).items()
          if not k.startswith("_")}
    assert "architectures" not in hf and hf["model_type"] == "olmoe"
    cfg = config_from_hf(hf, max_seq=4096)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads,
            cfg.head_dim, cfg.ff_dim) == (16, 2048, 16, 16, 128, 1024)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.vocab_size) == (64, 8, 50304)
    assert cfg.qk_norm and cfg.moe_norm_topk_prob is False
    assert cfg.moe_dropless and not cfg.tie_embeddings
    assert cfg.sliding_window == 0 and cfg.norm_eps == 1e-5
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["q_norm_scale"].shape == (16, 16, 128)
    assert shapes["layers"]["w_in"].shape == (16, 64, 2048, 1024)
    # the benchmark's own count of what a layer holds, plus the two
    # QK-norm scales config.json does not state
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["layers"]))
    assert n == 16 * (419_565_568 + 2 * 2048)
    same = config_from_hf(dict(hf, architectures=["OlmoeForCausalLM"]),
                          max_seq=4096)
    assert same == cfg


@pytest.mark.parametrize("key,value", [("clip_qkv", 8.0),
                                       ("attention_bias", True)])
def test_an_olmoe_this_tree_cannot_run_is_refused(key, value):
    with pytest.raises(ValueError, match="clip_qkv or attention_bias"):
        config_from_hf(dict(HF, **{key: value}))


def test_qk_scales_are_ones_at_init_and_take_the_head_sharding(model):
    mcfg, _ = model
    fresh = T.init(mcfg, jax.random.PRNGKey(0))["layers"]
    assert np.all(np.asarray(fresh["q_norm_scale"]) == 1)
    assert np.all(np.asarray(fresh["k_norm_scale"]) == 1)
    specs = T.logical_specs(mcfg)["layers"]
    assert specs["q_norm_scale"] == specs["wq"][:1] + specs["wq"][2:]


# -- training ------------------------------------------------------------

def test_training_forward_matches_the_reference(model, tokens):
    mcfg, params = model
    got = np.asarray(T.forward(params, jnp.asarray(tokens[:, :-1]), mcfg))
    want = F.ref_logits(FAMILY, params, tokens[:, :-1])
    assert np.abs(want).max() > 0.5          # the logits are not flat
    assert np.abs(got - want).max() < LOGITS_ATOL


def test_eval_loss_through_ds_initialize_matches_the_reference(model, tokens):
    import deepspeed_tpu as ds

    mcfg, params = model
    # without the load-balance term (0.01 x the layers' l_aux, ~0.06
    # here) the training loss IS the reference's cross-entropy
    loss_fn = T.make_loss_fn(dataclasses.replace(mcfg, moe_aux_loss_coef=0.0),
                             loss_chunks=2)
    eng = ds.initialize(
        {"train_micro_batch_size_per_gpu": 1, "train_batch_size": 8,
         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
         "steps_per_print": 10**9, "mesh": {"data": -1}},
        loss_fn=loss_fn, param_init_fn=lambda k: params,
        param_logical_specs=T.logical_specs(mcfg))
    # one row a device over the conftest's eight: the two rows four times
    batch = np.tile(tokens.astype(np.int32), (4, 1))
    want = ref.loss(F.top(params), F.layer_fn(params), tokens, HF)
    assert abs(float(eng.eval_batch({"tokens": batch})) - want) < LOSS_ATOL
    assert abs(float(loss_fn(params, {"tokens": jnp.asarray(tokens)}, None))
               - want) < LOSS_ATOL


# -- serving -------------------------------------------------------------

@pytest.fixture(scope="module")
def served(engines, tokens):
    """Prefill, a 3-token chunk and two decode steps of both rows
    through the paged cache; the logits each put() returned."""
    eng = engines()
    f, n, k = tokens.astype(np.int32), 50, 3
    got = [eng.put([0, 1], [r[:n - k] for r in f]),
           eng.put([0, 1], [r[n - k:n] for r in f]),
           eng.put([0, 1], [r[n:n + 1] for r in f]),
           eng.put([0, 1], [r[n + 1:n + 2] for r in f])]
    eng.flush(0), eng.flush(1)
    return eng, got, [n - k - 1, n - 1, n, n + 1]


def test_serving_through_the_paged_cache_matches_the_reference(
        model, tokens, served):
    _, params = model
    _, got, pos = served
    want = F.ref_logits(FAMILY, params, tokens)
    for step, p in enumerate(pos):
        assert np.abs(got[step] - want[:, p]).max() < LOGITS_ATOL, step


@pytest.mark.parametrize("mutant", ref.MUTANTS)
def test_a_wrong_model_fails_the_written_tolerance(model, tokens, served,
                                                   mutant):
    """Renormalised weights, no QK-norm, one expert fewer: the system's
    training and serving logits are far outside the limit of each."""
    mcfg, params = model
    _, got, pos = served
    wrong = F.ref_logits(FAMILY, params, tokens, mutant)
    for step, p in enumerate(pos):
        assert np.abs(got[step] - wrong[:, p]).max() > 100 * LOGITS_ATOL
    trained = np.asarray(T.forward(params, jnp.asarray(tokens), mcfg))
    assert np.abs(trained - wrong).max() > 100 * LOGITS_ATOL


def test_warmup_and_the_scheduler_decode_the_references_greedy_tokens(
        model, engines, tokens):
    mcfg, params = model
    eng = engines()
    eng.warmup(widths=[8], footprint=False)
    sched = ServingScheduler(
        eng, ServingSchedulerConfig(max_num_batched_tokens=16,
                                    prefill_chunk=8, warmup=False), seed=0)
    prompts = [tokens[0, :20].astype(np.int32), tokens[1, :13].astype(np.int32)]
    rids = [sched.submit(p, max_new_tokens=4) for p in prompts]
    sched.run()
    assert sched.counters["moe_token_expert_pairs"] == \
        sched.counters["batched_tokens"] * HF["num_experts_per_tok"] > 0
    for rid, p in zip(rids, prompts):
        out = sched.finished[rid].output
        seq = np.concatenate([p, out]).astype(np.int32)
        want = F.ref_logits(FAMILY, params, seq[None])[0]
        assert out == [int(want[len(p) - 1 + j].argmax()) for j in range(4)]


def test_tensor_parallel_serving_norms_over_all_heads(engines, tokens, served):
    """tp=2 splits the heads; the QK-norm statistic still spans all of
    them (a per-shard norm would move the logits by order 1)."""
    _, got, _ = served
    eng = engines(tensor_parallel={"tp_size": 2})
    assert "model" in tuple(
        eng.params["layers"][0]["q_norm_scale"].sharding.spec)
    out = eng.put([0, 1], [r[:47] for r in tokens.astype(np.int32)])
    eng.flush(0), eng.flush(1)
    assert np.abs(out - got[0]).max() < LOGITS_ATOL


def test_qk_norm_inside_a_manual_region_is_refused(model):
    mcfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    mesh = jax.make_mesh((2,), ("model",))
    P = jax.sharding.PartitionSpec
    q = jnp.ones((3, 4, 16))

    def per_shard(q, k, qs, ks):
        return T.qk_norm(q, k, {"q_norm_scale": qs, "k_norm_scale": ks}, mcfg)

    hs = P(None, "model", None)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.shard_map(per_shard, mesh=mesh,
                      in_specs=(hs, hs, P("model"), P("model")),
                      out_specs=(hs, hs))(
            q, q, lp["q_norm_scale"], lp["k_norm_scale"])


# -- the routed block alone ------------------------------------------------

@pytest.mark.parametrize("path", ["scan", "ragged"])
def test_the_routed_block_alone_matches_the_reference(model, monkeypatch, path):
    """So that a wrong weight rule fails by a factor, not by a hair."""
    mcfg, params = model
    monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", ALWAYS[path])
    lw, h = F.block(params, 24)
    assert M.expert_path(24, mcfg) == path
    got = np.asarray(M._mlp(h, lw, mcfg))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routed_mlp(h, lw, HF))
        wrong = {m: np.asarray(ref.routed_mlp(h, lw, HF, m))
                 for m in ("renormalised", "k_minus_1")}
    assert np.abs(want).max() > 0.05
    assert np.abs(got - want).max() < BLOCK_ATOL
    # renormalised weights are 1 / (the chosen experts' mass) times the
    # right ones, the same factor on every expert of a token: the
    # block's output grows by it, here 1.4 to 2.7 times
    ratio = np.linalg.norm(wrong["renormalised"], axis=-1) \
        / np.linalg.norm(want, axis=-1)
    assert ratio.min() > 1.3
    for m, w in wrong.items():
        assert np.abs(got - w).max() > 1000 * BLOCK_ATOL, m


def test_both_expert_paths_agree_and_the_shape_picks_one(
        model, monkeypatch, pallas_interpret):
    mcfg, params = model
    lw, h = F.block(params, 24)
    out = {}
    for path in ("scan", "ragged"):
        monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", ALWAYS[path])
        out[path] = np.asarray(M._mlp(h, lw, mcfg))
    assert np.abs(out["scan"] - out["ragged"]).max() < BLOCK_ATOL
    # the third path takes 16-bit stacks that fill whole lanes: the same
    # family two lanes wide, all three paths on the same bf16 leaves
    wide = dataclasses.replace(mcfg, d_model=128, d_ff=128)
    r = np.random.default_rng(5)
    bf = lambda *shape: jnp.asarray(r.normal(size=shape) * 0.1, jnp.bfloat16)
    lw = {"w_router": jnp.asarray(r.normal(size=(128, 8)), jnp.float32),
          "w_gate": bf(8, 128, 128), "w_in": bf(8, 128, 128),
          "w_out": bf(8, 128, 128)}
    h = bf(24, 128) * 10
    out = {}
    for path, rows in dict(ALWAYS, grouped=ALWAYS["stream"]).items():
        monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", rows)
        monkeypatch.setattr(M, "_STREAM_ROWS_PER_EXPERT", rows)
        monkeypatch.setattr(M, "_STREAM_RIDGE_TOKENS",
                            0 if path == "grouped" else float("inf"))
        kernels = path in ("stream", "grouped")
        assert M.expert_path(24, wide, lw, kernels) == path
        out[path] = np.asarray(
            M._mlp(h, lw, wide, None, kernels).astype(jnp.float32))
    top = np.abs(out["ragged"]).max()
    assert top > 0.1
    for path in ("stream", "scan", "grouped"):
        assert np.abs(out[path] - out["ragged"]).max() < BLOCK_RTOL_BF16 * top
    monkeypatch.undo()
    # T x k / X rows an expert at the published widths, from the shapes
    # alone. Where kernels run: the streamed pass above 1 row an expert
    # and under 128 (past 256 tokens, the chip's ridge, by its grouped
    # entry); where they do not: the scan above 2 and under 128
    pub = config_from_hf({k: v for k, v in json.loads(
        PUBLISHED.read_text()).items() if not k.startswith("_")})
    lp = F.expert_stacks(64, 2048, 1024)
    widths = (8, 16, 32, 128, 256, 512, 1024)
    assert [M.expert_path(t, pub, lp, True) for t in widths] == [
        "ragged", "stream", "stream", "stream", "stream", "grouped", "ragged"]
    assert [M.expert_path(t, pub, lp, True) for t in (257, 768)] == [
        "grouped", "grouped"]
    for by in ([M.expert_path(t, pub, lp, False) for t in widths],
               [M.expert_path(t, pub) for t in widths]):
        assert by == ["ragged", "ragged", "scan", "scan", "scan", "scan",
                      "ragged"]
    # no flag selects it
    assert M.expert_path(128, dataclasses.replace(pub, moe_dropless=False),
                         lp, True) == M.expert_path(128, pub, lp, True)


def test_init_inference_names_the_experts_and_the_path(model):
    mcfg, params = model
    profiler.clear()
    # its own two: the span of a build (and a dense model's)
    eng = init_inference(params, mcfg, dict(ENGINE), dtype=jnp.float32)
    (span,) = [s for s in profiler.spans() if s.name == "init.inference"]
    assert span.ids["n_experts"] == 8 and span.ids["moe_top_k"] == 3
    eng_path = span.ids["moe_expert_path"]
    assert eng_path == M.expert_path(8, mcfg, eng.params["layers"][0],
                                     eng.resolved_impl == "pallas") == "scan"
    # a dense model's span names no experts
    dense = dataclasses.replace(mcfg, n_experts=0)
    profiler.clear()
    init_inference(T.init(dense, jax.random.PRNGKey(1)), dense, dict(ENGINE),
                   dtype=jnp.float32)
    (span,) = [s for s in profiler.spans() if s.name == "init.inference"]
    assert not {"n_experts", "moe_top_k", "moe_expert_path"} & set(span.ids)


# -- the weight rule -------------------------------------------------------

def test_mixtral_still_renormalises_and_olmoe_does_not():
    mixtral = config_from_hf({
        "architectures": ["MixtralForCausalLM"], "vocab_size": 128,
        "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_local_experts": 4, "num_experts_per_tok": 2})
    assert mixtral.moe_norm_topk_prob is None and not mixtral.qk_norm
    assert T.TransformerConfig().moe_norm_topk_prob is None
    logits = jnp.asarray(np.random.default_rng(5).normal(size=(16, 8)))
    p = np.asarray(jax.nn.softmax(logits, -1))
    top3 = np.sort(p, -1)[:, -3:].sum(-1)
    assert top3.max() < 0.95
    for gate in (dropless_topk_gating, _capacity_weights):
        default = np.asarray(gate(logits, 3)[1]).sum(-1)
        raw = np.asarray(gate(logits, 3, renormalize=False)[1]).sum(-1)
        one = np.asarray(gate(logits, 1, renormalize=True)[1]).sum(-1)
        np.testing.assert_allclose(default, 1.0, rtol=1e-6)   # k > 1: GShard
        np.testing.assert_allclose(raw, top3, rtol=1e-6)      # OLMoE
        np.testing.assert_allclose(one, 1.0, rtol=1e-6)
        np.testing.assert_allclose(                           # k = 1: Switch
            np.asarray(gate(logits, 1)[1]).sum(-1), p.max(-1), rtol=1e-6)


def _capacity_weights(logits, top_k, renormalize=None):
    """The capacity path's combine weights per token, with room for
    every token (so nothing drops), in dropless_topk_gating's shape."""
    combine, _, _ = topk_gating(logits, top_k, capacity_factor=float(
        logits.shape[1]), renormalize=renormalize)
    return None, jnp.sum(combine, axis=2)


def test_the_capacity_training_path_honours_the_weight_rule(model, tokens):
    """moe_dropless off, capacity large enough to drop nothing: the
    same logits as the reference (raw weights), not the renormalised."""
    mcfg, params = model
    cap = dataclasses.replace(mcfg, moe_dropless=False,
                              moe_capacity_factor=8.0)
    got = np.asarray(T.forward(params, jnp.asarray(tokens[:, :32]), cap))
    assert np.abs(got - F.ref_logits(FAMILY, params, tokens[:, :32])).max() < LOGITS_ATOL


# -- the import --------------------------------------------------------------

def _as_olmoe_checkpoint(params, path):
    """The tree under the names and layouts HF's OlmoeForCausalLM saves
    (torch Linear: [out, in]; norms flat)."""
    from safetensors.numpy import save_file

    E = HF["hidden_size"]
    t = {"model.embed_tokens.weight": params["embed"],
         "model.norm.weight": params["ln_f_scale"],
         "lm_head.weight": params["lm_head"].T}
    lay = params["layers"]
    for i in range(HF["num_hidden_layers"]):
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = lay["ln1_scale"][i]
        t[p + "post_attention_layernorm.weight"] = lay["ln2_scale"][i]
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
            t[p + f"self_attn.{theirs}.weight"] = lay[ours][i].reshape(E, -1).T
        t[p + "self_attn.o_proj.weight"] = lay["wo"][i].reshape(-1, E).T
        t[p + "self_attn.q_norm.weight"] = lay["q_norm_scale"][i].reshape(-1)
        t[p + "self_attn.k_norm.weight"] = lay["k_norm_scale"][i].reshape(-1)
        t[p + "mlp.gate.weight"] = lay["w_router"][i].T
        for x in range(HF["num_experts"]):
            for ours, theirs in (("w_gate", "gate_proj"), ("w_in", "up_proj"),
                                 ("w_out", "down_proj")):
                t[p + f"mlp.experts.{x}.{theirs}.weight"] = lay[ours][i, x].T
    path.mkdir()
    save_file({k: np.ascontiguousarray(np.asarray(v)) for k, v in t.items()},
              str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps(HF))


def test_a_synthetic_checkpoint_round_trips(model, tmp_path):
    mcfg, params = model
    _as_olmoe_checkpoint(params, tmp_path / "ckpt")
    cfg, back = import_external(str(tmp_path / "ckpt"), use_flash=False)
    assert cfg == mcfg
    flat = dict(jax.tree_util.tree_leaves_with_path(back))
    want = jax.tree_util.tree_leaves_with_path(params)
    assert len(flat) == len(want)
    for path, leaf in want:
        np.testing.assert_array_equal(np.asarray(flat[path]), np.asarray(leaf),
                                      err_msg=jax.tree_util.keystr(path))
