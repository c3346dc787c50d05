"""Olmo-Hybrid (`olmo_hybrid`) on the normal serving path at a tiny size
on the CPU, against the plain float32 reference of
benchmarks/reference/olmo_hybrid.py: Gated DeltaNet layers whose heads
are 96 x 192 (two side by side in a lane row of the state slot) and
whose write strength is 2 sigmoid, multi-head attention layers with no
positions under a hidden-wide QK-norm, a dense SwiGLU, every RMSNorm on
its sublayer's OUTPUT; through whole-prompt prefill (the chunked scan),
chunks and single steps (the segmented recurrence), through the
scheduler with slots reused and never cleared; the step kernel at the
published head against the recurrence, both forms at a write strength
up to 2 on near-parallel keys, the controls that must fail, the
importer's refusals, and the cut's file.

Everything is float32 with seeded weights: two periods of (DeltaNet,
DeltaNet, DeltaNet, attention), d 128, 4 heads of 96 x 192, 4 query and
4 KV heads of 32, a SwiGLU of 320.
"""

import json
import logging
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.kernels import olmo_hybrid as shapes
from benchmarks.reference import olmo_hybrid as ref
from benchmarks.tests import helpers
from deepspeed_tpu.inference import (
    ServingScheduler,
    ServingSchedulerConfig,
    init_inference,
)
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import gated_delta as GD
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks"
CUT = BENCH / "configs/olmo-hybrid-7b-serve-l12.json"
PUBLISHED = BENCH / "configs/published/olmo-hybrid-7b.json"
HF = {"model_type": "olmo_hybrid", "vocab_size": 256, "hidden_size": 128,
      "intermediate_size": 320, "num_hidden_layers": 8,
      "num_attention_heads": 4, "num_key_value_heads": 4,
      "hidden_act": "silu", "max_position_embeddings": 512,
      "attention_bias": False, "rms_norm_eps": 1e-06,
      "tie_word_embeddings": False,
      "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
      "linear_num_key_heads": 4, "linear_num_value_heads": 4,
      "linear_key_head_dim": 96, "linear_value_head_dim": 192,
      "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
      "rope_parameters": {"rope_theta": None}}

# float32 on both sides, logits up to 16. The system reassociates (the
# chunked scan's matmuls and triangular solve against the recurrence,
# the one fused projection against the reference's slices, the taps' sum
# in another order, the norms of sublayer OUTPUTS dividing by an rms the
# two sides reach by other sums), which moves a logit by up to 3.5e-4
# (measured here over prefill, a chunk and single steps, at every chunk
# offset). The controls differ by 0.67 (`state_bf16`, the smallest), 8.1
# (`per_head_qk_norm`), 10.3 (`beta_not_doubled`), 11.2
# (`rotary_on_full_layers`), 16.9 (`pre_norm`), 18.9 (`no_state_carry`)
# and 21.3 (`no_decay`): every one at least 440 x the limit, which is
# 4 x the noise.
LOGITS_ATOL = 1.5e-3
ENGINE = dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=48,
              max_batch_size=32, max_tracked_sequences=6,
              min_prefill_bucket=32)


@pytest.fixture(scope="module")
def model():
    mcfg = config_from_hf(HF, use_flash=False)
    params = T.init(mcfg, jax.random.PRNGKey(1))
    # spread the logits (the 0.02 init gives nearly flat ones) and make
    # every norm scale, tap, decay and write strength matter
    params = jax.tree.map(lambda x: x * 4, params)

    def shaped(tree, salt):
        out = {}
        for i, (k, v) in enumerate(tree.items()):
            key = jax.random.fold_in(jax.random.PRNGKey(salt), i)
            if "scale" in k:
                v = 1 + 0.3 * jax.random.normal(key, v.shape)
            elif k == "gdn_taps":
                v = 0.6 * jax.random.normal(key, v.shape)
            elif k in ("gdn_a_log", "gdn_dt_bias"):
                # decays from 0.3 to 0.97 a token: long and short memory
                v = jax.random.uniform(key, v.shape, minval=-3.0, maxval=0.5)
            elif k == "gdn_ba":
                # b up to +-3: write strengths from 0.1 to 1.9
                v = 1.2 * jax.random.normal(key, v.shape)
            out[k] = v
        return out

    top = shaped({k: v for k, v in params.items() if k != "layers"}, 2)
    return mcfg, dict(top, layers=shaped(params["layers"], 3))


def _top(params):
    return {k: v for k, v in params.items() if k != "layers"}


def _layer_fn(params):
    return lambda l: jax.tree.map(lambda a: a[l], params["layers"])


def _ref_logits(params, toks, mutate=None, hf=HF):
    return np.asarray(ref.forward_logits(_top(params), _layer_fn(params),
                                         toks, hf, mutate))


def _engine(model, **over):
    mcfg, params = model
    return init_inference(params, mcfg, dict(ENGINE, **over),
                          dtype=jnp.float32)


@pytest.fixture(scope="module")
def shared_engine(model):
    """One engine for the teacher-forced tests: they flush what they
    put, and share its compiled programs."""
    return _engine(model)


def _feeds(model, eng, lens, splits, n_dec, seed=0):
    """Teacher-forced put() logits of prompts of `lens`, each fed as
    len - sum(splits) tokens whole, then chunks of `splits`, then n_dec
    single tokens: (engine logits [prompts, feeds, V], the reference's
    at the same positions)."""
    rng = np.random.default_rng(seed)
    full = [rng.integers(0, HF["vocab_size"], n + n_dec).astype(np.int32)
            for n in lens]
    uids = list(range(100, 100 + len(lens)))
    cuts = [[n - sum(splits[j:]) for j in range(len(splits) + 1)]
            + [n + j + 1 for j in range(n_dec)] for n in lens]
    got = []
    for j in range(len(cuts[0])):
        toks = [f[(c[j - 1] if j else 0):c[j]] for f, c in zip(full, cuts)]
        got.append(np.asarray(eng.put(uids, toks)))
    for u in uids:
        eng.flush(u)
    padded = np.zeros((len(full), max(map(len, full))), np.int32)
    for i, f in enumerate(full):
        padded[i, :len(f)] = f
    want = _ref_logits(model[1], padded)
    want = np.stack([want[i, np.asarray(c) - 1] for i, c in enumerate(cuts)])
    return np.stack(got, axis=1), want, padded, cuts


# -- the configuration ---------------------------------------------------

def test_the_cut_builds_at_published_widths():
    hf = json.loads(CUT.read_text())
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    assert (cfg.n_layers, cfg.depth, cfg.d_model, cfg.ff_dim) == \
        (12, 12, 3840, 11008)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (30, 30, 128)
    assert not cfg.use_rope and not cfg.use_learned_pos
    assert not any(cfg.rope_at(li) for li in range(12))
    assert cfg.qk_norm and not cfg.qk_norm_per_head
    assert cfg.output_norm and not cfg.sandwich_norm and cfg.gdn_neg_eigval
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.conv_kernel) == (30, 30, 96, 192, 4)
    assert cfg.layer_types == (("linear_attention",) * 3 + ("attention",)) * 3
    assert cfg.n_experts == 0 and cfg.is_gated and cfg.act_name == "silu"
    assert not cfg.tie_embeddings and cfg.vocab_size == 100352
    assert (cfg.n_kv_layers, cfg.n_state_layers) == (3, 9)
    # two heads of 192 side by side: three whole lane tiles a row; the
    # carried inputs' 90 lane rows in 96
    assert cfg.gdn_pack == 2
    assert cfg.state_shapes("linear_attention") == (
        ((15, 96, 384), jnp.float32), ((3, 96, 128), None))
    assert set(cfg.serving_only) >= {"output_norm", "gdn_neg_eigval",
                                     "layer_types", "position_embedding"}
    tree = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert sorted(tree["layers"]) == ["ln1_post_scale", "ln2_post_scale",
                                      "w_gate", "w_in", "w_out"]
    assert tree["layers"]["w_in"].shape == (12, 3840, 11008)
    assert tree["gdn_in"].shape == (9, 3840, 17280)
    assert tree["gdn_ba"].shape == (9, 3840, 60)
    assert tree["gdn_taps"].shape == (9, 11520, 4)
    assert tree["gdn_norm_scale"].shape == (9, 192)
    assert tree["gdn_out"].shape == (9, 5760, 3840)
    assert tree["attn_wq"].shape == tree["attn_wk"].shape == (3, 3840, 30, 128)
    assert tree["attn_q_norm_scale"].shape == (3, 30, 128)
    flat = dict(tree["layers"], **{k: v for k, v in tree.items()
                                   if k != "layers"})
    # the engine's tree against the benchmark's own count, every leaf
    assert sum(int(np.prod(s.shape)) for s in flat.values()) == \
        shapes.parameters(hf) == 3_268_268_508
    # ONE homogeneous stack and top-level ARRAYS: what the benchmark's
    # weight maker and reference_inputs take
    assert all(not isinstance(v, dict) for k, v in tree.items()
               if k != "layers")
    assert all(v.shape[0] == cfg.n_layers for v in tree["layers"].values())
    # the cache: K/V for the attention layers alone, 30 heads held in
    # 32 (whole tiles: what the layout pads them to anyway); two pools a
    # DeltaNet layer, the matrices' with the pad rows' slot
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, 561, 128, jnp.bfloat16, state_slots=128))
    assert [a.shape for a in cache.k] == [(561, 128, 32, 128)] * 3
    assert [tuple((a.shape, a.dtype) for a in pools)
            for pools in cache.state] == [
        (((129, 15, 96, 384), jnp.float32),
         ((128, 3, 96, 128), jnp.bfloat16))] * 9
    # a token NEEDS 46,080 B of K/V over the three layers and holds
    # 32 / 30 of that
    assert 3 * shapes.kv_bytes_per_token_per_layer(hf) == 46_080
    assert sum(a.size * 2 for a in cache.k + cache.v) / 561 / 128 == 49_152
    # what the engine counts before it allocates: 3.53 + 2.65 GB
    pools = E.pool_bytes(cfg, E.InferenceConfig(**hf["serve"]["engine"]),
                         jnp.bfloat16)
    assert pools == {"kv": 561 * 128 * 49_152,
                     "state": 9 * (129 * 2_211_840 + 128 * 73_728)}


def test_the_published_file_builds_the_whole_model():
    hf = {k: v for k, v in json.loads(PUBLISHED.read_text()).items()
          if not k.startswith("_")}
    cfg = config_from_hf(hf)
    assert (cfg.n_layers, cfg.n_kv_layers, cfg.n_state_layers) == (32, 8, 24)
    assert cfg.max_seq == 65536
    assert T.param_count(cfg) == shapes.parameters(hf) == \
        24 * 215_570_172 + 8 * 185_809_920 + 770_703_360 + 3_840


def test_the_cuts_file_keeps_the_published_widths():
    hf = json.loads(CUT.read_text())
    helpers.check_published_widths(hf, BENCH)
    assert sorted(hf["reduced"]) == ["layer_types", "num_hidden_layers"]
    published = json.loads(PUBLISHED.read_text())
    # three whole periods, the published 3 : 1
    assert hf["layer_types"] == published["layer_types"][:12]
    assert hf["stands_for"] and "share_of" not in hf
    for key in ("head_dim", "norm_placement", "qk_norm", "no_positions",
                "delta_net", "write_strength", "column_order", "state_dtype",
                "state_layout", "decay", "weights", "state_slots", "kv_pool",
                "max_tracked_sequences", "max_seq_len"):
        assert hf["assumed"][key]
    # every key of the catalog row's config, under the same name
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Olmo-Hybrid-7B"' in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        assert {k: v for k, v in published.items()
                if not k.startswith("_")} == row["config"]
        assert published["_source"] == hf["source"] == row["source_url"]


_MISTRAL = {"architectures": ["MistralForCausalLM"], "hidden_size": 64,
            "intermediate_size": 128, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": 2,
            "vocab_size": 64}


@pytest.mark.parametrize("what,hf,match", [
    ("a rotation on the full layers",
     dict(HF, rope_parameters={"rope_theta": 500000.0}), "rope_theta"),
    ("a top-level rope_theta", dict(HF, rope_theta=10000.0), "rope_theta"),
    ("a scaled rotation",
     dict(HF, rope_scaling={"rope_type": "yarn", "factor": 8.0}),
     "rope_theta"),
    ("biases on q, k, v", dict(HF, attention_bias=True), "attention_bias"),
    ("clipped q, k, v", dict(HF, clip_qkv=8.0), "clip_qkv"),
    ("another activation", dict(HF, hidden_act="gelu"), "hidden_act"),
    ("key heads that do not divide the value heads",
     dict(HF, linear_num_key_heads=3), "linear_num_key_heads"),
    ("a kind the family does not have",
     dict(HF, layer_types=["sliding_attention"] * 8), "layer_types names"),
    ("layer_types of another length",
     dict(HF, layer_types=["linear_attention"] * 7), "layer_types names"),
    ("a latent key olmo_hybrid does not read", dict(HF, kv_lora_rank=32),
     "does not read"),
    ("experts olmo_hybrid does not read",
     dict(HF, moe_intermediate_size=64), "does not read"),
    ("a write strength to 2 under another architecture",
     dict(_MISTRAL, linear_allow_neg_eigval=True), "does not read"),
])
def test_what_the_mapping_cannot_serve_is_an_error(what, hf, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(hf)


def test_the_repeat_of_fewer_key_heads_is_computed():
    cfg = config_from_hf(dict(HF, linear_num_key_heads=2))
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads) == (2, 4)
    assert not config_from_hf(
        dict(HF, linear_allow_neg_eigval=False)).gdn_neg_eigval


@pytest.mark.parametrize("kwargs,match", [
    (dict(output_norm=True, sandwich_norm=True), "output_norm"),
    (dict(output_norm=True, parallel_residual=True), "output_norm"),
    (dict(output_norm=True, variant="gpt2"), "output_norm"),
    (dict(output_norm=True, residual_multiplier=0.5), "output_norm"),
])
def test_the_output_norm_excludes_the_other_placements(kwargs, match):
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(n_layers=2, **kwargs)


@pytest.mark.parametrize("heads,dv,pack", [
    (30, 192, 2), (32, 128, 1), (8, 256, 1), (4, 64, 2), (8, 32, 4),
    (3, 192, 1), (4, 96, 4), (6, 96, 1)])
def test_heads_stand_side_by_side_where_that_fills_lane_tiles(heads, dv, pack):
    cfg = T.TransformerConfig(
        n_layers=1, layer_types=("linear_attention",), conv_kernel=4,
        gdn_key_heads=heads, gdn_value_heads=heads, gdn_key_dim=8,
        gdn_value_dim=dv)
    assert cfg.gdn_pack == pack
    assert cfg.gdn_state_shape == (heads // pack, 8, pack * dv)
    state = jnp.arange(2 * heads * 8 * dv, dtype=jnp.float32).reshape(
        2, heads, 8, dv)
    packed = GD.pack_heads(state, pack)
    assert packed.shape == (2,) + cfg.gdn_state_shape
    # head h of a row's `pack` lies in lanes [h dv, (h + 1) dv)
    np.testing.assert_array_equal(packed[1, 0, :, (pack - 1) * dv:],
                                  state[1, pack - 1])
    np.testing.assert_array_equal(GD.unpack_heads(packed, pack), state)


def test_the_training_forward_refuses_the_family(model):
    mcfg, params = model
    with pytest.raises(NotImplementedError, match="output_norm"):
        T.forward_hidden(params, jnp.zeros((1, 8), jnp.int32), mcfg)


# -- the engine against the reference -------------------------------------

@pytest.fixture(scope="module")
def served(model, shared_engine):
    return _feeds(model, shared_engine, [70, 83], [5], 6)


def test_prefill_chunks_and_single_steps_match_the_reference(served):
    got, want, _, _ = served
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


@pytest.mark.parametrize("chunk", [1, 2, 3, 4, 7])
def test_a_chunk_boundary_at_every_offset(model, shared_engine, chunk):
    """The first chunk starts 1..7 tokens before the prompt's end (a
    run of one, runs shorter and longer than the convolution's three
    carried inputs), a second chunk of 4 follows (its first rows read
    what the first left in the slot: the matrices and the inputs), then
    single steps."""
    got, want, _, _ = _feeds(model, shared_engine, [41, 56], [chunk, 4], 3,
                             seed=chunk)
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


def _float8(x):
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


@pytest.mark.parametrize("control", ref.MUTANTS + ("float8_weights",))
def test_a_wrong_model_fails_the_written_tolerance(model, served, control):
    """Each of the logits audit's controls, put in the reference's
    place: the engine must NOT agree with it. `state_bf16` (the
    matrices rounded to bf16 after every token) is judged HERE: the
    chip's bf16 engine cannot tell it from its own rounding."""
    got, _, padded, cuts = served
    params = model[1]
    if control == "float8_weights":
        wrong = _ref_logits(jax.tree.map(_float8, params), padded)
    else:
        wrong = _ref_logits(params, padded, control)
    wrong = np.stack([wrong[i, np.asarray(c) - 1] for i, c in enumerate(cuts)])
    assert np.abs(got - wrong).max() > 30 * LOGITS_ATOL, control


@pytest.mark.usefixtures("pallas_interpret")
def test_the_engine_with_kernels_matches_the_reference(model):
    """decode_impl 'auto' under the interpreter resolves the kernels:
    the step kernel on the aliased pool of paired heads, the
    convolution's one pass, the walk and write in the attention
    layers."""
    eng = _engine(model)
    assert eng.resolved_impl == "pallas"
    assert eng.step_kernel(8) and eng.carry_kernel(8)
    got, want, _, _ = _feeds(model, eng, [37, 45], [5], 3, seed=4)
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


def test_a_pool_that_holds_more_heads_than_the_model_serves_the_same(
        model, monkeypatch):
    """The published 30 KV heads are held in 32 (kv_heads_held: whole
    tiles). Here the 4 heads in 7, in float32 with no kernel: the new
    rows and the queries are padded with zero heads on their way to the
    pool and the padding's output is cut, through prefill, chunks and
    single steps, and through the scheduler."""
    monkeypatch.setattr(M, "kv_heads_held", lambda kv, d, itemsize: kv + 3)
    eng = _engine(model)
    assert [a.shape for a in eng.cache.k] == [(49, 32, 7, 32)] * 2
    got, want, _, _ = _feeds(model, eng, [37, 45], [5], 3, seed=4)
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)
    # the padding heads hold zeros
    assert all(float(jnp.abs(a[:, :, 4:]).max()) == 0 for a in eng.cache.k)
    requests = _requests(4, seed=9)
    _, outputs = _serve(_sched_engine(model), requests)
    _greedy_by_the_reference(model, requests, outputs)


def _step_text(eng):
    return eng._decode_fn(8, False).lower(
        eng.params, eng.cache, *(eng._dev(np.zeros(s, np.int32)) for s in
                                 ((8,), (8, eng.config.blocks_per_seq), (8,))),
        *eng.state_args(np.zeros((8,), np.int32))).as_text(debug_info=True)


def test_the_scopes_of_the_layer_are_in_the_program(model):
    """The operator keeps its four scopes; the output norms have scopes
    of their own name, OUTSIDE the operator's and the FFN's (a reader
    sums by the outermost match); no norm stands before a sublayer."""
    text = _step_text(_engine(model))
    for scope in ("linear_attention/gdn_project", "linear_attention/gdn_conv",
                  "linear_attention/gdn_state", "linear_attention/gdn_out",
                  "jit(step)/norm1_post", "jit(step)/norm2_post",
                  "jit(step)/attention", "jit(step)/mlp"):
        assert scope in text, scope
    for scope in ("/norm1/", "/norm2/", "attention/norm1_post",
                  "linear_attention/norm1_post", "mlp/norm2_post"):
        assert scope not in text, scope


@pytest.mark.usefixtures("pallas_interpret")
def test_the_warm_up_says_which_delta_rule_the_step_compiled(model, caplog):
    """`state_step kernel` or `state_step xla` in the line of each
    decode program and on its kept span: a silent fall-back to the loop
    over rows is seen in a run's own log."""
    from deepspeed_tpu.utils import profiler

    for impl, said in (("auto", "kernel"), ("xla", "xla")):
        eng = _engine(model, decode_impl=impl, max_batch_size=8)
        profiler.clear()
        logging.getLogger("deepspeed_tpu").propagate = True
        with caplog.at_level(logging.INFO, logger="deepspeed_tpu"):
            caplog.clear()
            eng.warmup(widths=[8], footprint=False)
        lines = [r.getMessage() for r in caplog.records
                 if "serving warmup program: kind decode" in r.getMessage()]
        assert lines and all(f"state_step {said}" in l for l in lines), lines
        spans = [s for s in profiler.spans()
                 if s.name == "warmup.program" and s.ids["kind"] == "decode"]
        assert spans and all(s.ids["state_step"] == said for s in spans)
        assert eng.step_kernel(8) is (said == "kernel")


# -- through the scheduler: slots taken, reused, never cleared -------------

def _requests(n, seed=5):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, HF["vocab_size"], int(rng.integers(9, 60))
                          ).tolist(), int(rng.integers(3, 12)))
            for _ in range(n)]


def _sched_engine(model, **over):
    return _engine(model, max_batch_size=ENGINE["max_tracked_sequences"],
                   **over)


def _serve(eng, requests, **sched):
    s = ServingScheduler(eng, ServingSchedulerConfig(
        **dict(dict(max_num_batched_tokens=48, prefill_chunk=8,
                    prefill_mode="chunked", decode_chunk=1, warmup=False),
               **sched)))
    rids = [s.submit(p, max_new_tokens=n) for p, n in requests]
    s.run()
    return s, [s.finished[r].output for r in rids]


def _greedy_by_the_reference(model, requests, outputs):
    for (prompt, _), out in zip(requests, outputs):
        toks = np.zeros((1, 96), np.int32)
        toks[0, :len(prompt) + len(out)] = prompt + out
        logits = _ref_logits(model[1], toks)[0]
        for j, t in enumerate(out):
            row = logits[len(prompt) + j - 1]
            assert row[t] >= row.max() - LOGITS_ATOL, (j, t, row.argmax())


def test_a_slot_is_handed_on_with_no_clearing(model):
    """12 requests of unequal lengths through 6 slots: every slot is
    handed on to a later sequence, and what the last one left in it
    (here: NaN, put there before the first admission too, in the
    matrices AND the carried inputs) never reaches the next."""
    eng = _sched_engine(model)
    eng.cache = eng.cache._replace(state=jax.tree.map(
        lambda p: jnp.full_like(p, jnp.nan), eng.cache.state))
    requests = _requests(12)
    s, outputs = _serve(eng, requests)
    assert all(len(o) == n for o, (_, n) in zip(outputs, requests))
    _greedy_by_the_reference(model, requests, outputs)
    d = s.counters
    assert d["state_slot_resets"] == 12 > ENGINE["max_tracked_sequences"]
    assert d["state_slots_live"] >= d["steps"] > 0
    assert eng.state.n_tracked == 0 and len(eng.state._free_slots) == 6
    # a slot: 6 DeltaNet layers x (4 matrices of 96 x 192 + 3 inputs of
    # 2 x 4 x 96 + 4 x 192 = 1,536 channels: 12 lane rows in 16, whole
    # (8, 128) tiles), float32: what the benchmark's count NEEDS but for
    # those four lane rows
    assert eng.state_slot_bytes == 6 * 4 * (4 * 96 * 192 + 3 * 2048)
    assert eng.state_slot_bytes - 6 * 4 * 3 * 512 == \
        6 * shapes.state_bytes_per_sequence_per_layer(HF, dtype_bytes=4)
    # a sequence a step a layer: its slot read once and written once
    assert d["state_bytes_moved"] % (2 * eng.state_slot_bytes) == 0
    assert d["state_bytes_moved"] >= 2 * eng.state_slot_bytes * d["steps"]
    prompts = sum(len(p) for p, _ in requests)
    assert prompts - 12 <= d["gdn_run_tokens"] <= prompts


def test_whole_prompt_waves_and_fused_decode_carry_the_state(model):
    """prefill_mode 'wave' runs the chunked scan and writes the slot at
    the prompt's end; decode_chunk 4 carries it through a fused scan."""
    requests = _requests(6, seed=3)
    s, outputs = _serve(_sched_engine(model), requests, prefill_mode="wave",
                        decode_chunk=4)
    _greedy_by_the_reference(model, requests, outputs)
    assert s.counters["gdn_run_tokens"] == sum(len(p) for p, _ in requests)


def test_preemption_recomputes_to_identical_tokens(model):
    requests = [(p, 40) for p, _ in _requests(6, seed=7)]
    _, roomy = _serve(_sched_engine(model), requests)
    s, tight = _serve(_sched_engine(model, num_kv_blocks=7), requests)
    assert s.counters["preemptions"] > 0
    assert tight == roomy


@pytest.mark.usefixtures("pallas_interpret")
def test_the_kernels_serve_what_the_xla_path_serves(model):
    """The scheduler's steps through `gdn_state` on paired heads and
    the convolution's one pass against decode_impl 'xla': the same
    served tokens, every step counted where the kernel ran."""
    eng, xla = _sched_engine(model), _sched_engine(model, decode_impl="xla")
    requests = [(p[:12], 3) for p, _ in _requests(2, seed=8)]
    s, served = _serve(eng, requests, max_num_batched_tokens=8)
    sx, served_xla = _serve(xla, requests, max_num_batched_tokens=8)
    assert served == served_xla
    assert s.counters["state_step_kernel_steps"] == s.counters["steps"] > 0
    assert sx.counters["state_step_kernel_steps"] == 0 < sx.counters["steps"]
    assert "linear_attention/gdn_state/jit(_gated_delta_step)" in \
        _step_text(eng)
    assert "jit(_gated_delta_step)" not in _step_text(xla)


# -- what cannot be right yet is refused where it is built ----------------

@pytest.mark.parametrize("what,kwargs,config", [
    ("int8_kv", {}, {"kv_cache_dtype": "int8"}),
    ("mesh", {}, {"tp_size": 2}),
    ("weight_quantization", {"quantization": {"bits": 8}}, {}),
    ("offload", {"offload": {"device": "cpu"}}, {}),
])
def test_the_engine_refuses_at_build(model, what, kwargs, config):
    mcfg, params = model
    assert E.pool_kinds(mcfg) == ("kv", "state")
    with pytest.raises(NotImplementedError, match=what):
        init_inference(params, mcfg, dict(ENGINE, **config),
                       dtype=jnp.float32, **kwargs)
