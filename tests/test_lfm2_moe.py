"""LFM2-MoE (`lfm2_moe`) on the normal serving path at a tiny size on the
CPU, against the plain float32 reference of
benchmarks/reference/lfm2_moe.py: layers of two kinds (gated short
convolutions whose sequences carry their last two inputs in a state
slot, attention layers that alone hold K/V, in pools packed two heads
of 64 a row), a leading dense layer, a sigmoid router with an expert
bias, through whole-prompt prefill, chunks and single steps, through
the scheduler with slots reused and under preemption, the mutants that
must fail, every refusal, the packed D = 64 kernels against their
oracle, and the cut's file.

Everything is float32 with seeded weights: the published layer pattern,
1 dense + two periods of (attention, conv, conv, conv), d 256, 4 query
/ 2 KV heads of 64, 8 experts of 128 top-2.
"""

import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.reference import lfm2_moe as ref
from benchmarks.tests import helpers
from deepspeed_tpu.inference import (
    ServingScheduler,
    ServingSchedulerConfig,
    init_inference,
)
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import paged_attention as PA
from deepspeed_tpu.utils import profiler
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks"
HF = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 256,
      "intermediate_size": 384,
      "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                      "full_attention", "conv", "conv", "conv"],
      "max_position_embeddings": 512, "model_type": "lfm2_moe",
      "moe_intermediate_size": 128, "norm_eps": 1e-05,
      "norm_topk_prob": True, "num_attention_heads": 4,
      "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
      "num_hidden_layers": 9, "num_key_value_heads": 2,
      "rope_theta": 1000000, "routed_scaling_factor": 1,
      "use_expert_bias": True, "vocab_size": 256}

# float32 on both sides, logits up to 5.0. The system reassociates (the
# fused QKV matmul, the expert paths' running sums, the taps' sum in
# another order, the oracle's softmax), which moves a logit by ~1e-5
# (measured here: 1.3e-5 over prefill, chunk and single steps, at most
# 1.5e-5 over the chunk offsets); a router tie flipped by that noise
# would move one by ~0.05, and none is at these seeds. The mutants
# differ by 0.24 (the bias used as a weight), 0.53 (a cache rounded to
# float8), 0.69 (no QK-norm), 1.2 (one expert fewer), 1.9 (weights
# rounded to float8), 6.3 (no state carried) and 7.1 (taps reversed):
# all at least 1,200 x the limit, which is 13 x the noise.
LOGITS_ATOL = 2e-4
ENGINE = dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=48,
              max_batch_size=32, max_tracked_sequences=6,
              min_prefill_bucket=32)


@pytest.fixture(scope="module")
def model():
    mcfg = config_from_hf(HF, use_flash=False)
    params = T.init(mcfg, jax.random.PRNGKey(1))
    # spread the logits (the 0.02 init gives nearly flat ones), make
    # every norm scale matter (T.init gives ones), give the taps and the
    # expert bias a size at which leaving them out shows
    params = jax.tree.map(lambda x: x * 4, params)

    def shaped(tree, salt):
        out = {}
        for i, (k, v) in enumerate(tree.items()):
            key = jax.random.fold_in(jax.random.PRNGKey(salt), i)
            if "scale" in k:
                v = 1 + 0.3 * jax.random.normal(key, v.shape)
            elif k == "conv_taps":
                v = 0.6 * jax.random.normal(key, v.shape)
            elif k == "expert_bias":
                v = 0.2 * jax.random.normal(key, v.shape)
            out[k] = v
        return out

    top = shaped({k: v for k, v in params.items() if k != "layers"}, 2)
    return mcfg, dict(top, layers=shaped(params["layers"], 3))


def _top(params):
    return {k: v for k, v in params.items() if k != "layers"}


def _layer_fn(params):
    return lambda l: jax.tree.map(lambda a: a[l], params["layers"])


def _ref_logits(params, toks, mutate=None):
    return np.asarray(ref.forward_logits(_top(params), _layer_fn(params),
                                         toks, HF, mutate))


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
    hf = json.loads((BENCH / "configs/lfm2-8b-a1b-serve-l13.json").read_text())
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    assert (cfg.n_dense_layers, cfg.n_layers, cfg.depth) == (1, 12, 13)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == \
        (2048, 32, 8, 64)
    assert (cfg.ff_dim, cfg.dense_d_ff, cfg.conv_kernel) == (1792, 7168, 3)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (32, 4, None)
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_expert_bias
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.tie_embeddings
    assert cfg.layer_types == ("conv",) + ("attention", "conv", "conv",
                                           "conv") * 3
    assert (cfg.n_kv_layers, cfg.n_state_layers, cfg.state_width("conv")) == \
        (3, 10, 4096)
    assert cfg.vocab_size == 65536 and cfg.rope_theta == 1e6
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["w_in"].shape == (12, 32, 2048, 1792)
    assert shapes["layers"]["expert_bias"].shape == (12, 32)
    assert shapes["dense_w_in"].shape == (1, 2048, 7168)
    assert shapes["conv_in"].shape == (10, 2048, 6144)
    assert shapes["conv_taps"].shape == (10, 2048, 3)
    assert shapes["attn_wk"].shape == (3, 2048, 8, 64)
    assert shapes["attn_q_norm_scale"].shape == (3, 64)
    flat = dict(shapes["layers"], **{k: v for k, v in shapes.items()
                                     if k != "layers"})
    # the file's own count, every leaf
    assert sum(int(np.prod(s.shape)) for s in flat.values()) == 4_606_249_728
    # ONE homogeneous stack and top-level ARRAYS: what the benchmark's
    # weight maker and reference_inputs take
    assert all(not isinstance(v, dict) for k, v in shapes.items()
               if k != "layers")
    assert all(v.shape[0] == cfg.n_layers for v in shapes["layers"].values())
    # the cache: K/V for the attention layers alone, packed; a slot a
    # tracked sequence a conv layer
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, 2049, 128, jnp.bfloat16, state_slots=1024))
    assert [a.shape for a in cache.k] == [(2049, 128, 4, 128)] * 3
    assert [a.shape for (a,) in cache.state] == [(1024, 2, 16, 128)] * 10
    assert sum(a.size * 2 for a in cache.k + cache.v) / 2049 / 128 == 6144


def test_the_published_shapes_group_their_pairs_past_the_ridge():
    """32 experts of 2048 x 1792, top-4: the cell's 512-row program
    multiplies an expert by its own rows; 128 rows, under the chip's
    ridge, every token by all 32; the logits check's whole-prompt
    prefill (two prompts of 512 rows: 128 rows an expert) keeps the
    ragged wire."""
    hf = json.loads((BENCH / "configs/lfm2-8b-a1b-serve-l13.json").read_text())
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    stack = jax.ShapeDtypeStruct((32, 2048, 1792), jnp.bfloat16)
    lp = {"w_gate": stack, "w_in": stack,
          "w_out": jax.ShapeDtypeStruct((32, 1792, 2048), jnp.bfloat16)}
    widths = (8, 16, 128, 256, 257, 512, 768, 1024, 2048)
    assert [M.expert_path(t, cfg, lp, True) for t in widths] == [
        "ragged", "stream", "stream", "stream", "grouped", "grouped",
        "grouped", "ragged", "ragged"]
    # kernels off (decode_impl "xla"), or a mesh: as before the entry
    assert [M.expert_path(t, cfg, lp, False) for t in widths] == [
        "ragged", "ragged", "scan", "scan", "scan", "scan", "scan",
        "ragged", "ragged"]


def test_the_cuts_file_keeps_the_published_widths():
    hf = json.loads((BENCH / "configs/lfm2-8b-a1b-serve-l13.json").read_text())
    helpers.check_published_widths(hf, BENCH)
    assert sorted(hf["reduced"]) == ["layer_types", "num_dense_layers",
                                     "num_hidden_layers"]
    published = json.loads(
        (BENCH / "configs/published/lfm2-8b-a1b.json").read_text())
    assert hf["layer_types"] == published["layer_types"][1:14]
    for key in ("split_order", "no_conv_activation", "qk_norm", "expert_bias",
                "tie_word_embeddings", "weights", "kv_pool", "state_slots",
                "max_seq_len"):
        assert hf["assumed"][key]
    assert "share_of" not in hf  # every expert, head and row is here


@pytest.mark.parametrize("what,hf", [
    ("a latent key lfm2_moe does not read", dict(HF, kv_lora_rank=32)),
    ("a shared expert lfm2_moe does not read", dict(HF, n_shared_experts=1)),
    ("conv layers under another architecture",
     {"architectures": ["MistralForCausalLM"], "hidden_size": 64,
      "intermediate_size": 128, "num_attention_heads": 4,
      "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 64,
      "layer_types": ["conv", "full_attention"]}),
    ("an expert bias under another architecture",
     {"architectures": ["MistralForCausalLM"], "hidden_size": 64,
      "intermediate_size": 128, "num_attention_heads": 4,
      "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 64,
      "use_expert_bias": True}),
])
def test_a_block_key_the_mapping_does_not_read_stays_an_error(what, hf):
    with pytest.raises(ValueError, match="does not read"):
        config_from_hf(hf)


def test_full_attention_layer_types_ask_nothing_of_another_architecture():
    cfg = config_from_hf({
        "architectures": ["MistralForCausalLM"], "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 64,
        "layer_types": ["full_attention", "full_attention"]})
    assert cfg.layer_types is None and cfg.n_state_layers == 0


def test_the_training_forward_refuses_the_family(model):
    mcfg, params = model
    with pytest.raises(NotImplementedError, match="layer_types"):
        T.forward_hidden(params, jnp.zeros((1, 8), jnp.int32), mcfg)


# -- the engine against the reference -------------------------------------

@pytest.fixture(scope="module")
def served(model, shared_engine):
    return _feeds(model, shared_engine, [70, 83], [5], 6)


def test_prefill_chunks_and_single_steps_match_the_reference(served):
    got, want, _, _ = served
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


@pytest.mark.parametrize("chunk", [1, 2, 3, 4, 5, 6])
def test_a_chunk_boundary_at_every_offset(model, shared_engine, chunk):
    """The first chunk starts 1..6 tokens before the prompt's end (every
    residue mod the kernel's 3 taps, one and two tokens among them: a
    row whose two inputs before it are BOTH in the slot, one in the
    slot and one a neighbour row, both neighbour rows), a second chunk
    of 4 follows (its first rows read what the first left), then steps."""
    got, want, _, _ = _feeds(model, shared_engine, [41, 56], [chunk, 4], 3,
                             seed=chunk)
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


def _float8(x):
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


@pytest.mark.parametrize("control", ref.MUTANTS + ("float8_weights",))
def test_a_wrong_model_fails_the_written_tolerance(model, served, control):
    """Each of the logits audit's controls, put in the reference's
    place: the engine must NOT agree with it."""
    got, _, padded, cuts = served
    params = model[1]
    if control == "float8_weights":
        wrong = _ref_logits(jax.tree.map(_float8, params), padded)
    else:
        wrong = _ref_logits(params, padded, control)
    wrong = np.stack([wrong[i, np.asarray(c) - 1] for i, c in enumerate(cuts)])
    assert np.abs(got - wrong).max() > 30 * LOGITS_ATOL, control


def test_a_host_tree_the_device_cannot_hold_twice_is_laid_out_on_the_host(
        model, monkeypatch):
    """9.2 GB of weights come to init_inference as host arrays (the
    benchmark's runner); the compiled transform would hold them twice.
    The engine reads the device's own limit: over half of it, the
    serving layout is made of host views and sent once, the same tree."""
    mcfg, params = model
    host = jax.device_get(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(host))
    compiled = _engine((mcfg, host))

    class Small:
        def memory_stats(self):
            return {"bytes_limit": int(1.5 * nbytes), "bytes_in_use": 0}

    monkeypatch.setattr(jax, "local_devices", lambda: [Small()])
    assert compiled._host_tree_too_large_twice(host)
    assert not compiled._host_tree_too_large_twice(params)  # device arrays
    on_host = _engine((mcfg, host))
    monkeypatch.undo()
    assert not compiled._host_tree_too_large_twice(host)  # the CPU: no limit
    a, ta = jax.tree.flatten(compiled.params)
    b, tb = jax.tree.flatten(on_host.params)
    assert ta == tb
    assert all(x.dtype == y.dtype and np.array_equal(x, y)
               for x, y in zip(a, b))


def test_the_bias_moves_the_choice_and_not_the_weights():
    cfg = config_from_hf(HF)
    logits = jnp.asarray([[0.0, 0.1, 0.2, 0.3, -1, -1, -1, -1.0]])
    plain_idx, plain_w = M._sigmoid_topk_gating(logits, cfg)
    bias = jnp.zeros((8,)).at[0].set(1.0)
    idx, w = M._sigmoid_topk_gating(logits, cfg, bias)
    assert sorted(np.asarray(plain_idx[0])) == [2, 3]
    assert sorted(np.asarray(idx[0])) == [0, 3]
    s = jax.nn.sigmoid(logits[0])
    want = np.asarray([s[0], s[3]]) / float(s[0] + s[3])
    np.testing.assert_allclose(sorted(np.asarray(w[0])), sorted(want),
                               rtol=1e-6)
    np.testing.assert_allclose(float(plain_w.sum()), 1.0, rtol=1e-6)


# -- through the scheduler: slots taken, reused, reset --------------------

def _requests(n, seed=5):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, HF["vocab_size"], int(rng.integers(9, 60))
                          ).tolist(), int(rng.integers(3, 12)))
            for _ in range(n)]


def _sched_engine(model, **over):
    """An engine whose row budget admits as many sequences as it has
    slots (the scheduler admits up to max_batch_size, and the tracked-
    sequence cap is an error, not a wait: tests/test_overload.py)."""
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
    """Every served token is the reference's argmax at its position,
    teacher-forced on the served tokens themselves (to a margin: two
    logits closer than the tolerance may swap)."""
    for (prompt, _), out in zip(requests, outputs):
        # one shape for every request: padding after the end cannot
        # reach the positions read under a causal mask
        toks = np.zeros((1, 96), np.int32)
        toks[0, :len(prompt) + len(out)] = prompt + out
        logits = _ref_logits(model[1], toks)[0]
        for j, t in enumerate(out):
            row = logits[len(prompt) + j - 1]
            assert row[t] >= row.max() - LOGITS_ATOL, (j, t, row.argmax())


def test_the_scheduler_serves_unequal_sequences_through_reused_slots(model):
    """12 requests of unequal lengths through 6 slots (a row budget of
    6 sequences at a time): every slot is handed on to a later
    sequence, and what the last one left in it (here: NaN, put there
    before the first admission too) never reaches the next."""
    eng = _sched_engine(model)
    eng.cache = eng.cache._replace(
        state=jax.tree.map(lambda p: jnp.full_like(p, jnp.nan),
                           eng.cache.state))
    requests = _requests(12)
    s, outputs = _serve(eng, requests)
    assert all(len(o) == n for o, (_, n) in zip(outputs, requests))
    _greedy_by_the_reference(model, requests, outputs)
    d = s.counters
    assert d["state_slot_resets"] == 12 > ENGINE["max_tracked_sequences"]
    assert d["state_slots_live"] >= d["steps"] > 0
    assert d["state_prefix_credits_refused"] == 0
    assert eng.state.n_tracked == 0 and len(eng.state._free_slots) == 6
    assert d["lookahead_steps"] > 0  # the slot is updated in program order


def test_step_and_run_serve_the_same_tokens(model):
    requests = _requests(8, seed=9)
    _, ahead = _serve(_sched_engine(model), requests)
    s = ServingScheduler(_sched_engine(model), ServingSchedulerConfig(
        max_num_batched_tokens=48, prefill_chunk=8, prefill_mode="chunked",
        decode_chunk=1, warmup=False))
    rids = [s.submit(p, max_new_tokens=n) for p, n in requests]
    while s.has_work:
        s.step()
    assert [s.finished[r].output for r in rids] == ahead


def test_whole_prompt_waves_and_fused_decode_carry_the_state(model):
    """prefill_mode 'wave' writes the slot at the end of a whole-prompt
    prefill; decode_chunk 4 carries it through a fused scan."""
    requests = _requests(6, seed=3)
    _, outputs = _serve(_sched_engine(model), requests, prefill_mode="wave",
                        decode_chunk=4)
    _greedy_by_the_reference(model, requests, outputs)


def test_preemption_recomputes_to_identical_tokens(model):
    """A pool too small for the batch: the youngest sequence is flushed
    and recomputed from its first token in whatever slot it is given."""
    requests = [(p, 40) for p, _ in _requests(6, seed=7)]
    _, roomy = _serve(_sched_engine(model), requests)
    s, tight = _serve(_sched_engine(model, num_kv_blocks=7), requests)
    assert s.counters["preemptions"] > 0
    assert s.counters["state_slot_resets"] == 6 + s.counters["preemptions"]
    assert tight == roomy


# -- what cannot be right yet is refused where it is built ----------------

def test_pool_kinds_and_what_each_cannot_do(model):
    mcfg, _ = model
    assert E.pool_kinds(mcfg) == ("kv", "state")
    assert E.pool_kinds(config_from_hf(
        {"architectures": ["MistralForCausalLM"], "hidden_size": 64,
         "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2,
         "vocab_size": 64})) == ("kv",)
    with pytest.raises(NotImplementedError, match=r"kv \+ state.*the state"):
        E.refuse_for_pools(mcfg, "speculation")


@pytest.mark.parametrize("what,kwargs,config", [
    ("int8_kv", {}, {"kv_cache_dtype": "int8"}),
    ("mesh", {}, {"tp_size": 2}),
    ("weight_quantization", {"quantization": {"bits": 8}}, {}),
    ("offload", {"offload": {"device": "cpu"}}, {}),
])
def test_the_engine_refuses_at_build(model, what, kwargs, config):
    mcfg, params = model
    with pytest.raises(NotImplementedError, match=what):
        init_inference(params, mcfg, dict(ENGINE, **config),
                       dtype=jnp.float32, **kwargs)


def test_pages_do_not_travel_without_their_slot(model):
    eng = _engine(model)
    eng.put([1], [np.arange(40, dtype=np.int32)])
    with pytest.raises(NotImplementedError, match="page_transfer"):
        eng.export_kv(1)
    with pytest.raises(NotImplementedError, match="page_transfer"):
        eng.import_kv(2, {})
    with pytest.raises(NotImplementedError, match="page_transfer"):
        eng.warmup_kv_transfer()


def test_the_scheduler_refuses_spill_handoff_and_speculation(model):
    eng = _engine(model)
    with pytest.raises(NotImplementedError, match="speculation"):
        ServingScheduler(eng, ServingSchedulerConfig(warmup=False),
                         speculative={"ngram": 2, "draft_len": 3})
    with pytest.raises(NotImplementedError, match="page_transfer"):
        ServingScheduler(eng, ServingSchedulerConfig(
            warmup=False, pressure={"enabled": True, "spill_enabled": True}))
    s = ServingScheduler(eng, ServingSchedulerConfig(warmup=False))
    with pytest.raises(NotImplementedError, match="page_transfer"):
        s.submit([1, 2, 3], handoff=True)
    with pytest.raises(NotImplementedError, match="speculation"):
        eng.generate_speculative([[1, 2, 3, 1, 2, 3, 1, 2]], max_new_tokens=4)


def test_a_prefix_credit_is_declined_and_counted(model):
    """The index fills and is walked, and no admission is credited: the
    credited tokens' state is in no slot."""
    eng = _engine(model, prefix_cache={"enabled": True})
    prompt = np.random.default_rng(2).integers(0, 256, 70).tolist()
    requests = [(prompt, 4), (prompt + [7, 8, 9], 4)]
    s = ServingScheduler(eng, ServingSchedulerConfig(
        max_num_batched_tokens=48, prefill_chunk=8, prefill_mode="chunked",
        decode_chunk=1, warmup=False))
    outputs = []
    for p, n in requests:  # the second arrives when the first is indexed
        rid = s.submit(p, max_new_tokens=n)
        s.run()
        outputs.append(s.finished[rid].output)
        assert s.finished[rid].n_cached == 0
    assert eng.state.indexed_blocks > 0
    assert s.counters["state_prefix_credits_refused"] == 1
    assert eng.state.stats["cached_tokens"] == 0
    _greedy_by_the_reference(model, requests, outputs)


def test_the_set_up_spans_name_the_layers_by_kind(model):
    profiler.enable()
    try:
        profiler.spans(clear=True)
        eng = _engine(model)
        eng.warmup(widths=[8], footprint=False)
        spans = profiler.spans(clear=True)
    finally:
        profiler.disable()
    pool = next(s for s in spans if s.name == "init.pool")
    assert pool.ids["kv_layers"] == 2 and pool.ids["state_layers"] == 7
    assert pool.ids["state_slots"] == 6
    assert pool.ids["state_bytes"] == 7 * 6 * 2 * 256 * 4
    init = next(s for s in spans if s.name == "init.inference")
    assert (init.ids["kv_layers"], init.ids["state_layers"]) == (2, 7)
    programs = [s for s in spans if s.name == "warmup.program"
                and s.ids["kind"] == "decode"]
    assert programs and all(s.ids["state_layers"] == 7 for s in programs)


def _step_text(eng):
    return eng._decode_fn(8, False).lower(
        eng.params, eng.cache, *(eng._dev(np.zeros(s, np.int32)) for s in
                                 ((8,), (8, eng.config.blocks_per_seq), (8,))),
        *eng.state_args(np.zeros((8,), np.int32))).as_text(debug_info=True)


def test_the_scopes_of_the_operator_are_in_the_program(model):
    text = _step_text(_engine(model))
    for scope in ("short_conv/conv_project", "short_conv/conv_state",
                  "short_conv/conv_out", "attention"):
        assert scope in text, scope


@pytest.mark.usefixtures("pallas_interpret")
def test_the_convolution_kernel_serves_what_the_xla_path_serves(model):
    """The step program with its convolutions as the one-pass kernel
    (ops/pallas/conv_carry.py, under `conv_state`) against decode_impl
    'xla' (_carry_rows + _depthwise): the same logits over a prefill, a
    chunk and single steps, the same served tokens through reused
    slots, and every step of the schedule counted where the kernel ran
    and none where it did not. (One width of program throughout: the
    interpreter's kernels are slow to trace.)"""
    eng, xla = _sched_engine(model), _sched_engine(model, decode_impl="xla")
    assert eng.resolved_impl == "pallas" and eng.carry_kernel(8)
    assert xla.resolved_impl == "xla" and not xla.carry_kernel(8)
    # (no head carries a matrix here: the step kernels' counter stays 0)
    assert not eng.step_kernel(8) and not xla.step_kernel(8)
    assert "short_conv/conv_state/jit(_conv_carry)" in _step_text(eng)
    assert "jit(_conv_carry)" not in _step_text(xla)
    got, want, _, _ = _feeds(model, eng, [21], [5], 2, seed=6)
    oracle, _, _, _ = _feeds(model, xla, [21], [5], 2, seed=6)
    assert np.abs(got - oracle).max() < LOGITS_ATOL
    assert np.abs(got - want).max() < LOGITS_ATOL
    requests = [(p[:12], n) for p, n in _requests(8, seed=8)]
    s, served = _serve(eng, requests, max_num_batched_tokens=8)
    sx, served_xla = _serve(xla, requests, max_num_batched_tokens=8)
    assert served == served_xla
    assert s.counters["state_slot_resets"] == 8  # through 6 slots
    assert s.counters["state_carry_kernel_steps"] == s.counters["steps"] > 0
    assert sx.counters["state_carry_kernel_steps"] == 0 < sx.counters["steps"]
    assert s.counters["state_step_kernel_steps"] == 0
    assert sx.counters["state_step_kernel_steps"] == 0


# -- head dim 64: packed pools through the unchanged kernels --------------

def _packed_case(rng, ctx, H=8, KV=4, D=64, bs=16, NB=4, dtype=jnp.float32,
                 chunk=None):
    S, NBLK = len(ctx), len(ctx) * NB + 1
    q = jnp.asarray(rng.normal(size=(S, H, D)), dtype)
    kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), dtype)
    vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), dtype)
    tbl = rng.permutation(NBLK - 1)[:S * NB].reshape(S, NB).astype(np.int32)
    if chunk:
        tbl[chunk[0]:chunk[0] + chunk[1]] = tbl[chunk[0]]
    packed = lambda c: c.reshape(NBLK, bs, KV // 2, 2 * D)
    return q, kc, vc, packed(kc), packed(vc), jnp.asarray(tbl), \
        jnp.asarray(np.asarray(ctx, np.int32))


def test_who_packs():
    assert PA.kv_pack(8, 64) == 2 and PA.kv_pack(2, 64) == 2
    assert PA.kv_pack(8, 128) == 1 and PA.kv_pack(8, 32) == 1
    assert PA.kv_pack(3, 64) == 1  # an odd count of heads fills no row


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("what,kw", [
    ("groups_of_2", {}),
    ("one_query_a_head", dict(H=4, KV=4)),
    ("groups_of_4_bf16", dict(H=16, KV=4, dtype=jnp.bfloat16)),
    ("a_chunk_shares_a_table", dict(chunk=(2, 3))),
])
def test_the_packed_walk_matches_the_oracle(rng, what, kw):
    """Packed pools through the live-block walk, against the oracle on
    the UNPACKED pools (so the packing itself is checked, not only the
    kernel against its own view)."""
    ctx = (0, 1, 16, 17, 40, 41, 42, 64)
    q, kc, vc, pk, pv, tbl, ctx = _packed_case(rng, ctx, **kw)
    with jax.default_matmul_precision("highest"):
        out = PA.paged_decode_attention(q, pk, pv, tbl, ctx)
        also = PA.paged_decode_attention_xla(q, pk, pv, tbl, ctx)
        want = PA.paged_decode_attention_xla(q, kc, vc, tbl, ctx)
    tol = 3e-2 if q.dtype == jnp.bfloat16 else 2e-3
    real = np.asarray(ctx) > 0
    for got in (out, also):
        np.testing.assert_allclose(np.asarray(got, np.float32)[real],
                                   np.asarray(want, np.float32)[real],
                                   rtol=tol, atol=tol)
    assert not np.asarray(out, np.float32)[~real].any()
    # it IS the walk: one grid step a row, whatever the table's width
    qg = PA._group_queries(PA._pack_queries(q, 2, q.shape[1] // 4), 2)[0]
    assert PA._walks_live_blocks(qg, pk)


@pytest.mark.usefixtures("pallas_interpret")
def test_packed_rows_are_written_and_the_fused_walk_reads_them(rng):
    ctx = (1, 16, 17, 40, 0, 64)
    q, kc, vc, pk, pv, tbl, ctx = _packed_case(rng, ctx)
    S, KV, D = len(ctx), 4, 64
    kn = jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
    pos = np.maximum(np.asarray(ctx) - 1, 0)
    slots = np.where(np.asarray(ctx) > 0,
                     np.asarray(tbl)[np.arange(S), pos // 16] * 16 + pos % 16,
                     -1).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        wk, wv = PA.paged_kv_write(pk, pv, kn, vn, jnp.asarray(slots))
        xk, xv = M._write_kv_xla(pk, pv, kn, vn, jnp.asarray(slots))
        want = PA.paged_decode_attention_xla(q, xk, xv, tbl, ctx)
        fused, fk, fv = PA.paged_decode_attention(
            q, pk, pv, tbl, ctx, k_new=kn, v_new=vn, slots=jnp.asarray(slots))
    np.testing.assert_array_equal(np.asarray(wk), np.asarray(xk))
    np.testing.assert_array_equal(np.asarray(wv), np.asarray(xv))
    # the unpacked view holds the row where an unpacked write puts it
    uk, _ = M._write_kv_xla(kc, vc, kn, vn, jnp.asarray(slots))
    np.testing.assert_array_equal(np.asarray(xk).reshape(uk.shape),
                                  np.asarray(uk))
    real = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(fused)[real], np.asarray(want)[real],
                               rtol=2e-3, atol=2e-3)
    live = np.asarray(tbl)[real].ravel()
    np.testing.assert_array_equal(np.asarray(fk)[live], np.asarray(xk)[live])


def test_a_wide_step_writes_first_and_attends_after():
    assert PA.fused_write_fits(128) and PA.fused_write_fits(248)
    assert not PA.fused_write_fits(256) and not PA.fused_write_fits(512)


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


@pytest.mark.parametrize("rows", [512, 128])
def test_the_packed_walk_and_write_compile_for_v5e(one_chip, rows):
    """The cell's shapes: 32 query / 8 KV heads of 64 over pools packed
    to [2049, 128, 4, 128], a table of 32 slots a row."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((2049, 128, 4, 128), jnp.bfloat16)
    q, new = sds((rows, 32, 64), jnp.bfloat16), sds((rows, 8, 64), jnp.bfloat16)
    table, ints = sds((rows, 32), jnp.int32), sds((rows,), jnp.int32)

    def fn(q, kc, vc, kn, vn, table, ctx, slots):
        kc, vc = PA.paged_kv_write(kc, vc, kn, vn, slots)
        return PA.paged_decode_attention(q, kc, vc, table, ctx), kc, vc

    text = jax.jit(fn, donate_argnums=(1, 2)).lower(
        q, pool, pool, new, new, table, ints, ints).compile().as_text()
    for name in ("paged_decode_grid", "paged_kv_write"):
        assert any('custom_call_target="tpu_custom_call"' in line
                   and name in line for line in text.splitlines()), name
