"""Qwen3-Next (`qwen3_next`) on the normal serving path at a tiny size
on the CPU, against the plain float32 reference of
benchmarks/reference/qwen3_next.py: Gated DeltaNet layers whose
sequences carry a float32 matrix a value head (and the last three
inputs of a convolution) in a state slot, gated attention layers that
alone hold K/V, a held share of routed experts beside a gated shared
expert; through whole-prompt prefill (the chunked scan), chunks and
single steps (the segmented recurrence), through the scheduler with
slots reused and never cleared; the chunked form against the
recurrence, the step kernel against its oracle, the eight shares that
add up to the uncut layer, the mutants that must fail, the refusals,
and the cut's file.

Everything is float32 with seeded weights: two periods of (DeltaNet,
DeltaNet, DeltaNet, attention), d 128, 4 key / 8 value heads of 32 /
128, 4 query / 2 KV heads of 64 (16 rotated), 16 experts of 64 top-4 of
which experts 8..15 are held.
"""

import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import _state_walk as W
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.reference import qwen3_next as ref
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
from deepspeed_tpu.ops.pallas import paged_attention as PA
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks"
CUT = BENCH / "configs/qwen3-next-80b-a3b-serve-l12-ep8.json"
HF = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 64,
      "hidden_act": "silu", "hidden_size": 128, "intermediate_size": 320,
      "linear_conv_kernel_dim": 4, "linear_key_head_dim": 32,
      "linear_num_key_heads": 4, "linear_num_value_heads": 8,
      "linear_value_head_dim": 128, "max_position_embeddings": 512,
      "mlp_only_layers": [], "model_type": "qwen3_next",
      "moe_intermediate_size": 64, "norm_topk_prob": True,
      "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 4,
      "num_hidden_layers": 8, "num_key_value_heads": 2,
      "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
      "rope_scaling": None, "rope_theta": 10000000,
      "shared_expert_intermediate_size": 64, "tie_word_embeddings": False,
      "vocab_size": 256,
      "reduced": {"num_experts": {"published": 16, "here": 8}},
      "experts_held": {"start": 8, "count": 8, "of": 16}}

# float32 on both sides, logits up to 3.5. The system reassociates (the
# fused QKV matmul, the chunked scan's matmuls against the recurrence,
# the expert scan's running sum, the taps' sum in another order), which
# moves a logit by a few 1e-5 (measured here: 2.4e-5 over prefill, a
# chunk and single steps, at most 4.6e-5 over the chunk offsets). The
# mutants differ by 0.15 (`state_bf16`, the smallest), 0.18
# (`rope_all_256`), 0.32 (`no_attn_gate`), 0.46 (`no_topk_renorm`), 1.3
# (`no_shared_gate`), 2.7 (`plain_norm_scale`), 3.1 (weights rounded to
# float8) and 4.3-5.4 (`beta_one`, `no_l2norm`, `no_state_carry`,
# `no_decay`): every one at least 770 x the limit, which is 4 x the
# noise.
LOGITS_ATOL = 2e-4
ENGINE = dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=48,
              max_batch_size=32, max_tracked_sequences=6,
              min_prefill_bucket=32)


@pytest.fixture(scope="module")
def model():
    mcfg = config_from_hf(HF, use_flash=False)
    params = T.init(mcfg, jax.random.PRNGKey(1))
    # spread the logits (the 0.02 init gives nearly flat ones) and make
    # every norm scale, tap and decay matter
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
                v = 0.3 * jax.random.normal(key, v.shape)
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
    assert (cfg.n_layers, cfg.depth, cfg.d_model) == (12, 12, 2048)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (16, 2, 256)
    assert T.rope_dim(cfg) == 64 and cfg.rope_theta == 1e7
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.conv_kernel) == (16, 32, 128, 128, 4)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("attention",) \
        + ("linear_attention",) * 3 + ("attention",) \
        + ("linear_attention",) * 3 + ("attention",)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held, cfg.ff_dim) == \
        (512, 10, (0, 64), 512)
    assert cfg.moe_scoring == "softmax" and cfg.moe_norm_topk_prob
    assert cfg.n_shared_experts == 1 and cfg.shared_expert_gate
    assert cfg.attn_output_gate and cfg.qk_norm and cfg.qk_norm_per_head
    assert not cfg.tie_embeddings and cfg.vocab_size == 18992
    assert (cfg.n_kv_layers, cfg.n_state_layers) == (3, 9)
    assert cfg.state_shapes("linear_attention") == (
        ((32, 128, 128), jnp.float32), ((3, 8192 // 128, 128), None))
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["w_in"].shape == (12, 64, 2048, 512)
    assert shapes["layers"]["w_router"].shape == (12, 2048, 512)
    assert shapes["layers"]["ws_sgate"].shape == (12, 2048, 1)
    assert shapes["gdn_in"].shape == (9, 2048, 12288)
    assert shapes["gdn_ba"].shape == (9, 2048, 64)
    assert shapes["gdn_taps"].shape == (9, 8192, 4)
    assert shapes["gdn_out"].shape == (9, 4096, 2048)
    assert shapes["attn_wq"].shape == shapes["attn_wq_gate"].shape == \
        (3, 2048, 16, 256)
    assert shapes["attn_wk"].shape == (3, 2048, 2, 256)
    assert shapes["attn_q_norm_scale"].shape == (3, 256)
    flat = dict(shapes["layers"], **{k: v for k, v in shapes.items()
                                     if k != "layers"})
    # the file's own count, every leaf
    assert sum(int(np.prod(s.shape)) for s in flat.values()) == 2_929_374_400
    # ONE homogeneous stack and top-level ARRAYS: what the benchmark's
    # weight maker and reference_inputs take
    assert all(not isinstance(v, dict) for k, v in shapes.items()
               if k != "layers")
    assert all(v.shape[0] == cfg.n_layers for v in shapes["layers"].values())
    # the cache: K/V for the attention layers alone; two pools a
    # DeltaNet layer, the matrices' with the pad rows' slot
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, 1025, 128, jnp.bfloat16, state_slots=256))
    assert [a.shape for a in cache.k] == [(1025, 128, 2, 256)] * 3
    assert [tuple((a.shape, a.dtype) for a in pools)
            for pools in cache.state] == [
        (((257, 32, 128, 128), jnp.float32),
         ((256, 3, 64, 128), jnp.bfloat16))] * 9
    assert sum(a.size * 2 for a in cache.k + cache.v) / 1025 / 128 == 6144
    # what the engine counts before it allocates
    pools = E.pool_bytes(cfg, E.InferenceConfig(**hf["serve"]["engine"]),
                         jnp.bfloat16)
    assert pools == {"kv": 1025 * 128 * 6144,
                     "state": 9 * (257 * 2_097_152 + 256 * 49_152)}


def test_the_published_shapes_stream_the_held_experts():
    """64 held experts of 2048 x 512: a held share streams at every
    width (expert_path), through the one pipelined pass."""
    hf = json.loads(CUT.read_text())
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    stack = jax.ShapeDtypeStruct((64, 2048, 512), jnp.bfloat16)
    lp = {"w_gate": stack, "w_in": stack,
          "w_out": jax.ShapeDtypeStruct((64, 512, 2048), jnp.bfloat16)}
    assert {M.expert_path(t, cfg, lp, True) for t in (8, 256, 640)} == \
        {"stream"}
    assert M.expert_path(256, cfg, lp, False) == "scan"


def test_the_cuts_file_keeps_the_published_widths():
    hf = json.loads(CUT.read_text())
    helpers.check_published_widths(hf, BENCH)
    assert sorted(hf["reduced"]) == ["num_experts", "num_hidden_layers",
                                     "vocab_size"]
    assert hf["share_of"] and hf["stands_for"]
    assert hf["experts_held"] == {"start": 0, "count": 64, "of": 512}
    assert hf["vocab_size"] * 8 == hf["reduced"]["vocab_size"]["published"]
    for key in ("state_dtype", "column_order", "norm_scales", "decay",
                "no_mtp", "head_padding", "weights", "state_slots", "kv_pool",
                "max_tracked_sequences", "max_seq_len"):
        assert hf["assumed"][key]


_MISTRAL = {"architectures": ["MistralForCausalLM"], "hidden_size": 64,
            "intermediate_size": 128, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": 2,
            "vocab_size": 64}


@pytest.mark.parametrize("what,hf,match", [
    ("a latent key qwen3_next does not read", dict(HF, kv_lora_rank=32),
     "does not read"),
    ("an expert bias qwen3_next does not read",
     dict(HF, use_expert_bias=True), "does not read"),
    ("linear-attention keys under another architecture",
     dict(_MISTRAL, linear_num_value_heads=8), "does not read"),
    ("a shared expert under another architecture",
     dict(_MISTRAL, shared_expert_intermediate_size=64), "does not read"),
    ("dense layers among the routed", dict(HF, mlp_only_layers=[0]),
     "mlp_only_layers"),
    ("a kind the family does not have",
     dict(HF, layer_types=["conv"] * 8), "layer_types names"),
    ("a shared expert that is no multiple of an expert",
     dict(HF, shared_expert_intermediate_size=100), "no multiple"),
])
def test_what_the_mapping_cannot_serve_is_an_error(what, hf, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(hf)


def test_layer_types_are_the_interval_or_as_named():
    by_interval = config_from_hf(HF)
    named = config_from_hf(dict(HF, layer_types=(
        ["linear_attention"] * 3 + ["full_attention"]) * 2))
    assert by_interval.layer_types == named.layer_types
    assert by_interval.experts_held == (8, 8) and by_interval.n_experts == 16


def test_the_kinds_a_layer_can_be_come_from_one_tuple():
    assert T.LAYER_KINDS == ("attention", "conv", "linear_attention",
                             "state_space", "experts")
    with pytest.raises(ValueError, match=r"one of \('attention', 'conv', "
                       r"'linear_attention', 'state_space', 'experts'\)"):
        T.TransformerConfig(n_layers=2, layer_types=("attention", "mamba"))
    with pytest.raises(ValueError, match="gdn_key_heads"):
        T.TransformerConfig(n_layers=2, conv_kernel=4, layer_types=(
            "attention", "linear_attention"))


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


# -- the delta rule: chunked = recurrent, the step over runs ---------------

def _delta_inputs(rng, *lead, H=8, Dk=32, Dv=128):
    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(normal(*lead, H, Dk)) * Dk ** -0.5, unit(normal(*lead, H, Dk)),
            normal(*lead, H, Dv), -jnp.exp(normal(*lead, H)) * 0.3,
            jax.nn.sigmoid(normal(*lead, H)))


@pytest.mark.parametrize("tokens,chunk", [(1, 64), (5, 64), (64, 64),
                                          (70, 64), (131, 64), (37, 16),
                                          (23, 7)])
def test_the_chunked_form_is_the_recurrence(rng, tokens, chunk):
    """Lengths that are and are not multiples of the chunk, from a
    state that is not zero: outputs and the state left behind."""
    args = _delta_inputs(rng, 2, tokens)
    state = jnp.asarray(rng.normal(size=(2, 8, 32, 128)), jnp.float32)
    o1, s1 = GD.gated_delta_recurrent(*args, state)
    o2, s2 = GD.gated_delta_chunked(*args, state, chunk=chunk)
    np.testing.assert_allclose(o2, o1, atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-5)


def test_a_padded_prompt_leaves_the_state_of_its_last_real_token(rng):
    """_recur_prompts: prompts of 9 and 30 tokens padded to 32, a pad
    prompt beside them; each slot gets the state after its prompt's own
    last token, the others are not touched."""
    q, k, v, g, beta = _delta_inputs(rng, 3, 32)
    pool = jnp.full((5, 8, 32, 128), 7.0)
    n_real = jnp.asarray([9, 30, 0], jnp.int32)
    slots = jnp.asarray([2, 0, -1], jnp.int32)
    o, new = M._recur_prompts("linear_attention", (q, k, v, g, beta), pool,
                              slots, n_real, None)
    for i, (n, slot) in enumerate([(9, 2), (30, 0)]):
        want_o, want_s = GD.gated_delta_recurrent(
            q[i:i + 1, :n], k[i:i + 1, :n], v[i:i + 1, :n], g[i:i + 1, :n],
            beta[i:i + 1, :n])
        np.testing.assert_allclose(o[i, :n], want_o[0], atol=2e-5)
        np.testing.assert_allclose(new[slot], want_s[0], atol=2e-5)
    assert (np.asarray(new[1]) == 7).all() and (np.asarray(new[3]) == 7).all()


def _ragged_rows(rng):
    """A step's rows: a run of five from a slot's state (positions
    5..9), a decode row, a pad row, a run of three from position 0 (the
    slot's NaN must not be read), another pad row."""
    slots = jnp.asarray([3, 3, 3, 3, 3, 1, -1, 0, 0, 0, -1], jnp.int32)
    pos = jnp.asarray([5, 6, 7, 8, 9, 12, 0, 0, 1, 2, 0], jnp.int32)
    pool = jnp.asarray(rng.normal(size=(6, 8, 32, 128)), jnp.float32)
    pool = pool.at[0].set(jnp.nan)
    return _delta_inputs(rng, 11), pool, slots, pos


def _check_step(step, rng):
    (q, k, v, g, beta), pool, slots, pos = _ragged_rows(rng)
    o, new = step(q, k, v, g, beta, pool, slots, pos)
    for rows, slot, start in ((slice(0, 5), 3, pool[3]), (slice(5, 6), 1,
                                                          pool[1]),
                              (slice(7, 10), 0, None)):
        want_o, want_s = GD.gated_delta_recurrent(
            q[None, rows], k[None, rows], v[None, rows], g[None, rows],
            beta[None, rows], None if start is None else start[None])
        np.testing.assert_allclose(o[rows], want_o[0], atol=2e-5)
        np.testing.assert_allclose(new[slot], want_s[0], atol=2e-5)
    # the slots of no row of this step are as they were
    np.testing.assert_array_equal(new[2], pool[2])
    np.testing.assert_array_equal(new[4], pool[4])


def test_the_step_over_runs_is_a_segmented_recurrence(rng):
    _check_step(GD.gated_delta_step_xla, rng)


@pytest.mark.usefixtures("pallas_interpret")
def test_the_step_kernel_matches_the_recurrence(rng):
    pool = jax.ShapeDtypeStruct((6, 8, 32, 128), jnp.float32)
    assert GD.step_fits(11, pool)
    assert not GD.step_fits(11, jax.ShapeDtypeStruct((6, 8, 32, 64),
                                                     jnp.float32))
    _check_step(GD.gated_delta_step, rng)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("pattern,walk", W.CASES)
def test_the_walk_over_a_steps_rows(rng, monkeypatch, pattern, walk):
    """The kernel's own copies (tests/_state_walk.py: the rows'
    patterns, the walk's batches) against the loop over rows in XLA."""
    shape = (W.SLOTS + 1, 3, 8, 128)
    W.set_walk(monkeypatch, walk, shape)
    W.check_walk(GD.gated_delta_step, GD.gated_delta_step_xla,
                 lambda rng, n: _delta_inputs(rng, n, H=3, Dk=8), shape,
                 pattern, rng)


@pytest.mark.parametrize("what,n_rows,shape,dtype,fits", [
    # two slots in VMEM is the least: a batch of one run, twice
    ("the cell's", 256, (257, 32, 128, 128), jnp.float32, True),
    ("slots of 24 MiB", 8, (5, 96, 128, 512), jnp.float32, True),
    ("slots of 26 MiB", 8, (5, 104, 128, 512), jnp.float32, False),
    ("bfloat16 state", 8, (5, 8, 32, 128), jnp.bfloat16, False),
    ("half a lane row", 8, (5, 8, 32, 64), jnp.float32, False),
    ("the rows' scalars past scalar memory", 2048, (5, 32, 128, 128),
     jnp.float32, False),
])
def test_step_fits(what, n_rows, shape, dtype, fits):
    assert GD.step_fits(n_rows, jax.ShapeDtypeStruct(shape, dtype)) is fits


@pytest.mark.usefixtures("pallas_interpret")
def test_the_engine_with_kernels_matches_the_reference(model):
    """decode_impl 'auto' under the interpreter resolves the kernels:
    the step kernel on the aliased pool, the packed D = 64 walk and
    write in the attention layers."""
    eng = _engine(model)
    assert eng.resolved_impl == "pallas"
    got, want, _, _ = _feeds(model, eng, [37, 45], [5], 3, seed=4)
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


def _step_text(eng):
    return eng._decode_fn(8, False).lower(
        eng.params, eng.cache, *(eng._dev(np.zeros(s, np.int32)) for s in
                                 ((8,), (8, eng.config.blocks_per_seq), (8,))),
        *eng.state_args(np.zeros((8,), np.int32))).as_text(debug_info=True)


@pytest.mark.usefixtures("pallas_interpret")
def test_the_convolution_kernel_serves_what_the_xla_path_serves(model):
    """The step program with its convolutions as the one-pass kernel
    (ops/pallas/conv_carry.py, under `gdn_conv`) against decode_impl
    'xla' (_carry_rows + _depthwise): the same logits over a prefill, a
    chunk and single steps, the same served tokens, and every step of
    the schedule counted where the kernel ran and none where it did
    not. (One width of program throughout: the interpreter's kernels
    are slow to trace.)"""
    eng, xla = _sched_engine(model), _sched_engine(model, decode_impl="xla")
    assert eng.resolved_impl == "pallas" and eng.carry_kernel(8)
    assert xla.resolved_impl == "xla" and not xla.carry_kernel(8)
    assert eng.step_kernel(8) and not xla.step_kernel(8)
    assert "linear_attention/gdn_conv/jit(_conv_carry)" in _step_text(eng)
    assert "jit(_conv_carry)" not in _step_text(xla)
    got, want, _, _ = _feeds(model, eng, [21], [5], 2, seed=6)
    oracle, _, _, _ = _feeds(model, xla, [21], [5], 2, seed=6)
    assert np.abs(got - oracle).max() < LOGITS_ATOL
    assert np.abs(got - want).max() < LOGITS_ATOL
    requests = [(p[:12], 3) for p, _ in _requests(2, seed=8)]
    s, served = _serve(eng, requests, max_num_batched_tokens=8)
    sx, served_xla = _serve(xla, requests, max_num_batched_tokens=8)
    assert served == served_xla
    assert s.counters["state_carry_kernel_steps"] == s.counters["steps"] > 0
    assert sx.counters["state_carry_kernel_steps"] == 0 < sx.counters["steps"]
    # and the matrices through `gdn_state`, each step counted once
    assert s.counters["state_step_kernel_steps"] == s.counters["steps"]
    assert sx.counters["state_step_kernel_steps"] == 0


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
    assert d["lookahead_steps"] > 0  # the slot is updated in program order
    # a slot: 6 DeltaNet layers x (8 matrices of 32 x 128 + 3 inputs of
    # 2 x 4 x 32 + 8 x 128 = 1,280 channels in a slot of 2,048: whole
    # (8, 128) tiles), float32
    assert eng.state_slot_bytes == 6 * 4 * (8 * 32 * 128 + 3 * 2048)
    assert d["state_bytes_moved"] % (2 * eng.state_slot_bytes) == 0
    assert d["state_bytes_moved"] >= 2 * eng.state_slot_bytes * d["steps"]
    # every prompt went in as chunks of up to 8: all its tokens but a
    # last chunk of one are rows of runs
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


# -- the share of an expert-parallel deployment ----------------------------

def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(model):
    """Guide section 4: the routed parts that eight shares of two
    experts give, with what every chip computes alike (the shared
    expert) counted ONCE, add up to what the uncut reference gives for
    the whole layer."""
    _, params = model
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(24, 128)), jnp.float32)
    key = jax.random.PRNGKey(9)
    lw = {"w_router": params["layers"]["w_router"][0],
          "ws_gate": params["layers"]["ws_gate"][0],
          "ws_in": params["layers"]["ws_in"][0],
          "ws_out": params["layers"]["ws_out"][0],
          "ws_sgate": params["layers"]["ws_sgate"][0]}
    full = {k: 0.08 * jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, (k, shape) in enumerate(
                {"w_gate": (16, 128, 64), "w_in": (16, 128, 64),
                 "w_out": (16, 64, 128)}.items())}
    uncut_hf = {k: v for k, v in HF.items()
                if k not in ("reduced", "experts_held")}
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.moe(n, dict(lw, **full), dict(uncut_hf, num_experts=16))
        shared = ref._swiglu(n, lw["ws_gate"], lw["ws_in"], lw["ws_out"]) \
            * jax.nn.sigmoid(n @ lw["ws_sgate"])
        parts = []
        for share in range(8):
            cfg = config_from_hf(dict(
                HF, num_experts=2, experts_held={"start": 2 * share},
                reduced={"num_experts": {"published": 16, "here": 2}}))
            assert cfg.experts_held == (2 * share, 2)
            lp = dict(lw, **{k: w[2 * share:2 * share + 2]
                             for k, w in full.items()})
            parts.append(M._mlp(n, lp, cfg) - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    assert float(jnp.abs(whole - shared).max()) > 0.01  # the routed part counts


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


def test_the_scheduler_refuses_speculation(model):
    with pytest.raises(NotImplementedError, match="speculation"):
        ServingScheduler(_engine(model), ServingSchedulerConfig(warmup=False),
                         speculative={"ngram": 2, "draft_len": 3})


def test_pools_that_exceed_the_device_are_refused_with_the_three_numbers(
        model, monkeypatch):
    """A state slot costs megabytes: an engine whose weights + K/V +
    state pools exceed the device's own limit is refused where it is
    built, naming the three; a backend that states no limit (the CPU)
    refuses nothing, and so does a model without state."""
    pools = {"kv": 806_092_800, "state": 4_963_958_784}
    E.refuse_pools_beyond(None, 5_858_748_800, pools)
    E.refuse_pools_beyond(16 * 10 ** 9, 5_858_748_800, pools)
    with pytest.raises(ValueError, match=r"weights 5.86 GB \+ K/V pools "
                       r"0.81 GB \+ state pools 4.96 GB = 11.63 GB of "
                       r"10.00 GB"):
        E.refuse_pools_beyond(10 * 10 ** 9, 5_858_748_800, pools)
    E.refuse_pools_beyond(10 ** 9, 5_858_748_800, {"kv": 9 * 10 ** 9,
                                                   "state": 0})
    mcfg, params = model

    class Small:
        def memory_stats(self):
            return {"bytes_limit": 2_000_000, "bytes_in_use": 0}

    real = jax.local_devices
    monkeypatch.setattr(jax, "local_devices", lambda: [Small()])
    with pytest.raises(ValueError, match="does not fit the device"):
        _engine((mcfg, params))
    monkeypatch.setattr(jax, "local_devices", real)
    _engine((mcfg, params), max_tracked_sequences=2)


def test_the_scopes_of_the_operator_are_in_the_program(model):
    text = _step_text(_engine(model))
    for scope in ("linear_attention/gdn_project", "linear_attention/gdn_conv",
                  "linear_attention/gdn_state", "linear_attention/gdn_out",
                  "attention/attn_gate", "mlp/moe_shared"):
        assert scope in text, scope


# -- head dim 256, two KV heads, and the kernel at the cell's shapes -------

@pytest.mark.usefixtures("pallas_interpret")
def test_the_walk_at_head_dim_256_matches_the_oracle(rng):
    S, H, KV, D, bs, NB = 5, 16, 2, 256, 16, 4
    ctx = np.asarray([1, 17, 40, 64, 0], np.int32)
    NBLK = S * NB + 1
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(NBLK - 1)[:S * NB].reshape(S, NB),
                      jnp.int32)
    got = PA.paged_decode_attention(q, kc, vc, tbl, jnp.asarray(ctx))
    want = PA.paged_decode_attention_xla(q, kc, vc, tbl, jnp.asarray(ctx))
    np.testing.assert_allclose(got[:4], want[:4], atol=2e-5)


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


def _kernels(text):
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def test_the_step_kernel_compiles_for_v5e_at_the_cells_shapes(one_chip):
    """256 rows of 32 heads of 128 x 128 over a pool of 257 slots,
    aliased in and out (no second 539 MB pool among the temporaries)."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, pool = 256, sds((257, 32, 128, 128))
    assert GD.step_fits(rows, pool)
    compiled = jax.jit(GD.gated_delta_step, donate_argnums=(5,)).lower(
        sds((rows, 32, 128)), sds((rows, 32, 128)), sds((rows, 32, 128)),
        sds((rows, 32)), sds((rows, 32)), pool, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)).compile()
    calls = _kernels(compiled.as_text())
    assert len(calls) == 1 and "gdn_state" in calls[0]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 257 * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 64 << 20


def test_the_walk_and_write_compile_for_v5e_at_head_dim_256(one_chip):
    """16 query / 2 KV heads of 256 over pools [1025, 128, 2, 256], a
    table of 32 slots a row, 256 rows."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, pool = 256, sds((1025, 128, 2, 256), jnp.bfloat16)
    q, new = sds((rows, 16, 256), jnp.bfloat16), sds((rows, 2, 256),
                                                     jnp.bfloat16)
    table, ints = sds((rows, 32), jnp.int32), sds((rows,), jnp.int32)

    def fn(q, kc, vc, kn, vn, table, ctx, slots):
        kc, vc = PA.paged_kv_write(kc, vc, kn, vn, slots)
        return PA.paged_decode_attention(q, kc, vc, table, ctx), kc, vc

    text = jax.jit(fn, donate_argnums=(1, 2)).lower(
        q, pool, pool, new, new, table, ints, ints).compile().as_text()
    for name in ("paged_decode_grid", "paged_kv_write"):
        assert any(name in line for line in _kernels(text)), name
