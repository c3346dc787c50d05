"""Qwen3-Next (`qwen3_next`) on the normal serving path at a tiny size
on the CPU, against the plain float32 reference of
benchmarks/reference/qwen3_next.py: Gated DeltaNet layers whose
sequences carry a float32 matrix a value head (and the last three
inputs of a convolution) in a state slot, gated attention layers that
alone hold K/V, a held share of routed experts beside a gated shared
expert; through whole-prompt prefill (the chunked scan), chunks and
single steps (the segmented recurrence), through the scheduler with
slots reused and never cleared; the eight shares that add up to the
uncut layer, the mutants that must fail, the refusals, and the cut's
file; then the delta rule alone (the chunked form, the step kernel, its
walk, the kernels at the cell's shapes). The contract every served
family is held to is tests/_family.py's.

Everything is float32 with seeded weights: two periods of (DeltaNet,
DeltaNet, DeltaNet, attention), d 128, 4 key / 8 value heads of 32 /
128, 4 query / 2 KV heads of 64 (16 rotated), 16 experts of 64 top-4 of
which experts 8..15 are held.
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import _state_walk as W
import pytest
from _family import (  # noqa: F401 - the contract's fixtures and cases, collected here
    engines,
    family,
    model,
    one_chip,
    pytest_generate_tests,
    served,
    test_a_chunk_boundary_at_every_offset,
    test_a_wrong_model_fails_the_written_tolerance,
    test_prefill_chunks_and_single_steps_match_the_reference,
    test_preemption_recomputes_to_identical_tokens,
    test_the_cuts_file_keeps_the_published_widths,
    test_the_engine_refuses_at_build,
    test_the_engine_with_kernels_matches_the_reference,
    test_the_training_forward_refuses_the_family,
    test_what_the_mapping_cannot_serve_is_an_error,
    test_whole_prompt_waves_and_fused_decode_carry_the_state,
)

from benchmarks.reference import qwen3_next as ref
from deepspeed_tpu.inference import ServingScheduler, ServingSchedulerConfig
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import gated_delta as GD
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

CUT = F.BENCH / "configs/qwen3-next-80b-a3b-serve-l12-ep8.json"
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


def _jig(k, v, key):
    """Every norm scale, tap and decay matters."""
    if "scale" in k:
        return 1 + 0.3 * jax.random.normal(key, v.shape)
    if k == "gdn_taps":
        return 0.6 * jax.random.normal(key, v.shape)
    if k in ("gdn_a_log", "gdn_dt_bias"):
        # decays from 0.3 to 0.97 a token: long and short memory
        return jax.random.uniform(key, v.shape, minval=-3.0, maxval=0.5)
    if k == "gdn_ba":
        return 0.3 * jax.random.normal(key, v.shape)
    return v


def test_what_only_this_cut_states():
    hf = json.loads(CUT.read_text())
    assert hf["vocab_size"] * 8 == hf["reduced"]["vocab_size"]["published"]


FAMILY = F.Family(
    hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE, jig=_jig,
    spread=1.7, run_tokens="gdn_run_tokens", cut=CUT,
    reduced=("num_experts", "num_hidden_layers", "vocab_size"),
    held={"start": 0, "count": 64, "of": 512},
    assumed=("state_dtype", "column_order", "norm_scales", "decay", "no_mtp",
             "head_padding", "weights", "state_slots", "kv_pool",
             "max_tracked_sequences", "max_seq_len"),
    unservable=(
        ("a latent key qwen3_next does not read", dict(HF, kv_lora_rank=32),
         "does not read"),
        ("an expert bias qwen3_next does not read",
         dict(HF, use_expert_bias=True), "does not read"),
        ("linear-attention keys under another architecture",
         dict(F.MISTRAL, linear_num_value_heads=8), "does not read"),
        ("a shared expert under another architecture",
         dict(F.MISTRAL, shared_expert_intermediate_size=64), "does not read"),
        ("dense layers among the routed", dict(HF, mlp_only_layers=[0]),
         "mlp_only_layers"),
        ("a kind the family does not have",
         dict(HF, layer_types=["conv"] * 8), "layer_types names"),
        ("a shared expert that is no multiple of an expert",
         dict(HF, shared_expert_intermediate_size=100), "no multiple"),
    ))


# -- the configuration ---------------------------------------------------

def test_the_cut_builds_at_published_widths():
    hf, cfg = F.cut_of(FAMILY)
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
    flat = F.one_stack(cfg, shapes)
    # the file's own count, every leaf
    assert sum(int(np.prod(s.shape)) for s in flat.values()) == 2_929_374_400
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
    hf, cfg = F.cut_of(FAMILY)
    lp = F.expert_stacks(64, 2048, 512)
    assert {M.expert_path(t, cfg, lp, True) for t in (8, 256, 640)} == \
        {"stream"}
    assert M.expert_path(256, cfg, lp, False) == "scan"


def test_layer_types_are_the_interval_or_as_named():
    by_interval = config_from_hf(HF)
    named = config_from_hf(dict(HF, layer_types=(
        ["linear_attention"] * 3 + ["full_attention"]) * 2))
    assert by_interval.layer_types == named.layer_types
    assert by_interval.experts_held == (8, 8) and by_interval.n_experts == 16


def test_the_kinds_a_layer_can_be_come_from_one_tuple():
    assert T.LAYER_KINDS == ("attention", "conv", "linear_attention",
                             "state_space", "experts", "selective_scan",
                             "gated_memory", "cross_attention")
    with pytest.raises(ValueError, match=r"one of \('attention', 'conv', "
                       r"'linear_attention', 'state_space', 'experts', "
                       r"'selective_scan', 'gated_memory', "
                       r"'cross_attention'\)"):
        T.TransformerConfig(n_layers=2, layer_types=("attention", "mamba"))
    with pytest.raises(ValueError, match="gdn_key_heads"):
        T.TransformerConfig(n_layers=2, conv_kernel=4, layer_types=(
            "attention", "linear_attention"))


# -- the engine against the reference -------------------------------------

@pytest.mark.usefixtures("pallas_interpret")
def test_the_convolution_kernel_serves_what_the_xla_path_serves(model,
                                                                engines):
    """The step program with its convolutions as the one-pass kernel
    (ops/pallas/conv_carry.py, under `gdn_conv`) against decode_impl
    'xla' (_carry_rows + _depthwise): the same logits over a prefill, a
    chunk and single steps, the same served tokens, and every step of
    the schedule counted where the kernel ran and none where it did
    not. (One width of program throughout: the interpreter's kernels
    are slow to trace.)"""
    eng, xla = engines(), engines(decode_impl="xla")
    assert eng.resolved_impl == "pallas" and eng.carry_kernel(8)
    assert xla.resolved_impl == "xla" and not xla.carry_kernel(8)
    assert eng.step_kernel(8) and not xla.step_kernel(8)
    assert "linear_attention/gdn_conv/jit(_conv_carry)" in F.step_text(eng)
    assert "jit(_conv_carry)" not in F.step_text(xla)
    # (the shapes of the contract's kernels case: compiled for `eng`)
    got, want, _, _ = F.feeds(FAMILY, model, eng, [32, 36], [4], 2, seed=6)
    oracle, _, _, _ = F.feeds(FAMILY, model, xla, [32, 36], [4], 2, seed=6)
    assert np.abs(got - oracle).max() < LOGITS_ATOL
    assert np.abs(got - want).max() < LOGITS_ATOL
    requests = [(p[:12], 3) for p, _ in F.requests(FAMILY, 2, seed=8)]
    s, served = F.serve(eng, requests, max_num_batched_tokens=8)
    sx, served_xla = F.serve(xla, requests, max_num_batched_tokens=8)
    assert served == served_xla
    assert s.counters["state_carry_kernel_steps"] == s.counters["steps"] > 0
    assert sx.counters["state_carry_kernel_steps"] == 0 < sx.counters["steps"]
    # and the matrices through `gdn_state`, each step counted once
    assert s.counters["state_step_kernel_steps"] == s.counters["steps"]
    assert sx.counters["state_step_kernel_steps"] == 0


# -- through the scheduler: slots taken, reused, never cleared -------------

def test_a_slot_is_handed_on_with_no_clearing(model, engines):
    """12 requests of unequal lengths through 6 slots: every slot is
    handed on to a later sequence, and what the last one left in it
    (here: NaN, put there before the first admission too, in the
    matrices AND the carried inputs) never reaches the next."""
    eng = engines.sched()
    d, requests = F.through_reused_slots(FAMILY, model, eng)
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
    F.shares_of_two_add_up(FAMILY, "num_experts", n, lw, full, whole, shared)


# -- what cannot be right yet is refused where it is built ----------------

def test_the_scheduler_refuses_speculation(engines):
    with pytest.raises(NotImplementedError, match="speculation"):
        ServingScheduler(engines(), ServingSchedulerConfig(warmup=False),
                         speculative={"ngram": 2, "draft_len": 3})


def test_pools_that_exceed_the_device_are_refused_with_the_three_numbers(
        model, engines, monkeypatch):
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
    # its own two: the build reads the patched device's limit
    with pytest.raises(ValueError, match="does not fit the device"):
        engines.fresh()
    monkeypatch.setattr(jax, "local_devices", real)
    engines.fresh(max_tracked_sequences=2)


def test_the_scopes_of_the_operator_are_in_the_program(engines):
    text = F.step_text(engines())
    for scope in ("linear_attention/gdn_project", "linear_attention/gdn_conv",
                  "linear_attention/gdn_state", "linear_attention/gdn_out",
                  "attention/attn_gate", "mlp/moe_shared"):
        assert scope in text, scope


# -- the delta rule alone --------------------------------------------------

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
    # (each form ONE program: op by op the chunked form is some forty
    # small compiles a case)
    o1, s1 = jax.jit(GD.gated_delta_recurrent)(*args, state)
    o2, s2 = jax.jit(functools.partial(GD.gated_delta_chunked, chunk=chunk))(
        *args, state)
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


def _check_step(step, rng):
    pool, slots, pos, runs = W.ragged(rng, (6, 8, 32, 128))
    q, k, v, g, beta = _delta_inputs(rng, 11)
    o, new = jax.jit(step)(q, k, v, g, beta, pool, slots, pos)
    for rows, slot, start in runs:
        want_o, want_s = jax.jit(GD.gated_delta_recurrent)(
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


# -- head dim 256, two KV heads, and the kernel at the cell's shapes -------

@pytest.mark.usefixtures("pallas_interpret")
def test_the_walk_at_head_dim_256_matches_the_oracle(rng):
    F.walk_matches_the_oracle(rng, H=16, KV=2, D=256)


def test_the_step_kernel_compiles_for_v5e_at_the_cells_shapes(one_chip):
    """256 rows of 32 heads of 128 x 128 over a pool of 257 slots,
    aliased in and out (no second 539 MB pool among the temporaries)."""
    sds = F.on_chip(one_chip, jnp.float32)
    rows, pool = 256, sds((257, 32, 128, 128))
    assert GD.step_fits(rows, pool)
    F.compiles_one_aliased_kernel(GD.gated_delta_step, (
        sds((rows, 32, 128)), sds((rows, 32, 128)), sds((rows, 32, 128)),
        sds((rows, 32)), sds((rows, 32)), pool, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)), 5, "gdn_state")


def test_the_walk_and_write_compile_for_v5e_at_head_dim_256(one_chip):
    """16 query / 2 KV heads of 256 over pools [1025, 128, 2, 256], a
    table of 32 slots a row, 256 rows."""
    F.walk_and_write_compile(one_chip, 256, 16, 2, 256, (1025, 128, 2, 256))
