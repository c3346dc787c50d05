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

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import pytest
from _family import (  # noqa: F401 - the contract's fixtures and cases, collected here
    engines,
    family,
    model,
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

from benchmarks.kernels import olmo_hybrid as shapes
from benchmarks.reference import olmo_hybrid as ref
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import gated_delta as GD
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

BENCH = F.BENCH
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
def _jig(k, v, key):
    """Every norm scale, tap, decay and write strength matters."""
    if "scale" in k:
        return 1 + 0.3 * jax.random.normal(key, v.shape)
    if k == "gdn_taps":
        return 0.6 * jax.random.normal(key, v.shape)
    if k in ("gdn_a_log", "gdn_dt_bias"):
        # decays from 0.3 to 0.97 a token: long and short memory
        return jax.random.uniform(key, v.shape, minval=-3.0, maxval=0.5)
    if k == "gdn_ba":
        # b up to +-3: write strengths from 0.1 to 1.9
        return 1.2 * jax.random.normal(key, v.shape)
    return v


def test_what_only_this_cut_states():
    hf = json.loads(CUT.read_text())
    published = json.loads(PUBLISHED.read_text())
    # three whole periods, the published 3 : 1
    assert hf["layer_types"] == published["layer_types"][:12]
    assert hf["stands_for"]
    # every key of the catalog row's config, under the same name
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"Olmo-Hybrid-7B"' in line)
        assert {k: v for k, v in published.items()
                if not k.startswith("_")} == row["config"]
        assert published["_source"] == hf["source"] == row["source_url"]


FAMILY = F.Family(
    hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE, jig=_jig, spread=1.7,
    training_refuses="output_norm", run_tokens="gdn_run_tokens",
    cut=CUT,
    reduced=("layer_types", "num_hidden_layers"),
    assumed=("head_dim", "norm_placement", "qk_norm", "no_positions",
             "delta_net", "write_strength", "column_order", "state_dtype",
             "state_layout", "decay", "weights", "state_slots", "kv_pool",
             "max_tracked_sequences", "max_seq_len"),
    unservable=(
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
         dict(F.MISTRAL, linear_allow_neg_eigval=True), "does not read"),
    ))


# -- the configuration ---------------------------------------------------

def test_the_cut_builds_at_published_widths():
    hf, cfg = F.cut_of(FAMILY)
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
    flat = F.one_stack(cfg, tree)
    # the engine's tree against the benchmark's own count, every leaf
    assert sum(int(np.prod(s.shape)) for s in flat.values()) == \
        shapes.parameters(hf) == 3_268_268_508
    # the cache: K/V for the attention layers alone, 30 heads of 128 as
    # 2 heads of 1,920 (kv_pack: whole tiles, nothing padded, the
    # published 15,360 B a token a layer); two pools a DeltaNet layer,
    # the matrices' with the pad rows' slot
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, 561, 128, jnp.bfloat16, state_slots=128))
    assert [a.shape for a in cache.k] == [(561, 128, 2, 1920)] * 3
    assert [tuple((a.shape, a.dtype) for a in pools)
            for pools in cache.state] == [
        (((129, 15, 96, 384), jnp.float32),
         ((128, 3, 96, 128), jnp.bfloat16))] * 9
    # a token NEEDS 46,080 B of K/V over the three layers and holds
    # just that
    assert 3 * shapes.kv_bytes_per_token_per_layer(hf) == 46_080
    assert sum(a.size * 2 for a in cache.k + cache.v) / 561 / 128 == 46_080
    # what the engine counts before it allocates: 3.31 + 2.65 GB
    pools = E.pool_bytes(cfg, E.InferenceConfig(**hf["serve"]["engine"]),
                         jnp.bfloat16)
    assert pools == {"kv": 561 * 128 * 46_080,
                     "state": 9 * (129 * 2_211_840 + 128 * 73_728)}


def test_the_published_file_builds_the_whole_model():
    hf = {k: v for k, v in json.loads(PUBLISHED.read_text()).items()
          if not k.startswith("_")}
    cfg = config_from_hf(hf)
    assert (cfg.n_layers, cfg.n_kv_layers, cfg.n_state_layers) == (32, 8, 24)
    assert cfg.max_seq == 65536
    assert T.param_count(cfg) == shapes.parameters(hf) == \
        24 * 215_570_172 + 8 * 185_809_920 + 770_703_360 + 3_840


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


# -- the engine against the reference -------------------------------------

def test_a_packed_pool_serves_the_same_as_the_models_own_shape(
        model, engines, monkeypatch):
    """The published 30 KV heads of 128 lie 15 side by side in 2 heads
    of 1,920 (kv_pack: the layout would pad them to 32). Here the 4
    heads of 32 as 2 of 64, in float32 with no kernel: the new rows
    reach the pool by a reshape and the queries attend it as the same
    row-major bytes, through prefill, chunks and single steps, and
    through the scheduler; the pool's bytes are the unpacked pool's."""
    plain = engines.fresh()
    assert [a.shape for a in plain.cache.k] == [(49, 32, 4, 32)] * 2
    monkeypatch.setattr(M, "kv_pack", lambda kv, d, itemsize: 2)
    eng = engines.fresh()  # its own: built under the patch
    assert [a.shape for a in eng.cache.k] == [(49, 32, 2, 64)] * 2
    fed = ([32, 36], [4], 3)
    got, want, _, _ = F.feeds(FAMILY, model, eng, *fed, seed=4)
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)
    own, _, _, _ = F.feeds(FAMILY, model, plain, *fed, seed=4)
    np.testing.assert_allclose(got, own, atol=1e-5)
    for packed, unpacked in zip(eng.cache.k + eng.cache.v,
                                plain.cache.k + plain.cache.v):
        np.testing.assert_array_equal(packed.reshape(-1),
                                      unpacked.reshape(-1))
    requests = F.requests(FAMILY, 4, seed=9)  # four: within its six slots
    s, outputs = F.serve(eng, requests)
    F.greedy_by_the_reference(FAMILY, model, requests, outputs)
    # the scheduler read the packing off the pool: two heads' queries a group
    assert s.counters["kv_block_reads"] <= s.counters["kv_live_blocks"]


def test_the_scopes_of_the_layer_are_in_the_program(engines):
    """The operator keeps its four scopes; the output norms have scopes
    of their own name, OUTSIDE the operator's and the FFN's (a reader
    sums by the outermost match); no norm stands before a sublayer."""
    text = F.step_text(engines())
    for scope in ("linear_attention/gdn_project", "linear_attention/gdn_conv",
                  "linear_attention/gdn_state", "linear_attention/gdn_out",
                  "jit(step)/norm1_post", "jit(step)/norm2_post",
                  "jit(step)/attention", "jit(step)/mlp"):
        assert scope in text, scope
    for scope in ("/norm1/", "/norm2/", "attention/norm1_post",
                  "linear_attention/norm1_post", "mlp/norm2_post"):
        assert scope not in text, scope


@pytest.mark.usefixtures("pallas_interpret")
def test_the_warm_up_says_which_delta_rule_the_step_compiled(engines, caplog):
    """`state_step kernel` or `state_step xla` in the line of each
    decode program and on its kept span: a silent fall-back to the loop
    over rows is seen in a run's own log."""
    from deepspeed_tpu.utils import profiler

    for eng, said in ((engines(), "kernel"),
                      (engines(decode_impl="xla"), "xla")):
        # (the module's engines: a warm-up names every program it runs,
        # compiled before or not)
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

def test_a_slot_is_handed_on_with_no_clearing(model, engines):
    """12 requests of unequal lengths through 6 slots: every slot is
    handed on to a later sequence, and what the last one left in it
    (here: NaN, put there before the first admission too, in the
    matrices AND the carried inputs) never reaches the next."""
    eng = engines.sched()
    d, requests = F.through_reused_slots(FAMILY, model, eng)
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


@pytest.mark.usefixtures("pallas_interpret")
def test_the_kernels_serve_what_the_xla_path_serves(engines):
    """The scheduler's steps through `gdn_state` on paired heads and
    the convolution's one pass against decode_impl 'xla': the same
    served tokens, every step counted where the kernel ran."""
    eng, xla = engines(), engines(decode_impl="xla")
    requests = [(p[:12], 3) for p, _ in F.requests(FAMILY, 2, seed=8)]
    s, served = F.serve(eng, requests, max_num_batched_tokens=8)
    sx, served_xla = F.serve(xla, requests, max_num_batched_tokens=8)
    assert served == served_xla
    assert s.counters["state_step_kernel_steps"] == s.counters["steps"] > 0
    assert sx.counters["state_step_kernel_steps"] == 0 < sx.counters["steps"]
    assert "linear_attention/gdn_state/jit(_gated_delta_step)" in \
        F.step_text(eng)
    assert "jit(_gated_delta_step)" not in F.step_text(xla)
