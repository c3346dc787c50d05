"""Nemotron-H (`nemotron_h`) on the normal serving path at a tiny size
on the CPU, against the plain float32 reference of
benchmarks/reference/nemotron_h.py: layers that are ONE mixer each (a
Mamba-2 mixer whose B and C come in groups, attention WITHOUT positions,
or a routed block of ungated squared-relu experts beside an ungated
shared one, chosen by sigmoid scores plus a bias and scaled), of which
the mixers hold a state slot, the attention layers K/V and the routed
layers NOTHING; through whole-prompt prefill (the chunked scan a
group), chunks and single steps, through the scheduler with slots
reused; the grouped recurrence's three forms against the loop, the
ungated pass against a loop, the four shares that add up to the uncut
layer, the mutants that must fail, the importer and its refusals, the
cut's file, and the kernels compiled for a v5e at the cell's shapes.

Everything is float32 with seeded weights: MEM*EMEM, d 64, 16
state-space heads of 16 in 2 groups with a state of 32 (eight heads
side by side in a 128-lane row of the pool, a row inside one group), 4
query / 2 KV heads of 16, 8 experts of 24 (no lane tile) top-3 x 2.5 of
which experts 4..7 are held, a shared expert of 48.
"""

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
    test_prefix_credit_and_speculation_are_refused,
    test_the_cuts_file_keeps_the_published_widths,
    test_the_engine_refuses_at_build,
    test_the_engine_with_kernels_matches_the_reference,
    test_the_training_forward_refuses_the_family,
    test_what_the_mapping_cannot_serve_is_an_error,
    test_whole_prompt_waves_and_fused_decode_carry_the_state,
)

from benchmarks.reference import nemotron_h as ref
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import expert_stream as ES
from deepspeed_tpu.ops.pallas import ssm_state as SS
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

BENCH = F.BENCH
CUT = BENCH / "configs/nemotron-3-nano-30b-a3b-serve-l13-ep4.json"
PUBLISHED = BENCH / "configs/published/nemotron-3-nano-30b-a3b-bf16.json"
HF = {"attention_bias": False, "chunk_size": 16, "conv_kernel": 4,
      "expand": 2, "head_dim": 16, "hidden_size": 64,
      "hybrid_override_pattern": "MEM*EMEM", "intermediate_size": 48,
      "mamba_head_dim": 16, "mamba_hidden_act": "silu",
      "mamba_num_heads": 16, "mamba_proj_bias": False,
      "max_position_embeddings": 512, "mlp_bias": False,
      "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
      "moe_intermediate_size": 24,
      "moe_shared_expert_intermediate_size": 48, "n_group": 1,
      "n_groups": 2, "n_routed_experts": 4, "n_shared_experts": 1,
      "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 4,
      "num_experts_per_tok": 3, "num_hidden_layers": 8,
      "num_key_value_heads": 2, "rope_theta": 10000,
      "routed_scaling_factor": 2.5, "ssm_state_size": 32,
      "tie_word_embeddings": False, "topk_group": 1, "use_bias": False,
      "use_conv_bias": True, "vocab_size": 256,
      "reduced": {"n_routed_experts": {"published": 8, "here": 4}},
      "experts_held": {"start": 4, "count": 4, "of": 8}}

# float32 on both sides, logits up to ~10. The system reassociates (the
# fused QKV matmul, the chunked scan's matmuls against the recurrence,
# the expert scan's running sum over zero-padded columns, the taps' sum
# in another order), which moves a logit by under 4e-5 (measured here:
# 2.7e-5 over prefill, a chunk and single steps). The mutants differ by
# at least 300 x the limit.
LOGITS_ATOL = 1e-4
ENGINE = dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=48,
              max_batch_size=32, max_tracked_sequences=6,
              min_prefill_bucket=32)


def _jig(k, v, key):
    """Every norm scale, tap, bias, decay, skip and router bias
    matters."""
    if k == "ssm_d":
        return jax.random.uniform(key, v.shape, minval=0.4, maxval=0.7)
    if "scale" in k:
        return 1 + 0.3 * jax.random.normal(key, v.shape)
    if k == "ssm_taps":
        return 0.6 * jax.random.normal(key, v.shape)
    if k == "ssm_conv_bias":
        return 0.5 * jax.random.normal(key, v.shape)
    if k in ("attn_wq", "attn_wk"):
        return v * 6  # scores sharp enough for positions to matter
    if k in ("ssm_a_log", "ssm_dt_bias"):
        # decays from 0.3 to 0.97 a token: long and short memory
        return jax.random.uniform(key, v.shape, minval=-3.0, maxval=0.5)
    if k == "moe_expert_bias":
        return 0.3 * jax.random.normal(key, v.shape)  # moves choices
    if k in ("moe_w_in", "moe_ws_in"):
        return v * 3  # relu^2 of a small input is smaller still
    return v


def test_what_only_this_cut_states():
    hf = json.loads(CUT.read_text())
    assert hf["vocab_size"] * 4 == hf["reduced"]["vocab_size"]["published"]
    assert hf["hybrid_override_pattern"] == \
        hf["reduced"]["hybrid_override_pattern"]["published"][:13]
    sv = hf["serve"]
    assert sv["engine"]["max_batch_size"] == \
        sv["engine"]["max_tracked_sequences"] == 256
    assert sv["scheduler"] == {
        "max_num_batched_tokens": 512, "prefill_chunk": 32,
        "prefill_mode": "chunked", "decode_chunk": 1}


# ragged rows: the first chunk starts 1, 3 or 7 tokens before the
# prompt's end
FAMILY = F.Family(
    hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE, jig=_jig, spread=2.0,
    far=300, chunks=(1, 3, 7), training_refuses="mixer_only",
    run_tokens="ssm_run_tokens", cut=CUT,
    with_kernels=(True, True, "state_space/ssm_state/jit(_ssm_step)"),
    reduced=("hybrid_override_pattern", "n_routed_experts",
             "num_hidden_layers", "vocab_size"),
    held={"start": 0, "count": 32, "of": 128},
    assumed=("expand_unused", "no_positions", "time_step_keys",
             "state_dtype", "state_layout", "weights", "state_slots",
             "kv_pool", "max_tracked_sequences", "max_seq_len"),
    unservable=(
        ("the family's dense layer",
         dict(HF, hybrid_override_pattern="MEM*-MEM"), "dense MLP layer"),
        ("group-limited routing", dict(HF, n_group=2), "n_group"),
        ("groups kept of several", dict(HF, topk_group=2), "topk_group"),
        ("an unknown character", dict(HF, hybrid_override_pattern="MEM*EMEX"),
         "hybrid_override_pattern names"),
        ("a pattern of another length", dict(HF, num_hidden_layers=9),
         "hybrid_override_pattern names"),
        ("gated experts", dict(HF, mlp_hidden_act="silu"), "mlp_hidden_act"),
        ("a convolution without its bias", dict(HF, use_conv_bias=False),
         "use_conv_bias"),
        ("a latent key the mapping does not read", dict(HF, kv_lora_rank=32),
         "does not read"),
        ("the pattern under another architecture",
         dict(F.MISTRAL, hybrid_override_pattern="M*"), "does not read"),
        ("groups under another architecture", dict(F.MISTRAL, n_groups=8),
         "does not read"),
        ("a shared expert that is no multiple of an expert",
         dict(HF, moe_shared_expert_intermediate_size=50), "no multiple"),
    ))


# -- the configuration ---------------------------------------------------

def _published():
    return {k: v for k, v in json.loads(PUBLISHED.read_text()).items()
            if not k.startswith("_")}


def test_the_importer_reads_the_published_file():
    """52 kinds, 23 / 23 / 6, and every derived width; with max_seq
    alone overridden."""
    cfg = config_from_hf(_published(), max_seq=4096)
    kinds = cfg.layer_types
    assert len(kinds) == cfg.n_layers == cfg.depth == 52
    assert [kinds.count(k) for k in ("state_space", "experts", "attention")
            ] == [23, 23, 6]
    assert kinds[:6] == ("state_space", "experts", "state_space", "experts",
                         "state_space", "attention")
    assert cfg.mixer_only and (cfg.n_kv_layers, cfg.n_state_layers) == (6, 23)
    assert (cfg.d_model, cfg.vocab_size, cfg.norm_eps) == (2688, 131072, 1e-5)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_dim,
            cfg.ssm_groups, cfg.conv_kernel, cfg.ssm_chunk) == \
        (64, 64, 128, 8, 4, 128)
    # heads x head_dim, NOT expand x hidden_size (5,376)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_pack) == (4096, 6144, 2)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 2, 128)
    assert not cfg.use_rope and not cfg.use_learned_pos
    assert cfg.attention_multiplier is None  # 128^-0.5, the kernels' own
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held, cfg.ff_dim,
            cfg.n_shared_experts) == (128, 6, None, 1856, 2)
    assert (cfg.moe_scoring, cfg.moe_expert_bias, cfg.routed_scaling_factor,
            cfg.moe_norm_topk_prob) == ("sigmoid", True, 2.5, True)
    assert not cfg.is_gated and cfg.act_name == "relu2"
    assert not cfg.tie_embeddings and not cfg.shared_expert_gate
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == \
        31_577_940_288  # the published "31.6B"
    assert shapes["ssm_in"].shape == (23, 2688, 10304)


def test_the_cut_builds_at_published_widths():
    hf, cfg = F.cut_of(FAMILY)
    full = config_from_hf(_published(), max_seq=4096)
    # the cut differs from the published model in the four reduced keys
    import dataclasses
    differ = {f.name for f in dataclasses.fields(cfg)
              if getattr(cfg, f.name) != getattr(full, f.name)}
    assert differ == {"n_layers", "layer_types", "experts_held", "vocab_size"}
    assert cfg.layer_types == tuple(
        {"M": "state_space", "E": "experts", "*": "attention"}[c]
        for c in "MEMEM*EMEMEM*")
    assert (cfg.n_kv_layers, cfg.n_state_layers, cfg.depth) == (2, 6, 13)
    assert cfg.experts_held == (0, 32) and cfg.n_experts == 128
    assert [cfg.state_index(li) for li in range(13)] == \
        [0, 1, 1, 2, 2, 3, 3, 3, 4, 4, 5, 5, 6]
    assert [cfg.op_index(li) for li in (5, 12)] == [0, 1]
    # a head's matrix transposed, two heads a lane row; 48 lane rows of
    # carried inputs are 6 whole tiles
    assert cfg.state_shapes("state_space") == (
        ((32, 128, 128), jnp.float32), ((3, 48, 128), None))
    # (the routed block's own settings train since PR 55; the layers of
    # one mixer each, the groups and the missing positions do not)
    assert set(cfg.serving_only) == {
        "layer_types", "mixer_only", "ssm_groups", "position_embedding"}
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert set(shapes["layers"]) == {"ln1_scale"}  # ONE norm a layer
    assert shapes["layers"]["ln1_scale"].shape == (13, 2688)
    assert shapes["moe_w_in"].shape == (5, 32, 2688, 1856)
    assert shapes["moe_w_out"].shape == (5, 32, 1856, 2688)
    assert shapes["moe_w_router"].shape == (5, 2688, 128)
    assert shapes["moe_expert_bias"].shape == (5, 128)
    assert shapes["moe_ws_in"].shape == (5, 2688, 3712)
    assert not any("gate" in k for k in shapes)  # no gate anywhere
    assert shapes["ssm_in"].shape == (6, 2688, 10304)
    assert shapes["ssm_taps"].shape == (6, 6144, 4)
    assert shapes["ssm_norm_scale"].shape == (6, 4096)
    assert shapes["attn_wq"].shape == (2, 2688, 32, 128)
    assert shapes["attn_wk"].shape == (2, 2688, 2, 128)
    assert shapes["embed"].shape == (32768, 2688) and \
        shapes["lm_head"].shape == (2688, 32768)
    # the file's own count, every leaf
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == \
        2_153_400_832
    # the cache: K/V for the TWO attention layers, two pools for each of
    # the SIX mixers, nothing for the five routed layers
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, 2049, 128, jnp.bfloat16, state_slots=256))
    assert [a.shape for a in cache.k] == [(2049, 128, 2, 128)] * 2
    assert [tuple((a.shape, a.dtype) for a in pools)
            for pools in cache.state] == [
        (((257, 32, 128, 128), jnp.float32),
         ((256, 3, 48, 128), jnp.bfloat16))] * 6
    pools = E.pool_bytes(cfg, E.InferenceConfig(**hf["serve"]["engine"]),
                         jnp.bfloat16)
    assert pools == {"kv": 2049 * 128 * 2048,
                     "state": 6 * (257 * 2_097_152 + 256 * 36_864)}
    # prepare pads the ungated experts' 1,856 to whole lanes, and the
    # pass takes them; the shared expert's 3,712 is whole
    lp = jax.eval_shape(lambda: M.prepare(jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), T.init(cfg, jax.random.PRNGKey(0))),
        cfg))["layers"][1]
    assert lp["w_in"].shape == (32, 2688, 1920)
    assert lp["w_out"].shape == (32, 1920, 2688)
    assert lp["ws_in"].shape == (2688, 3712) and "ws_gate" not in lp
    assert {M.expert_path(t, cfg, lp, True) for t in (8, 256, 512)} == \
        {"stream"}
    assert M.expert_path(256, cfg, lp, False) == "scan"
    assert ES.stream_f_tile(256, None, lp["w_in"], lp["w_out"]) == 640


def test_the_kinds_and_what_they_hold():
    """The fifth kind is an entry of OPERATOR_PREFIX and of no state
    table: a layer holds K/V, state or nothing by its kind."""
    assert "experts" in T.LAYER_KINDS and "experts" not in T._STATE_LAYERS
    assert "experts" not in M._STATE_OPERATORS
    with pytest.raises(ValueError, match="mixer_only"):
        T.TransformerConfig(n_layers=2, n_experts=4, layer_types=(
            "attention", "experts"))
    with pytest.raises(ValueError, match="mixer_only"):
        T.TransformerConfig(n_layers=2, n_experts=4, mixer_only=True,
                            layer_types=("attention", "attention"))
    with pytest.raises(ValueError, match="ssm_groups"):
        T.TransformerConfig(n_layers=1, conv_kernel=4, ssm_heads=16,
                            ssm_head_dim=16, ssm_state_dim=32, ssm_groups=4,
                            layer_types=("state_space",))  # half a lane row
    with pytest.raises(ValueError, match="mixer_only layers"):
        T.TransformerConfig(n_layers=2, mixer_only=True)


def test_a_dense_squared_relu_mlp_trains():
    """relu2 is one entry of the activation table train and serve
    share."""
    cfg = T.TransformerConfig(vocab_size=64, n_layers=1, n_heads=2,
                              d_model=32, max_seq=16, use_flash=False,
                              gated_mlp=False, activation="relu2")
    x = T.forward_hidden(T.init(cfg, jax.random.PRNGKey(0)),
                         jnp.zeros((1, 8), jnp.int32), cfg)
    assert np.isfinite(np.asarray(x[0] if isinstance(x, tuple) else x)).all()
    np.testing.assert_array_equal(
        T._act_fn(cfg)(jnp.asarray([-2.0, 0.0, 3.0])), [0.0, 0.0, 9.0])


# -- the recurrence in groups ------------------------------------------------

def _loop(x, dt, A, Bm, Cm, state, G):
    """The recurrence head by head in numpy float64: head h reads the B
    and C of group h // (H / G)."""
    x, dt, A, Bm, Cm, S = (np.asarray(a, np.float64)
                           for a in (x, dt, A, Bm, Cm, state))
    B_, T_, H, _ = x.shape
    N = S.shape[-1]
    y = np.zeros(x.shape)
    for t in range(T_):
        for h in range(H):
            g = h // (H // G)
            b, c = Bm[:, t, g * N:(g + 1) * N], Cm[:, t, g * N:(g + 1) * N]
            S[:, h] = S[:, h] * np.exp(dt[:, t, h] * A[h])[:, None, None] + (
                (dt[:, t, h, None] * x[:, t, h])[:, :, None] * b[:, None, :])
            y[:, t, h] = np.einsum("bpn,bn->bp", S[:, h], c)
    return y, S


@pytest.mark.parametrize("G", [1, 2, 8])
def test_the_recurrence_and_the_chunked_form_read_their_groups(rng, G):
    x, dt, A, Bm, Cm = W.ssm_inputs(rng, 2, 23, G=G)
    state = jnp.asarray(rng.normal(size=(2, 8, 16, 32)), jnp.float32)
    want_y, want_s = _loop(x, dt, A, Bm, Cm, state, G)
    y1, s1 = SS.ssm_recurrent(x, dt, A, Bm, Cm, state, groups=G)
    y2, s2 = SS.ssm_chunked(x, dt, A, Bm, Cm, state, chunk=7, groups=G)
    for y, s in ((y1, s1), (y2, s2)):
        np.testing.assert_allclose(y, want_y, rtol=5e-5, atol=1e-4)
        np.testing.assert_allclose(s, want_s, rtol=5e-5, atol=1e-4)
    # from a zero state the groups are told by `groups`
    y3, _ = SS.ssm_recurrent(x, dt, A, Bm, Cm, groups=G)
    y4, _ = SS.ssm_chunked(x, dt, A, Bm, Cm, groups=G, chunk=16)
    np.testing.assert_allclose(y4, y3, rtol=5e-5, atol=1e-4)


def _check_step(step, rng, G):
    """16 heads of 16 in two lane rows of the pool."""
    pool, slots, pos, runs = W.ragged(rng, (6, 2, 32, 128))
    x, dt, A, Bm, Cm = W.ssm_inputs(rng, 11, G=G, H=16)
    y, new = jax.jit(step)(x, dt, A, Bm, Cm, pool, slots, pos)
    for rows, slot, start in runs:
        first = (np.zeros((1, 16, 16, 32)) if start is None
                 else SS.unpack_state(start, 8)[None])
        want_y, want_s = _loop(x[None, rows], dt[None, rows], A,
                               Bm[None, rows], Cm[None, rows], first, G)
        np.testing.assert_allclose(y[rows], want_y[0], atol=5e-5)
        np.testing.assert_allclose(SS.unpack_state(new[slot], 8), want_s[0],
                                   atol=5e-5)
    np.testing.assert_array_equal(new[2], pool[2])
    np.testing.assert_array_equal(new[4], pool[4])


@pytest.mark.parametrize("G", [1, 2])
def test_the_step_over_runs_reads_its_groups(rng, G):
    _check_step(SS.ssm_step_xla, rng, G)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("G", [1, 2])
def test_the_step_kernel_reads_its_groups(rng, G):
    """Two lane rows of eight heads: with two groups each row reads its
    own column of B and of C."""
    _check_step(SS.ssm_step, rng, G)


@pytest.mark.usefixtures("pallas_interpret")
def test_the_step_kernel_with_eight_groups(rng):
    """64 heads of 16 in eight lane rows, one a group, against the loop
    over rows in XLA."""
    slots = jnp.asarray([2, 2, 2, 0, -1, 1, 3, 3], jnp.int32)
    pos = jnp.asarray([0, 1, 2, 9, 0, 4, 7, 8], jnp.int32)
    pool = jnp.asarray(rng.normal(size=(5, 8, 8, 128)), jnp.float32)
    args = W.ssm_inputs(rng, 8, G=8, H=64, N=8)
    y1, p1 = SS.ssm_step(*args, pool, slots, pos)
    y2, p2 = SS.ssm_step_xla(*args, pool, slots, pos)
    np.testing.assert_allclose(y1, y2, atol=2e-5)
    np.testing.assert_allclose(p1[:4], p2[:4], atol=2e-5)


# -- the ungated pass --------------------------------------------------------

@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("rows,F", [(24, 200), (40, 128)])
def test_the_ungated_pass_is_the_loop_over_experts(rng, rows, F):
    """act(h W_in) W_out under a combine column, two stacks streamed:
    at an F that fills no lane tile (padded by prepare's rule) and at
    one that does."""
    bf = jnp.bfloat16
    act = T._ACT_FNS["relu2"]
    h = jnp.asarray(rng.normal(size=(rows, 128)), bf)
    w_in = jnp.asarray(rng.normal(size=(4, 128, F)) * 0.1, bf)
    w_out = jnp.asarray(rng.normal(size=(4, F, 128)) * 0.1, bf)
    wcols = jnp.asarray(rng.uniform(size=(4, rows)) * (
        rng.uniform(size=(4, rows)) > 0.5), jnp.float32)
    assert (ES.stream_f_tile(rows, None, w_in, w_out) is None) == bool(F % 128)
    p_in, p_out = M._whole_lane_experts(w_in, w_out)
    assert p_in.shape[-1] % 128 == 0 and p_out.shape[1] == p_in.shape[-1]
    got = ES.expert_stream_ungated_mlp(h, p_in, p_out, wcols, act)
    f32 = lambda a: a.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = sum(wcols[x][:, None] * (act(f32(h) @ f32(w_in[x]))
                                        @ f32(w_out[x])) for x in range(4))
    assert float(jnp.abs(want).max()) > 1
    # bf16 operands, the inner product rounded to bf16 once an expert
    np.testing.assert_allclose(f32(got), want, rtol=0.02, atol=0.05)


def test_stacks_that_fill_their_lanes_stay_as_they_are():
    w_in, w_out = jnp.zeros((2, 128, 256)), jnp.zeros((2, 256, 128))
    assert M._whole_lane_experts(w_in, w_out) == (w_in, w_out)
    # E fills no lanes: the pass would refuse the stacks anyway
    w_in, w_out = jnp.zeros((2, 64, 24)), jnp.zeros((2, 24, 64))
    assert M._whole_lane_experts(w_in, w_out) == (w_in, w_out)


# -- the engine with kernels, the program's text ---------------------------

def test_the_scopes_of_a_layer_that_is_one_mixer(engines):
    """The scopes a kind already has keep their names, the one norm is
    `norm1`, and there is no `norm2`: span-only readers work
    unchanged."""
    text = F.step_text(engines())
    for scope in ("norm1", "state_space/ssm_project", "state_space/ssm_conv",
                  "state_space/ssm_state", "state_space/ssm_gate_norm",
                  "state_space/ssm_out", "attention", "mlp/moe_route",
                  "mlp/moe_experts", "mlp/moe_shared"):
        assert scope in text, scope
    assert "norm2" not in text
    assert "rope" not in text and "cos" not in text


def test_the_ungated_pass_has_a_kernel_name_of_its_own():
    """At widths that fill their lanes the routed layer's program holds
    `expert_stream_ungated`, and no `expert_stream` beside it."""
    cfg = T.TransformerConfig(
        vocab_size=64, n_layers=1, n_heads=2, d_model=128, d_ff=200,
        max_seq=64, use_flash=False, mixer_only=True,
        layer_types=("experts",), n_experts=8, moe_top_k=2,
        experts_held=(0, 4), n_shared_experts=1, gated_mlp=False,
        activation="relu2", moe_scoring="sigmoid", moe_expert_bias=True)
    lp = jax.eval_shape(lambda: M.prepare(jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), T.init(cfg, jax.random.PRNGKey(0))),
        cfg))["layers"][0]
    assert lp["w_in"].shape == (4, 128, 256)
    h = jax.ShapeDtypeStruct((16, 128), jnp.bfloat16)
    text = jax.jit(lambda h, lp: M._mlp(h, lp, cfg, use_kernel=True)).trace(
        h, lp).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "moe_experts/jit(_stream_ungated_mlp)" in text
    assert "expert_stream_ungated/pallas_call" in text
    assert "jit(_stream_mlp)" not in text


# -- through the scheduler: slots taken, reused, counted by what holds them --

def test_a_slot_is_handed_on_and_counted_by_the_layers_that_hold_one(
        model, engines):
    """12 requests through 6 slots: every slot is handed on to a later
    sequence and what the last one left in it (here: NaN) never reaches
    the next; the counters of state count the FOUR mixers of eight
    layers, not the depth."""
    eng = engines.sched()
    assert E.pool_kinds(model[0]) == ("kv", "state")
    assert len(eng.cache.state) == 4 and len(eng.cache.k) == 1
    d, requests = F.through_reused_slots(FAMILY, model, eng)
    # a slot: 4 mixers x (16 matrices of 16 x 32 + 3 inputs of 16 x 16 +
    # 2 x 2 x 32 = 384 channels: three whole lane rows), float32
    assert eng.state_slot_bytes == 4 * 4 * (16 * 16 * 32 + 3 * 384)
    assert d["state_bytes_moved"] % (2 * eng.state_slot_bytes) == 0
    prompts = sum(len(p) for p, _ in requests)
    assert prompts - 12 <= d["ssm_run_tokens"] <= prompts
    assert d["gdn_run_tokens"] == 0
    assert d["moe_token_expert_pairs"] == \
        d["batched_tokens"] * HF["num_experts_per_tok"]


def test_the_census_says_how_many_pairs_reached_the_held_experts(engines):
    """With the census on, `metrics()` sets the pairs that reached this
    chip's experts beside the even router's expectation."""
    eng = engines.sched(moe_census=True)
    s, _ = F.serve(eng, F.requests(FAMILY, 4, seed=2))
    m = s.metrics()
    census = eng.moe_expert_census()
    assert m["moe_census_held_pairs"] == float(census[4:8].sum()) > 0
    assert m["moe_census_held_pairs_expected"] == \
        pytest.approx(census.sum() * 4 / 8)


# -- the share of an expert-parallel deployment ----------------------------

def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer(model):
    """Guide section 4: the routed parts that four shares of two
    experts give, with what every chip computes alike (the shared
    expert) counted ONCE, add up to what the uncut reference gives for
    the whole layer."""
    _, params = model
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    key = jax.random.PRNGKey(9)
    shared = {k: params["moe_" + k][0]
              for k in ("w_router", "expert_bias", "ws_in", "ws_out")}
    full = {k: 0.3 * jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, (k, shape) in enumerate(
                {"w_in": (8, 64, 24), "w_out": (8, 24, 64)}.items())}
    uncut_hf = dict({k: v for k, v in HF.items()
                     if k not in ("reduced", "experts_held")},
                    n_routed_experts=8)
    ow = {"moe_" + k: v for k, v in dict(shared, **full).items()}
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.moe(n[None], ow, uncut_hf)
        alike = ref._relu2(n, shared["ws_in"], shared["ws_out"])
    F.shares_of_two_add_up(FAMILY, "n_routed_experts", n, shared, full,
                           whole[0], alike)


# -- the kernels at the cell's shapes --------------------------------------

def test_the_step_kernel_compiles_for_v5e_with_eight_groups(one_chip):
    """256 rows of 64 heads of 64 x 128 in 8 groups over a pool of 257
    slots of 2 MiB, aliased in and out."""
    sds = F.on_chip(one_chip, jnp.float32)
    rows, pool = 256, sds((257, 32, 128, 128))
    assert SS.ssm_step_fits(rows, pool)
    F.compiles_one_aliased_kernel(SS.ssm_step, (
        sds((rows, 64, 64)), sds((rows, 64)), sds((64,)), sds((rows, 1024)),
        sds((rows, 1024)), pool, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)), 5, "ssm_state")


def test_the_ungated_pass_compiles_for_v5e_at_the_cells_shapes(one_chip):
    """32 held experts of 2688 x 1,856 padded to 1,920: three F tiles of
    640 an expert, 256 rows resident."""
    sds = F.on_chip(one_chip, jnp.bfloat16)
    w_in, w_out = sds((32, 2688, 1920)), sds((32, 1920, 2688))
    compiled = jax.jit(lambda h, wi, wo, c: ES.expert_stream_ungated_mlp(
        h, wi, wo, c, T._ACT_FNS["relu2"])).lower(
        sds((256, 2688)), w_in, w_out, sds((32, 256), jnp.float32)).compile()
    calls = F.kernels(compiled.as_text())
    assert len(calls) == 1 and "expert_stream_ungated" in calls[0]
