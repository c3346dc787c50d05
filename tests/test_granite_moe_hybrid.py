"""Granite 4.0-H (`granitemoehybrid`) on the normal serving path at a
tiny size on the CPU, against the plain float32 reference of
benchmarks/reference/granite_moe_hybrid.py: Mamba-2 state-space layers
whose sequences carry a float32 matrix a head (and the last three
inputs of a convolution with a bias) in a state slot, attention layers
WITHOUT positions that alone hold K/V, a held share of routed experts
beside an ungated shared expert, the four scalars; through whole-prompt
prefill (the chunked scan), chunks and single steps (the segmented
recurrence), through the scheduler with slots reused and never cleared;
the four shares that add up to the uncut layer, the mutants that must
fail, the refusals, and the cut's file; then the recurrence alone (the
chunked form, the step kernel, its walk, the kernels at the cell's
shapes). The contract every served family is held to is
tests/_family.py's.

Everything is float32 with seeded weights: two periods of (mamba,
mamba, attention, mamba), d 64, 8 state-space heads of 16 with a state
of 32 (all eight side by side in ONE 128-lane row of the pool), 4 query
/ 2 KV heads of 16 with a softmax scale of 1/16 (not 16^-0.5), 8
experts of 32 top-3 of which experts 4..7 are held, a shared expert of
64.
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
    test_prefix_credit_and_speculation_are_refused,
    test_the_cuts_file_keeps_the_published_widths,
    test_the_engine_refuses_at_build,
    test_the_engine_with_kernels_matches_the_reference,
    test_the_training_forward_refuses_the_family,
    test_what_the_mapping_cannot_serve_is_an_error,
    test_whole_prompt_waves_and_fused_decode_carry_the_state,
)

from benchmarks.reference import granite_moe_hybrid as ref
from deepspeed_tpu.inference import ServingScheduler, ServingSchedulerConfig
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import conv_carry as CC
from deepspeed_tpu.ops.pallas import ssm_state as SS
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

BENCH = F.BENCH
CUT = BENCH / "configs/granite-4.0-h-small-serve-l10-ep4.json"
PATTERN = ["mamba", "mamba", "attention", "mamba"]
HF = {"attention_bias": False, "attention_multiplier": 0.0625,
      "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 64,
      "intermediate_size": 32, "layer_types": PATTERN * 2,
      "logits_scaling": 16, "mamba_chunk_size": 16, "mamba_conv_bias": True,
      "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 32,
      "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
      "mamba_proj_bias": False, "max_position_embeddings": 512,
      "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
      "num_attention_heads": 4, "num_experts_per_tok": 3,
      "num_hidden_layers": 8, "num_key_value_heads": 2,
      "num_local_experts": 4, "position_embedding_type": "nope",
      "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
      "rope_scaling": None, "rope_theta": 10000,
      "shared_intermediate_size": 64, "tie_word_embeddings": True,
      "vocab_size": 256,
      "reduced": {"num_local_experts": {"published": 8, "here": 4}},
      "experts_held": {"start": 4, "count": 4, "of": 8}}

# float32 on both sides, logits up to 0.32 (they are divided by 16).
# The system reassociates (the fused QKV matmul, the chunked scan's
# matmuls against the recurrence, the expert scan's running sum, the
# taps' sum in another order, q scaled before the scores and not
# after), which moves a logit by under 1e-7 (measured here: 6e-8 over
# prefill, a chunk and single steps; 7e-8 over the chunk offsets). The
# mutants differ by at least 316 x the limit (the nearest of them,
# `softmax_all_no_renorm`), which is 30 x the noise.
LOGITS_ATOL = 2e-6
ENGINE = dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=48,
              max_batch_size=32, max_tracked_sequences=6,
              min_prefill_bucket=32)


def _jig(k, v, key):
    """Every norm scale, tap, bias, decay and skip matters."""
    if k == "ssm_d":
        # away from 0 (no skip) and from 1 (the publisher's start)
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
    return v


def test_what_only_this_cut_states():
    hf = json.loads(CUT.read_text())
    assert hf["vocab_size"] * 4 == hf["reduced"]["vocab_size"]["published"]
    assert hf["layer_types"] == hf["reduced"]["layer_types"]["published"][:10]


FAMILY = F.Family(
    hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE, jig=_jig, spread=0.2,
    far=300, run_tokens="ssm_run_tokens", cut=CUT,
    with_kernels=(True, False, "state_space/ssm_state/jit(_ssm_step)"),
    reduced=("layer_types", "num_hidden_layers", "num_local_experts",
             "vocab_size"),
    held={"start": 0, "count": 18, "of": 72},
    assumed=("state_dtype", "state_layout", "column_order", "no_dt_clamp",
             "decay", "skip_d", "weights", "state_slots", "kv_pool",
             "max_tracked_sequences", "max_seq_len"),
    unservable=(
        ("a latent key the mapping does not read", dict(HF, kv_lora_rank=32),
         "does not read"),
        ("state-space keys under another architecture",
         dict(F.MISTRAL, mamba_n_heads=8), "does not read"),
        ("a multiplier under another architecture",
         dict(F.MISTRAL, residual_multiplier=0.22), "does not read"),
        ("no positions under another architecture",
         dict(F.MISTRAL, position_embedding_type="nope"), "does not read"),
        ("groups that cut a lane row of the pool's heads",
         dict(HF, mamba_n_groups=2), "ssm_groups"),
        ("rotary positions", dict(HF, position_embedding_type="rope"),
         "position_embedding_type"),
        ("a convolution without its bias", dict(HF, mamba_conv_bias=False),
         "mamba_conv_bias"),
        ("a kind the family does not have",
         dict(HF, layer_types=["conv"] * 8), "layer_types names"),
        ("heads that are not the expansion", dict(HF, mamba_n_heads=6),
         "mamba_expand"),
        ("a shared expert that is no multiple of an expert",
         dict(HF, shared_intermediate_size=100), "no multiple"),
    ))


# -- the configuration ---------------------------------------------------

def test_the_cut_builds_at_published_widths():
    hf, cfg = F.cut_of(FAMILY)
    row = json.loads((BENCH / "configs/published/granite-4.0-h-small.json"
                      ).read_text())
    # the six widths benchmarks/tests/helpers.WIDTH_KEY does not know
    for key, mine in (("mamba_d_state", cfg.ssm_state_dim),
                      ("mamba_d_head", cfg.ssm_head_dim),
                      ("mamba_d_conv", cfg.conv_kernel),
                      ("mamba_n_heads", cfg.ssm_heads),
                      ("mamba_n_groups", 1),
                      ("mamba_chunk_size", cfg.ssm_chunk)):
        assert hf[key] == row[key] == mine, key
    assert (cfg.n_layers, cfg.depth, cfg.d_model) == (10, 10, 4096)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 8, 128)
    assert not cfg.use_rope and not cfg.use_learned_pos
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier) == \
        (12.0, 0.22, 16.0, 0.0078125)
    assert cfg.layer_types == ("state_space",) * 5 + ("attention",) \
        + ("state_space",) * 4
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held, cfg.ff_dim) == \
        (72, 10, (0, 18), 768)
    assert cfg.moe_scoring == "softmax" and cfg.moe_norm_topk_prob
    assert cfg.n_shared_experts == 2 and not cfg.shared_expert_gate
    assert cfg.tie_embeddings and cfg.vocab_size == 25088 == 196 * 128
    assert (cfg.n_kv_layers, cfg.n_state_layers) == (1, 9)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_pack) == (8192, 8448, 2)
    assert cfg.conv_channels("state_space") == 8448
    # a head's matrix transposed, two heads a lane row; 66 lane rows of
    # carried inputs in a slot of 72
    assert cfg.state_shapes("state_space") == (
        ((64, 128, 128), jnp.float32), ((3, 72, 128), None))
    # (embedding_multiplier trains since PR 55: tests/test_trinity.py)
    assert set(cfg.serving_only) == {
        "layer_types", "position_embedding",
        "residual_multiplier", "logits_scaling", "attention_multiplier"}
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["w_in"].shape == (10, 18, 4096, 768)
    assert shapes["layers"]["w_router"].shape == (10, 4096, 72)
    assert shapes["layers"]["ws_in"].shape == (10, 4096, 1536)
    assert "ws_sgate" not in shapes["layers"] and "lm_head" not in shapes
    assert shapes["ssm_in"].shape == (9, 4096, 16768)
    assert shapes["ssm_taps"].shape == (9, 8448, 4)
    assert shapes["ssm_conv_bias"].shape == (9, 8448)
    assert shapes["ssm_a_log"].shape == shapes["ssm_dt_bias"].shape == \
        shapes["ssm_d"].shape == (9, 128)
    assert shapes["ssm_norm_scale"].shape == (9, 8192)
    assert shapes["ssm_out"].shape == (9, 8192, 4096)
    assert shapes["attn_wq"].shape == (1, 4096, 32, 128)
    assert shapes["attn_wk"].shape == (1, 4096, 8, 128)
    flat = F.one_stack(cfg, shapes)
    # the file's own count, every leaf
    assert sum(int(np.prod(s.shape)) for s in flat.values()) == 2_955_758_208
    # the cache: K/V for the ONE attention layer; two pools a Mamba-2
    # layer, the matrices' with the pad rows' slot
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, 1025, 128, jnp.bfloat16, state_slots=128))
    assert [a.shape for a in cache.k] == [(1025, 128, 8, 128)]
    assert [tuple((a.shape, a.dtype) for a in pools)
            for pools in cache.state] == [
        (((129, 64, 128, 128), jnp.float32),
         ((128, 3, 72, 128), jnp.bfloat16))] * 9
    assert sum(a.size * 2 for a in cache.k + cache.v) / 1025 / 128 == 4096
    pools = E.pool_bytes(cfg, E.InferenceConfig(**hf["serve"]["engine"]),
                         jnp.bfloat16)
    assert pools == {"kv": 1025 * 128 * 4096,
                     "state": 9 * (129 * 4_194_304 + 128 * 55_296)}


def test_the_seeded_recipes_draw_a_skip_the_size_of_the_states_read():
    """`ssm_d` is a plain leaf to benchmarks/weights.py and to
    models/transformer.init: drawn 0.02 x normal like the taps, so that
    the skip D x and the state's read S C are of one size and `correct`
    sees both (drawn 1, as the publisher initialises it, the read is
    under a hundredth of the skip: benchmarks/traffic/
    chat-saturated-granite4h.json says what each reads)."""
    from benchmarks import weights

    mcfg = config_from_hf(HF)
    for params in (weights.make_params(mcfg, 3, jnp.float32),
                   T.init(mcfg, jax.random.PRNGKey(3))):
        assert 0 < np.abs(np.asarray(params["ssm_d"])).max() < 0.2
        assert np.abs(np.asarray(params["ssm_d"])).min() > 0
        assert (np.asarray(params["ssm_norm_scale"]) == 1).all()
        assert np.abs(np.asarray(params["ssm_conv_bias"])).min() > 0
        assert 0 < np.abs(np.asarray(params["ssm_a_log"])).max() < 0.2


def test_the_published_shapes_stream_the_held_experts():
    """18 held experts of 4096 x 768 (three F tiles of 256 an expert): a
    held share streams at every width (expert_path), through the one
    pipelined pass."""
    from deepspeed_tpu.ops.pallas.expert_stream import stream_f_tile

    _, cfg = F.cut_of(FAMILY)
    lp = F.expert_stacks(18, 4096, 768)
    assert {M.expert_path(t, cfg, lp, True) for t in (8, 128, 256)} == \
        {"stream"}
    assert M.expert_path(128, cfg, lp, False) == "scan"
    assert stream_f_tile(128, lp["w_gate"], lp["w_in"], lp["w_out"]) == 256


def test_b_and_c_in_two_groups_are_served(engines):
    """`mamba_n_groups` is read, not refused: 16 heads in two lane rows
    of the pool, a row a group. The whole-prompt scan (chunked, a
    group) and the same tokens as a prefill, a chunk and single steps
    (the recurrence from the slot) give one answer, and it is not the
    one-group model's."""
    hf = dict(HF, mamba_n_groups=2, mamba_n_heads=16, mamba_expand=4)
    mcfg = config_from_hf(hf, use_flash=False)
    assert (mcfg.ssm_groups, mcfg.ssm_inner, mcfg.ssm_conv_dim) == \
        (2, 256, 256 + 2 * 2 * 32)
    params = jax.tree.map(lambda x: x * 4, T.init(mcfg, jax.random.PRNGKey(2)))
    eng = engines.fresh(model=(mcfg, params))  # two other models: an engine each
    toks = np.random.default_rng(3).integers(0, 256, 40).astype(np.int32)
    whole = np.asarray(eng.put([1], [toks]))[0]
    eng.put([2], [toks[:30]])
    eng.put([2], [toks[30:37]])
    for t in toks[37:]:
        stepped = np.asarray(eng.put([2], [np.asarray([t])]))[0]
    assert np.abs(whole).max() > 0.1
    assert np.abs(whole - stepped).max() < LOGITS_ATOL
    # every head reading group 0's B and C is another model
    one = dict(params, ssm_in=params["ssm_in"].at[:, :, 576:608].set(
        params["ssm_in"][:, :, 544:576]))
    other = engines.fresh(model=(mcfg, one))
    assert np.abs(np.asarray(other.put([1], [toks]))[0] - whole).max() > \
        300 * LOGITS_ATOL


def test_the_kinds_and_their_tables_are_one():
    """A kind is an entry of each table, and the layer_types error names
    the kinds from the one tuple. A layer holds K/V ('attention'),
    state (the kinds of _STATE_LAYERS) or nothing ('experts', the routed
    block as a layer of its own; 'gated_memory' and 'cross_attention',
    which read another layer), by its kind."""
    from deepspeed_tpu.inference import scheduler as S

    assert T.LAYER_KINDS == tuple(T.OPERATOR_PREFIX)
    state_kinds = set(T.LAYER_KINDS) - {"attention", "experts",
                                        "gated_memory", "cross_attention"}
    assert set(T._STATE_LAYERS) == set(M._STATE_OPERATORS) == state_kinds
    cfg = T.TransformerConfig(
        n_layers=4, conv_kernel=4, ssm_heads=8, ssm_head_dim=16,
        ssm_state_dim=32, mixer_only=True, n_experts=4, layer_types=(
            "state_space", "experts", "attention", "state_space"))
    assert (cfg.n_kv_layers, cfg.n_state_layers) == (1, 2)
    assert cfg.state_layer_kinds == ("state_space",) * 2
    assert [cfg.state_index(li) for li in (0, 3)] == [0, 1]
    assert {k for _, k, *_ in T._operator_leaves(cfg)} == set(cfg.layer_types)
    matrix_kinds = {k for k, (_, m) in T._STATE_LAYERS.items() if m}
    assert set(M._STEP_OF) == set(M._SCAN_OF) == set(S._RUN_TOKENS) == \
        matrix_kinds == {"linear_attention", "state_space", "selective_scan"}
    with pytest.raises(ValueError, match="ssm_heads"):
        T.TransformerConfig(n_layers=2, conv_kernel=4, layer_types=(
            "attention", "state_space"))
    with pytest.raises(ValueError, match="position_embedding"):
        T.TransformerConfig(position_embedding="sinusoidal")


def test_the_training_forward_refuses_each_scalar_on_its_own():
    """With no layers of several kinds (the family whole: the contract's
    test_the_training_forward_refuses_the_family)."""
    for field, value in (("residual_multiplier", 0.22),
                         ("logits_scaling", 16.0),
                         ("attention_multiplier", 0.0625),
                         ("position_embedding", "none")):
        cfg = T.TransformerConfig(vocab_size=64, n_layers=1, n_heads=2,
                                  d_model=32, max_seq=16, use_flash=False,
                                  **{field: value})
        with pytest.raises(NotImplementedError, match=field):
            T.forward_hidden(T.init(cfg, jax.random.PRNGKey(0)),
                             jnp.zeros((1, 8), jnp.int32), cfg)


# -- the recurrence from the model's slots ---------------------------------

def test_a_padded_prompt_leaves_the_state_of_its_last_real_token(rng, model):
    """_recur_prompts: prompts of 9 and 30 tokens padded to 32, a pad
    prompt beside them; each slot gets the state after its prompt's own
    last token (in the pool's layout), the others are not touched."""
    mcfg = model[0]
    x, dt, A, Bm, Cm = W.ssm_inputs(rng, 3, 32)
    pool = jnp.full((5, 1, 32, 128), 7.0)
    n_real = jnp.asarray([9, 30, 0], jnp.int32)
    slots = jnp.asarray([2, 0, -1], jnp.int32)
    y, new = M._recur_prompts("state_space", (x, dt, A, Bm, Cm), pool, slots,
                              n_real, mcfg)
    for i, (n, slot) in enumerate([(9, 2), (30, 0)]):
        want_y, want_s = SS.ssm_recurrent(
            x[i:i + 1, :n], dt[i:i + 1, :n], A, Bm[i:i + 1, :n],
            Cm[i:i + 1, :n])
        np.testing.assert_allclose(y[i, :n], want_y[0], atol=1e-4)
        np.testing.assert_allclose(SS.unpack_state(new[slot], 8), want_s[0],
                                   atol=1e-4)
    assert (np.asarray(new[1]) == 7).all() and (np.asarray(new[3]) == 7).all()


def test_a_slot_wider_than_the_channels_serves_the_same(model, engines,
                                                         pallas_interpret):
    """A state of 576 makes the convolution's channels 1,280: ten lane
    rows in a slot of sixteen (cfg.state_shapes pads more than one
    tile's rows to whole tiles, as the published 66 rows are padded to
    72). The inputs and taps are padded to the slot (model._slot_wide)
    on both paths: the one-pass kernel and decode_impl 'xla' give the
    reference's logits over a prefill, a chunk and single steps."""
    hf = dict(HF, mamba_d_state=576)
    mcfg = config_from_hf(hf, use_flash=False)
    assert mcfg.state_shapes("state_space")[-1] == ((3, 16, 128), None)
    # up to a tile's rows stay a block of the whole dimension
    assert config_from_hf(dict(HF, mamba_d_state=64)).state_shapes(
        "state_space")[-1] == ((3, 2, 128), None)
    shapes = jax.eval_shape(lambda k: T.init(mcfg, k), jax.random.PRNGKey(0))
    fresh = T.init(mcfg, jax.random.PRNGKey(4))
    # the fixture's values wherever the shapes agree
    flat = lambda p: dict(p["layers"], **F.top(p))
    old = flat(model[1])
    take = lambda k, v: old[k] if old[k].shape == v.shape else v * 4
    params = {k: take(k, v) for k, v in F.top(fresh).items()}
    params["layers"] = {k: take(k, v) for k, v in fresh["layers"].items()}
    assert shapes["ssm_taps"].shape == (6, 1280, 4)
    full = np.random.default_rng(8).integers(0, 256, (1, 30)).astype(np.int32)
    want = F.ref_logits(FAMILY, params, full, hf=hf)[0]
    for impl, kernel in (("auto", True), ("xla", False)):
        # another model: an engine each
        eng = engines.fresh(model=(mcfg, params), decode_impl=impl)
        assert eng.carry_kernel(8) is kernel
        got = [np.asarray(eng.put([7], [full[0, a:b]]))[0]
               for a, b in ((0, 20), (20, 25), (25, 26), (26, 27))]
        eng.flush(7)
        err = np.abs(np.stack(got) - want[[19, 24, 25, 26]]).max()
        assert err < LOGITS_ATOL, (impl, err)


def test_the_scopes_of_the_operator_are_in_the_program(engines):
    text = F.step_text(engines())
    for scope in ("state_space/ssm_project", "state_space/ssm_conv",
                  "state_space/ssm_state", "state_space/ssm_gate_norm",
                  "state_space/ssm_out", "mlp/moe_shared", "mlp/moe_route"):
        assert scope in text, scope
    assert "rope" not in text and "cos" not in text


# -- through the scheduler: slots taken, reused, never cleared -------------

def test_a_slot_is_handed_on_with_no_clearing(model, engines):
    """12 requests of unequal lengths through 6 slots: every slot is
    handed on to a later sequence, and what the last one left in it
    (here: NaN, put there before the first admission too, in the
    matrices AND the carried inputs) never reaches the next."""
    eng = engines.sched()
    d, requests = F.through_reused_slots(FAMILY, model, eng)
    # a slot: 6 Mamba-2 layers x (8 matrices of 16 x 32 + 3 inputs of
    # 8 x 16 + 2 x 32 = 192 channels: no whole lanes, so as they are),
    # float32
    assert eng.state_slot_bytes == 6 * 4 * (8 * 16 * 32 + 3 * 192)
    assert d["state_bytes_moved"] % (2 * eng.state_slot_bytes) == 0
    assert d["state_bytes_moved"] >= 2 * eng.state_slot_bytes * d["steps"]
    # every prompt went in as chunks of up to 8: all its tokens but a
    # last chunk of one are rows of runs
    prompts = sum(len(p) for p, _ in requests)
    assert prompts - 12 <= d["ssm_run_tokens"] <= prompts
    assert d["gdn_run_tokens"] == 0


@pytest.mark.usefixtures("pallas_interpret")
def test_the_steps_through_the_step_kernel_are_counted(model, engines,
                                                       monkeypatch):
    """`state_step_kernel_steps`: every step over rows of an engine
    whose kernels run and whose pools fit the walk, each once; none
    under decode_impl 'xla'; none where a pool does not fit (the step
    is then the loop in XLA). One width of program: the interpreter's
    kernels are slow to trace."""
    eng, xla = engines(), engines(decode_impl="xla")
    assert eng.step_kernel(8) and not xla.step_kernel(8)
    requests = [(p[:12], 3) for p, _ in F.requests(FAMILY, 2, seed=8)]
    s, served = F.serve(eng, requests, max_num_batched_tokens=8)
    assert s.counters["state_step_kernel_steps"] == s.counters["steps"] > 0
    assert s.counters["state_carry_kernel_steps"] == 0  # 192 channels
    sx = ServingScheduler(xla, ServingSchedulerConfig(warmup=False))
    sx._count_state([1, 1], 8)
    assert sx.counters["state_step_kernel_steps"] == 0
    F.greedy_by_the_reference(FAMILY, model, requests, served)
    # slots past the walk's VMEM: another width asks again, and answers no
    monkeypatch.setattr(W.GD, "_SLOTS_VMEM", 16 << 10)
    pool = eng.cache.state[0][0]
    assert not SS.ssm_step_fits(16, pool) and not eng.step_kernel(16)
    before = s.counters["state_step_kernel_steps"]
    s._count_state([1, 1], 16)
    assert s.counters["state_step_kernel_steps"] == before
    args = W.ssm_inputs(np.random.default_rng(0), 16)
    text = str(jax.make_jaxpr(lambda *a: M._recur_rows(
        "state_space", a, pool, jnp.zeros((16,), jnp.int32),
        jnp.ones((16,), jnp.int32), True))(*args))
    assert "pallas_call" not in text


# -- the share of an expert-parallel deployment ----------------------------

def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer(model):
    """Guide section 4: the routed parts that four shares of two
    experts give, with what every chip computes alike (the shared
    expert) counted ONCE, add up to what the uncut reference gives for
    the whole layer (before the 0.22)."""
    _, params = model
    rng = np.random.default_rng(0)
    n = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    key = jax.random.PRNGKey(9)
    lw = {k: params["layers"][k][0]
          for k in ("w_router", "ws_gate", "ws_in", "ws_out")}
    full = {k: 0.1 * jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, (k, shape) in enumerate(
                {"w_gate": (8, 64, 32), "w_in": (8, 64, 32),
                 "w_out": (8, 32, 64)}.items())}
    uncut_hf = {k: v for k, v in HF.items()
                if k not in ("reduced", "experts_held")}
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.moe(n, dict(lw, **full),
                           dict(uncut_hf, num_local_experts=8))
        shared = ref._swiglu(n, lw["ws_gate"], lw["ws_in"], lw["ws_out"])
    F.shares_of_two_add_up(FAMILY, "num_local_experts", n, lw, full, whole,
                           shared)


# -- what cannot be right yet is refused where it is built ----------------

def test_pools_beyond_the_device_are_refused_with_the_three_numbers():
    """128 slots of 38.2 MB beside 5.91 GB of weights fit 16 GB; 256 do
    not, and the refusal names the three numbers."""
    hf, cfg = F.cut_of(FAMILY)
    weights = 2 * 2_955_758_208
    conf = E.InferenceConfig(**hf["serve"]["engine"])
    pools = E.pool_bytes(cfg, conf, jnp.bfloat16)
    assert round((weights + sum(pools.values())) / 1e9, 1) == 11.4
    E.refuse_pools_beyond(16 * 10 ** 9, weights, pools)
    twice = E.pool_bytes(cfg, E.InferenceConfig(**dict(
        hf["serve"]["engine"], max_tracked_sequences=256)), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"weights 5.91 GB \+ K/V pools "
                       r"0.54 GB \+ state pools 9.83 GB = 16.28 GB of "
                       r"16.00 GB"):
        E.refuse_pools_beyond(16 * 10 ** 9, weights, twice)


# -- the recurrence alone --------------------------------------------------

@pytest.mark.parametrize("tokens,chunk", [(1, 16), (5, 16), (16, 16),
                                          (23, 16), (70, 16), (37, 8),
                                          (23, 7), (300, 256)])
def test_the_chunked_form_is_the_recurrence(rng, tokens, chunk):
    """Lengths that are and are not multiples of the chunk, from a
    state that is not zero: outputs and the state left behind."""
    x, dt, A, Bm, Cm = W.ssm_inputs(rng, 2, tokens)
    state = jnp.asarray(rng.normal(size=(2, 8, 16, 32)), jnp.float32)
    # (each form ONE program: op by op the chunked form is dozens of
    # small compiles a case)
    y1, s1 = jax.jit(SS.ssm_recurrent)(x, dt, A, Bm, Cm, state)
    y2, s2 = jax.jit(functools.partial(SS.ssm_chunked, chunk=chunk))(
        x, dt, A, Bm, Cm, state)
    # float32 sums of up to 256 terms in another order: relative
    np.testing.assert_allclose(y2, y1, rtol=5e-5, atol=1e-4)
    np.testing.assert_allclose(s2, s1, rtol=5e-5, atol=1e-4)


def test_the_pools_layout_is_a_view_of_the_heads_matrices(rng):
    s = jnp.asarray(rng.normal(size=(3, 8, 16, 32)), jnp.float32)
    packed = SS.pack_state(s, 8)
    assert packed.shape == (3, 1, 32, 128)
    # head h's [P, N] transposed, in lanes h P .. (h + 1) P of the row
    np.testing.assert_array_equal(packed[1, 0, :, 5 * 16:6 * 16], s[1, 5].T)
    np.testing.assert_array_equal(SS.unpack_state(packed, 8), s)
    np.testing.assert_array_equal(
        SS.unpack_state(SS.pack_state(s, 2), 2), s)


def _check_step(step, rng):
    pool, slots, pos, runs = W.ragged(rng, (6, 1, 32, 128))
    x, dt, A, Bm, Cm = W.ssm_inputs(rng, 11)
    y, new = jax.jit(step)(x, dt, A, Bm, Cm, pool, slots, pos)
    for rows, slot, start in runs:
        want_y, want_s = jax.jit(SS.ssm_recurrent)(
            x[None, rows], dt[None, rows], A, Bm[None, rows], Cm[None, rows],
            None if start is None else SS.unpack_state(start, 8)[None])
        np.testing.assert_allclose(y[rows], want_y[0], atol=2e-5)
        np.testing.assert_allclose(SS.unpack_state(new[slot], 8), want_s[0],
                                   atol=2e-5)
    # the slots of no row of this step are as they were
    np.testing.assert_array_equal(new[2], pool[2])
    np.testing.assert_array_equal(new[4], pool[4])


def test_the_step_over_runs_is_a_segmented_recurrence(rng):
    _check_step(SS.ssm_step_xla, rng)


@pytest.mark.usefixtures("pallas_interpret")
def test_the_step_kernel_matches_the_recurrence(rng):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    assert SS.ssm_step_fits(11, f32(6, 1, 32, 128))
    # a head's width that fills no lane row; a state of half sublanes
    assert not SS.ssm_step_fits(11, f32(6, 8, 32, 48))
    assert not SS.ssm_step_fits(11, f32(6, 1, 12, 128))
    assert not SS.ssm_step_fits(
        11, jax.ShapeDtypeStruct((6, 1, 32, 128), jnp.bfloat16))
    _check_step(SS.ssm_step, rng)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("pattern,walk", W.CASES)
def test_the_walk_over_a_steps_rows(rng, monkeypatch, pattern, walk):
    """The kernel's own copies (tests/_state_walk.py: the rows'
    patterns, the walk's batches) against the loop over rows in XLA:
    three lane rows of eight heads of 16, a state of 8."""
    shape = (W.SLOTS + 1, 3, 8, 128)
    W.set_walk(monkeypatch, walk, shape)
    W.check_walk(SS.ssm_step, SS.ssm_step_xla,
                 lambda rng, n: W.ssm_inputs(rng, n, H=24, P=16, N=8), shape,
                 pattern, rng)


@pytest.mark.parametrize("what,shape,fits", [
    # two slots in VMEM is the least: a batch of one run, twice
    ("the cell's", (129, 64, 128, 128), True),
    ("slots of 24 MiB", (5, 384, 128, 128), True),
    ("slots of 26 MiB", (5, 416, 128, 128), False),
])
def test_ssm_step_fits_the_walks_slots(what, shape, fits):
    assert SS.ssm_step_fits(128, jax.ShapeDtypeStruct(shape, jnp.float32)) \
        is fits


# -- the kernels at the cell's shapes --------------------------------------

def test_the_step_kernel_compiles_for_v5e_at_the_cells_shapes(one_chip):
    """128 rows of 128 heads of 64 x 128 over a pool of 129 slots of
    4 MiB, aliased in and out (no second 541 MB pool among the
    temporaries)."""
    sds = F.on_chip(one_chip, jnp.float32)
    rows, pool = 128, sds((129, 64, 128, 128))
    assert SS.ssm_step_fits(rows, pool)
    F.compiles_one_aliased_kernel(SS.ssm_step, (
        sds((rows, 128, 64)), sds((rows, 128)), sds((128,)), sds((rows, 128)),
        sds((rows, 128)), pool, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)), 5, "ssm_state")


def test_the_convolution_compiles_for_v5e_at_8448_channels(one_chip):
    """8,448 channels are 66 lane rows: a slot of 66 is refused by
    Mosaic ("Slice shape along dimension 2 must be aligned to tiling
    (8)"), which is why cfg.state_shapes pads it to 72 and the step's
    inputs with it (model._slot_wide)."""
    sds = F.on_chip(one_chip, jnp.bfloat16)
    rows, pool = 128, sds((128, 3, 72, 128))
    assert CC.carry_fits(rows, jnp.bfloat16, pool)
    compiled = jax.jit(CC.conv_carry, donate_argnums=(2,)).lower(
        sds((rows, 9216)), sds((9216, 4)), pool, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)).compile()
    calls = F.kernels(compiled.as_text())
    assert len(calls) == 1 and "conv_carry" in calls[0]
