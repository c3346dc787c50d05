"""Phi-4-mini-flash (`phi4flash`) on the normal serving path at a tiny
size on the CPU, against the plain float32 reference of
benchmarks/reference/phi4flash.py: Mamba-1 selective scans (a matrix of
decay rates, a float32 state slot a sequence), differential attention in
windows (rings), ONE full layer whose pages two later layers walk again
(cross attention: a query alone), and gated memory units that read the
last scan's output of the same token; through whole-prompt prefill (the
chunked scan, flash over the donor's in-flight K/V), chunks and single
steps (the step over ragged rows, the donor's pool as its own write
left it), through the scheduler with rings, pages and slots admitted
and released together; the controls that must fail, the importer's
refusals, and the configuration's file.

Everything is float32 with seeded weights: a depth of 12 gives, by the
publisher's rule, four scans, three windowed layers, the full layer,
two units and two cross layers (what is shared has two readers); d 64,
8 query and 4 KV heads of 8, a window of 24 (shorter than every prompt,
its ring of 5 blocks of 8 shorter too), a SwiGLU of 128.
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
    test_the_cuts_file_keeps_the_published_widths,
    test_the_engine_with_kernels_matches_the_reference,
    test_the_training_forward_refuses_the_family,
    test_what_the_mapping_cannot_serve_is_an_error,
    test_whole_prompt_waves_and_fused_decode_carry_the_state,
)

from benchmarks.kernels import phi4flash as shapes
from benchmarks.reference import phi4flash as ref
from deepspeed_tpu.inference import (
    ServingScheduler,
    ServingSchedulerConfig,
)
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.utils import hf_checkpoint
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

BENCH = F.BENCH
CUT = BENCH / "configs/phi-4-mini-flash-reasoning-serve-l32.json"
PUBLISHED = BENCH / "configs/published/phi-4-mini-flash-reasoning.json"
HF = {"model_type": "phi4flash", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 128, "num_hidden_layers": 12,
      "num_attention_heads": 8, "num_key_value_heads": 4,
      "hidden_act": "silu", "layer_norm_eps": 1e-05,
      "max_position_embeddings": 512, "mb_per_layer": 2,
      "sliding_window": 24, "embd_pdrop": 0, "resid_pdrop": 0,
      "mlp_bias": False, "lm_head_bias": False, "tie_word_embeddings": True,
      # the reference's `stale_ring` reads the served block size here
      "serve": {"engine": {"kv_block_size": 8}}}
KINDS = (["selective_scan", "attention"] * 4
         + ["gated_memory", "cross_attention"] * 2)

# float32 on both sides, logits up to 2.6. The system reassociates (the
# chunked scan's associative products against the recurrence, the fused
# QKV and gate-up projections against the reference's slices, a pair's
# two maps as ONE walk over zero-padded heads against two dense maps,
# the ring's blocks in another order), which moves a logit by up to
# 9e-6 (measured here over prefill, a chunk and single steps; 2e-5 at
# the other chunk offsets). The limit is 5 x that. The controls differ
# by 0.076 (`cross_reads_own`, the smallest: 760 x the limit), 0.078
# (`state_bf16`), 0.10 (`all_windowed`), 0.78-0.90 (`stale_ring`,
# `all_full`), 1.06 (float8 weights) and 1.8-3.4 (`no_differential`,
# `memory_after_gate`, `no_memory`, `scalar_decay`): `far` below asks
# 300 x the limit of every one.
LOGITS_ATOL = 1e-4
ENGINE = dict(max_seq_len=256, kv_block_size=8, num_kv_blocks=96,
              max_batch_size=32, max_tracked_sequences=6, num_kv_rings=6,
              min_prefill_bucket=32)


def _jig(k, v, key):
    """Every norm scale and bias, tap, rate, step and lam matters."""
    normal = lambda s: s * jax.random.normal(key, v.shape)
    if "scale" in k:
        return 1 + normal(0.3)
    if k.endswith(("_bias", "_bq", "_bk", "_bv", "_bo")) and "sscan" not in k:
        return normal(0.1)
    if k == "sscan_taps":
        return normal(0.6)
    if k == "sscan_a_log":
        # rates from -0.14 to -4.5 a (channel, state) pair: a MATRIX
        return jax.random.uniform(key, v.shape, minval=-2.0, maxval=1.5)
    if k == "sscan_dt_bias":
        return jax.random.uniform(key, v.shape, minval=-2.0, maxval=1.0)
    if k in ("sscan_x", "sscan_d", "sscan_conv_bias"):
        return normal(0.5)
    if "diff_l" in k:
        return normal(0.4)  # lam moves by tenths about lam0(l)
    if k.endswith("_wo"):
        return normal(0.25)  # what the layers that attend write counts
    return v


def _unread(**keys):
    return dict(HF, **keys)


FAMILY = F.Family(
    hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE, jig=_jig, spread=2.0,
    far=300, training_refuses="layer_types", run_tokens="sscan_run_tokens",
    with_kernels=(True, True, "selective_scan/sscan_state"),
    cut=CUT, reduced=(),
    assumed=("layer_rule", "norm", "ffn", "no_positions", "mamba", "memory",
             "differential_attention", "biases", "cross_attention",
             "head_dim", "kv_layout", "state_dtype", "decay", "weights",
             "state_slots", "rings", "kv_pool", "max_seq_len", "ssm_chunk",
             "scheduler"),
    unservable=(
        ("another count of mixers a layer", _unread(mb_per_layer=1),
         "mb_per_layer"),
        ("an odd depth", _unread(num_hidden_layers=11), "num_hidden_layers"),
        ("a rotary key that is set", _unread(rope_theta=10000.0),
         "rope_theta"),
        ("a scaled rotation",
         _unread(rope_scaling={"rope_type": "yarn", "factor": 8.0}),
         "rope_scaling"),
        ("a partial rotation", _unread(partial_rotary_factor=0.5),
         "partial_rotary_factor"),
        ("dropout on the stream", _unread(resid_pdrop=0.1), "resid_pdrop"),
        ("dropout in attention", _unread(attention_dropout=0.1),
         "attention_dropout"),
        ("a bias in the FFN", _unread(mlp_bias=True), "mlp_bias"),
        ("a bias on the head", _unread(lm_head_bias=True), "lm_head_bias"),
        ("another activation", _unread(hidden_act="gelu"), "hidden_act"),
        ("a window on a layer that reads another's K/V",
         _unread(sliding_window=[24] * 12), "sliding_window gives layers"),
        ("windows of another length", _unread(sliding_window=[24] * 5),
         "sliding_window lists"),
        ("a key the mapping does not read", _unread(use_qk_norm=True),
         "use_qk_norm"),
        ("a latent key", _unread(kv_lora_rank=32), "kv_lora_rank"),
        ("experts", _unread(num_local_experts=8), "num_local_experts"),
        ("the key for another architecture",
         dict(F.MISTRAL, mb_per_layer=2), "mb_per_layer"),
        ("the rank for another architecture",
         dict(F.MISTRAL, mamba_dt_rank=8), "mamba_dt_rank"),
    ))


# -- the configuration, the import ------------------------------------------

def test_the_rule_of_the_depth_gives_the_kinds_and_the_donors(model):
    mcfg, _ = model
    assert list(mcfg.layer_types) == KINDS
    assert (mcfg.memory_donor, mcfg.kv_donor) == (6, 7)
    assert mcfg.n_kv_reader_layers == 2 and mcfg.n_kv_layers == 4
    # three rings, one paged pool; the window is the odd layers' under half
    assert mcfg.ring_layers == (True, True, True, False)
    assert [mcfg.window_for_layer(l) for l in range(12)] == [
        24 if l in (1, 3, 5) else 0 for l in range(12)]
    assert mcfg.differential_attention and not mcfg.use_rope
    assert mcfg.norm_has_bias and mcfg.has_qkv_bias and mcfg.has_attn_out_bias
    assert (mcfg.ssm_inner, mcfg.ssm_state_dim, mcfg.conv_kernel,
            mcfg.ssm_dt_rank) == (128, 16, 4, 4)
    assert set(mcfg.serving_only) >= {"layer_types", "differential_attention",
                                      "ssm_dt_rank", "position_embedding"}
    # the reference states the same rule, independently
    kinds, windows = ref.mixers(HF)
    names = {"scan": "selective_scan", "window": "attention",
             "full": "attention", "unit": "gated_memory",
             "cross": "cross_attention"}
    assert [names[k] for k in kinds] == KINDS
    assert windows == [mcfg.window_for_layer(l) for l in range(12)]


def test_the_mamba_sizes_are_read_where_the_file_has_them():
    cfg = config_from_hf(dict(HF, mamba_expand=4, mamba_d_state=8,
                              mamba_d_conv=3, mamba_dt_rank=6))
    assert (cfg.ssm_inner, cfg.ssm_state_dim, cfg.conv_kernel,
            cfg.ssm_dt_rank) == (256, 8, 3, 6)
    assert config_from_hf(dict(HF, mamba_dt_rank="auto")).ssm_dt_rank == 4
    # a list of windows, one entry a layer
    listed = config_from_hf(dict(HF, sliding_window=[
        24 if l in (1, 3, 5) else 0 for l in range(12)]))
    assert listed == config_from_hf(HF)


def test_the_published_file_builds_the_whole_model():
    """config_from_hf on the catalog row's config: 32 layers of the
    kinds the issue lists, and the engine's tree of 3,852 M."""
    published = json.loads(PUBLISHED.read_text())
    cfg = config_from_hf(published)
    count = lambda kind: cfg.layer_types.count(kind)
    assert (cfg.depth, count("selective_scan"), count("attention"),
            count("gated_memory"), count("cross_attention")) == (32, 9, 9, 7, 7)
    assert (cfg.memory_donor, cfg.kv_donor) == (16, 17)
    assert sum(cfg.ring_layers) == 8
    assert [l for l in range(32) if cfg.window_for_layer(l)] == list(
        range(1, 16, 2))
    assert cfg.widest_window == 512
    assert (cfg.ssm_inner, cfg.ssm_dt_rank) == (5120, 160)
    # five pairs side by side: 2 heads of 640, whole tiles, no padding
    assert M.kv_pool_shape(cfg) == (2, 640)
    assert cfg.state_shapes("selective_scan") == (
        ((40, 16, 128), jnp.float32), ((3, 40, 128), None))
    n = T.param_count(cfg)
    assert n == shapes.parameters(published) == 3_852_562_944
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"Phi-4-mini-flash-reasoning"' in line)
        assert {k: v for k, v in published.items()
                if not k.startswith("_")} == row["config"]
        assert published["_source"] == row["source_url"]


def test_what_only_this_configuration_states():
    hf = json.loads(CUT.read_text())
    published = json.loads(PUBLISHED.read_text())
    assert hf["reduced"] == {} and hf["stands_for"]
    assert hf["source"] == published["_source"]
    assert all(hf[k] == v for k, v in published.items()
               if not k.startswith("_"))
    assert hf["serve"]["engine"] == {
        "max_seq_len": 8192, "kv_block_size": 128, "num_kv_blocks": 2560,
        "num_kv_rings": 64, "max_batch_size": 128,
        "max_tracked_sequences": 128, "kv_cache_dtype": "auto",
        "decode_impl": "auto"}
    assert hf["serve"]["scheduler"] == json.loads(
        (BENCH / "configs/mistral-7b-serve-l16.json").read_text()
    )["serve"]["scheduler"]
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    # what a sequence holds, by the issue's arithmetic
    assert shapes.kv_bytes_per_token_per_layer(hf) == 5_120
    assert shapes.slot_bytes_per_sequence_per_layer(hf) == 327_680 + 30_720
    assert M.ring_blocks(cfg, 128, 64) == 6


def test_the_weights_do_not_import_yet(tmp_path):
    (tmp_path / "config.json").write_text(json.dumps(HF))
    with pytest.raises(NotImplementedError, match="configuration alone"):
        hf_checkpoint.import_external(str(tmp_path))


@pytest.mark.parametrize("kwargs,match", [
    (dict(n_kv_heads=3), "pairs"),
    (dict(n_heads=6, n_kv_heads=4), "pairs"),
    (dict(attn_output_gate=True), "differential_attention"),
    (dict(qk_norm=True), "differential_attention"),
])
def test_differential_attention_needs_heads_that_pair(kwargs, match):
    base = dict(vocab_size=64, n_layers=2, n_heads=4, n_kv_heads=2,
                d_model=32, differential_attention=True,
                position_embedding="none")
    T.TransformerConfig(**base)
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**dict(base, **kwargs))


@pytest.mark.parametrize("types,match", [
    (("attention", "cross_attention"), None),
    (("cross_attention", "attention"), "cross_attention layers need"),
    (("gated_memory", "attention"), "gated_memory layers need"),
])
def test_a_reader_needs_its_donor_before_it(types, match):
    kw = dict(vocab_size=64, n_layers=2, n_heads=4, n_kv_heads=2, d_model=32,
              position_embedding="none", layer_types=types, conv_kernel=4)
    if match is None:
        assert T.TransformerConfig(**kw).kv_donor == 0
        return
    with pytest.raises(ValueError, match=match):
        T.TransformerConfig(**kw)


def test_a_cross_layer_with_positions_is_refused():
    with pytest.raises(NotImplementedError, match="cross_attention"):
        T.TransformerConfig(vocab_size=64, n_layers=2, n_heads=4, d_model=32,
                            layer_types=("attention", "cross_attention"))


# -- the program --------------------------------------------------------------

def test_the_scopes_of_the_layers_are_in_the_program(engines):
    text = F.step_text(engines())
    for scope in ("selective_scan/sscan_project", "selective_scan/sscan_conv",
                  "selective_scan/sscan_state", "selective_scan/sscan_gate",
                  "selective_scan/sscan_out", "jit(step)/gated_memory",
                  "attention/attn_window", "attention/attn_full",
                  "attention/attn_cross", "attention/diff_combine",
                  "jit(step)/norm1", "jit(step)/mlp"):
        assert scope in text, scope
    # the combine is outside the walks' scopes: a reader sums each apart
    for scope in ("attn_cross/diff_combine", "attn_full/diff_combine",
                  "attn_window/diff_combine"):
        assert scope not in text, scope


def test_a_cross_layer_writes_nothing_and_walks_the_donors_pool(model,
                                                                engines):
    """The cache holds K/V for the four layers that own it, and a step
    returns pools for those alone; what a cross layer attends is the
    donor's pool AFTER this step's write (the tokens' own K/V)."""
    mcfg, _ = model
    eng = engines()
    assert len(eng.cache.k) == len(eng.cache.v) == 4
    assert len(eng.cache.state) == 4
    rings, R = E.ring_geometry(mcfg, eng.config)
    assert (rings, R) == (6, 5)
    assert [p.shape[0] for p in eng.cache.k] == [rings * R + 1] * 3 + [97]
    # two pairs of 16 values: no fold at these widths, [.., 2, 16]
    assert eng.cache.k[3].shape[2:] == M.kv_pool_shape(mcfg) == (2, 16)


def test_the_pool_ids_say_who_owns_and_who_reads(model, engines):
    from deepspeed_tpu.utils import profiler

    profiler.clear()
    engines.fresh()  # reads the build's kept span
    (span,) = [s for s in profiler.spans() if s.name == "init.pool"]
    assert (span.ids["kv_owner_layers"], span.ids["kv_ring_layers"],
            span.ids["kv_reader_layers"], span.ids["state_layers"]) == (
        1, 3, 2, 4)
    assert span.ids["rings"] == 6 and span.ids["state_slots"] == 6


@pytest.mark.usefixtures("pallas_interpret")
def test_the_warm_up_says_which_scan_the_step_compiled(engines, caplog):
    from deepspeed_tpu.utils import profiler

    for eng, said in ((engines(), "kernel"),
                      (engines(decode_impl="xla"), "xla")):
        profiler.clear()
        logging.getLogger("deepspeed_tpu").propagate = True
        with caplog.at_level(logging.INFO, logger="deepspeed_tpu"):
            caplog.clear()
            eng.warmup(widths=[8], footprint=False)
        lines = [r.getMessage() for r in caplog.records
                 if "serving warmup program: kind decode" in r.getMessage()]
        assert lines and all(f"state_step {said}" in l for l in lines), lines
        assert eng.step_kernel(8) is (said == "kernel")


@pytest.mark.parametrize("what,kwargs,config", [
    ("int8_kv", {}, {"kv_cache_dtype": "int8"}),
    ("mesh", {}, {"tp_size": 2}),
    ("weight_quantization", {"quantization": {"bits": 8}}, {}),
    ("offload", {"offload": {"device": "cpu"}}, {}),
])
def test_the_engine_refuses_at_build(model, engines, what, kwargs, config):
    """Rings, pages and state in one cache: what any of the three
    cannot do is refused where the engine is built."""
    assert E.pool_kinds(model[0]) == ("kv", "ring", "state")
    with pytest.raises(NotImplementedError, match=what):
        engines.fresh(init=kwargs, **config)


def test_prefix_credit_and_speculation_are_refused(model, engines):
    assert not E.pools_can(model[0], "prefix_credit")
    assert not E.pools_can(model[0], "page_transfer")
    with pytest.raises(NotImplementedError, match="speculation"):
        ServingScheduler(engines(), ServingSchedulerConfig(warmup=False),
                         speculative={"ngram": 2, "draft_len": 3})


# -- through the scheduler: a ring, a slot and pages a sequence ---------------

def test_a_slot_a_ring_and_pages_are_taken_and_released_together(family, model,
                                                                  engines):
    """Twelve requests through six slots and six rings, state and rings
    poisoned first: every one is the reference's greedy answer, and at
    the end nothing is tracked, every ring, slot and page is free."""
    eng = engines.sched()
    d, asked = F.through_reused_slots(family, model, eng)
    assert eng.state.free_rings == eng.state.num_rings == 6
    assert d["kv_rings_live"] >= d["steps"]
    assert d["preemptions"] == 0


def test_preemption_recomputes_to_identical_tokens(family, engines):
    """Pages too few for the batch's answers (the contract's case at
    this module's block of 8: six prompts fit 40 blocks, their 40
    answers do not): the youngest sequence is flushed, its ring and
    slot with its pages, and recomputed from its first token in
    whatever ring and slot it is given."""
    asked = [(p, 40) for p, _ in F.requests(family, 6, seed=7)]
    _, roomy = F.serve(engines.sched(), asked)
    eng = engines.sched(num_kv_blocks=40)
    s, tight = F.serve(eng, asked)
    assert s.counters["preemptions"] > 0
    assert s.counters["state_slot_resets"] == 6 + s.counters["preemptions"]
    assert tight == roomy
    assert eng.state.free_rings == eng.state.num_rings


@pytest.mark.parametrize("short,engine", [
    ("window", dict(num_kv_rings=3)),
    ("full", dict(num_kv_blocks=12)),
])
def test_admission_waits_on_the_pool_that_is_short_and_says_which(
        engines, short, engine):
    """Six requests of 20 + 4 tokens (3 blocks each) against three
    rings, or against 12 paged blocks: the others wait in the queue, the
    counter names the pool, and every one gets the tokens a scheduler
    with room gives."""
    def run(**cfg):
        eng = engines.sched(**cfg)
        rng = np.random.default_rng(3)
        asked = [(rng.integers(0, 256, 20).tolist(), 4) for _ in range(6)]
        s, out = F.serve(eng, asked, max_num_batched_tokens=32,
                         prefill_chunk=4)
        assert eng.state.n_tracked == 0
        assert eng.state.free_rings == eng.state.num_rings
        return s, out

    s, got = run(**engine)
    roomy, want = run()
    assert got == want and all(len(o) == 4 for o in got)
    other = {"window": "full", "full": "window"}[short]
    assert s.counters[f"admit_waits_{short}_pool"] > 0
    assert s.counters[f"admit_waits_{other}_pool"] == 0
    assert s.counters["preemptions"] == 0
    assert roomy.counters["admit_waits_window_pool"] \
        == roomy.counters["admit_waits_full_pool"] == 0


def test_the_counters_count_this_model_truly(engines):
    """A prompt of 41 tokens in chunks of 8 and 3 answers: a chunk's
    rows are one read of each pool; the two cross layers read the full
    layer's context again; the rows whose logits are read are the last
    chunk's last and the answers'."""
    eng = engines()
    s, _ = F.serve(eng, [(list(range(41)), 3)], max_num_batched_tokens=32,
                   prefill_chunk=8)
    c = s.counters
    contexts = [8, 16, 24, 32, 40, 41, 42, 43]
    assert c["steps"] == len(contexts)
    assert c["kv_full_tokens"] == sum(contexts)
    assert c["kv_shared_tokens"] == 2 * c["kv_full_tokens"]
    assert c["kv_window_tokens"] == sum(min(x, 24) for x in contexts)
    assert c["kv_rings_live"] == c["state_slots_live"] == len(contexts)
    # 43 tokens are 6 blocks of 8 through a ring of 5
    assert c["kv_ring_blocks_recycled"] == 1
    assert c["sscan_run_tokens"] == 41 - 1  # the last chunk is one row
    assert c["cross_rows_run"] == 43 and c["cross_rows_needed"] == 3
    assert c["cross_rows_needed"] <= c["cross_rows_run"]
    assert c["state_bytes_moved"] == 2 * len(contexts) * eng.state_slot_bytes
    assert c["gdn_run_tokens"] == c["ssm_run_tokens"] == 0


def test_another_models_scheduler_counts_none_of_it(engines):
    """(a model without readers: the three counters stay 0)"""
    mcfg = T.TransformerConfig(vocab_size=64, n_layers=2, n_heads=4,
                               d_model=32, max_seq=64, use_flash=False)
    eng = engines.fresh(
        model=(mcfg, T.init(mcfg, jax.random.PRNGKey(0))), num_kv_rings=0)
    s, _ = F.serve(eng, [(list(range(9)), 2)])
    assert s.counters["steps"] > 0
    assert (s.counters["kv_shared_tokens"], s.counters["cross_rows_run"],
            s.counters["cross_rows_needed"], s.counters["sscan_run_tokens"]
            ) == (0, 0, 0, 0)


# -- pairs folded side by side (the published widths' layout, small) -----------

@pytest.fixture(scope="module")
def folded():
    """Six K/V pairs of 128 values: no whole tiles as 6 heads, so three
    a head, [.., 2, 384], as the published 10 pairs lie five a head."""
    mcfg = T.TransformerConfig(
        vocab_size=128, n_layers=4, n_heads=12, n_kv_heads=12, d_model=64,
        d_ff=128, head_dim_override=64, max_seq=128, variant="llama",
        position_embedding="none", norm_type="layer", qkv_bias=True,
        attn_out_bias=True, differential_attention=True, use_flash=False,
        layer_types=("selective_scan", "attention", "gated_memory",
                     "cross_attention"),
        conv_kernel=4, ssm_heads=1, ssm_head_dim=128, ssm_state_dim=16,
        ssm_dt_rank=4, ssm_chunk=16)
    assert M.kv_pool_shape(mcfg) == (2, 384)
    params = jax.jit(lambda k: jax.tree.map(
        lambda x: x * 4, T.init(mcfg, k)))(jax.random.PRNGKey(5))
    return mcfg, params


def test_folded_pairs_serve_what_unfolded_pairs_serve(folded, engines,
                                                      monkeypatch):
    """The same weights through a cache that folds three pairs a head
    and through one that folds none: the same logits, prefill, a chunk
    and single steps (the fold is a layout, not a model)."""
    mcfg, params = folded
    rng = np.random.default_rng(2)
    toks = rng.integers(0, 128, 40).astype(np.int32)

    def logits(eng):
        out = [np.asarray(eng.put([1], [toks[a:b]]))[0]
               for a, b in ((0, 30), (30, 34), (34, 35), (35, 36))]
        eng.flush(1)
        return np.stack(out)

    cfg = dict(num_kv_rings=0, max_seq_len=128)
    got = logits(engines.fresh(model=folded, **cfg))  # another model
    assert got.shape == (4, 128) and np.isfinite(got).all()
    monkeypatch.setattr(M, "kv_pair_fold", lambda pairs, width: 1)
    plain = engines.fresh(model=folded, **cfg)  # patched under the build
    assert plain.cache.k[0].shape[2:] == (6, 128)
    np.testing.assert_allclose(got, logits(plain), atol=1e-4)
