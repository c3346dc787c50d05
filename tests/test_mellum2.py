"""Mellum 2 (`mellum`: window and full attention mixed 3 : 1, a rotary
table by layer type, a routed block) through the normal serving path at
tiny widths in float32 on the CPU: the engine against the plain
reference through whole-prompt prefill, a chunk and single steps on
sequences longer than three turns of a ring; the windowed layers' ring
(R blocks a sequence at any length, both pools' admission waits, what
rings refuse); YaRN's table against its closed form; the import's
refusals; the deliberately wrong models against the written tolerance;
and the older families' step programs, hashed as the parent lowered
them.

Tiny widths: hidden 64, 4 / 2 heads of 16, 8 experts of 32 top-2,
window 16 over blocks of 8 (a ring of 4 blocks = 32 tokens), 8 layers
(two periods of window, window, window, full), YaRN over 64 positions.
"""

import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import pytest
from _family import (  # noqa: F401 - the contract's fixtures and cases, collected here
    engines,
    family,
    pytest_generate_tests,
    test_a_wrong_model_fails_the_written_tolerance,
    test_the_training_forward_refuses_the_family,
    test_what_the_mapping_cannot_serve_is_an_error,
)
from _step_program import step_program, tiny as _tiny

from benchmarks.reference import mellum2 as ref
from deepspeed_tpu.inference import ServingScheduler, ServingSchedulerConfig
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.inference.ragged import (
    KVCacheExhaustedError,
    KVRingsExhaustedError,
    StateManager,
)
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.utils import profiler
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

BENCH = F.BENCH
CUT = BENCH / "configs/mellum2-12b-a2.5b-serve-l8.json"
PUBLISHED = BENCH / "configs/published/mellum2-12b-a2.5b-instruct.json"
HASHES = pathlib.Path(__file__).with_name("data") / "step_program_hashes.json"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 16,
        "original_max_position_embeddings": 64, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
HF = {"model_type": "mellum", "attention_bias": False, "head_dim": 16,
      "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
      "layer_types": PERIOD * 2, "mlp_layer_types": ["sparse"] * 8,
      "max_position_embeddings": 4096, "max_window_layers": 0,
      "moe_intermediate_size": 32, "norm_topk_prob": True,
      "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
      "num_hidden_layers": 8, "num_key_value_heads": 2,
      "rms_norm_eps": 1e-6,
      "rope_parameters": {
          "full_attention": YARN,
          "sliding_attention": {"rope_type": "default",
                                "rope_theta": 10000.0}},
      "sliding_window": 16, "tie_word_embeddings": False, "vocab_size": 128,
      "use_sliding_window": True,
      "serve": {"engine": {"kv_block_size": 8}}}
ENGINE = dict(max_seq_len=256, kv_block_size=8, num_kv_blocks=96,
              max_batch_size=32, max_tracked_sequences=8, num_kv_rings=4)
RING = 4                  # ceil((16 + 7) / 8) + 1 blocks of 8 tokens
# float32 engine against float32 reference: the largest difference read
# is 8e-5 on logits of up to 2.7 (summation order); the nearest wrong
# model, YaRN without its factor, reads 1.2
LOGITS_ATOL = 5e-4
FAMILY = F.Family(
    hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE, far=100,
    training_refuses="rope_scaling",
    unservable=(
        ("a dense layer after a sparse one",
         dict(HF, mlp_layer_types=["sparse", "dense"] + ["sparse"] * 6),
         "dense layer after"),
        ("an unknown layer type",
         dict(HF, layer_types=["chunked_attention"] * 8), "unknown"),
        ("a scaled table on the sliding layers",
         dict(HF, rope_parameters=dict(HF["rope_parameters"],
                                       sliding_attention=YARN)),
         "scaled table on"),
        ("another scaling than YaRN",
         dict(HF, rope_parameters=dict(
             HF["rope_parameters"],
             full_attention=dict(YARN, rope_type="longrope"))), "longrope"),
        ("YaRN without truncate",
         dict(HF, rope_parameters=dict(
             HF["rope_parameters"],
             full_attention=dict(YARN, truncate=False))), "truncate"),
        ("a bias on attention", dict(HF, attention_bias=True),
         "attention_bias"),
        ("sliding layers without a window", dict(HF, sliding_window=None),
         "need sliding_window"),
    ))


def _model(hf=HF, seed=0):
    mcfg = config_from_hf(hf, max_seq=512, use_flash=False)
    # four times the recipe's spread: scores sharp enough for the window,
    # the positions and the rotary table to matter (ONE program: leaf by
    # leaf the tree is dozens of small compiles)
    return mcfg, jax.jit(lambda: jax.tree.map(
        lambda a: a * 4 if a.ndim > 1 else a,
        T.init(mcfg, jax.random.PRNGKey(seed))))()


@pytest.fixture(scope="module")
def model():
    return _model()


def _ring_feeds(model, eng, lens, tail, chunk, n_dec, seed=0, hf=HF):
    """F.feed of prompts of `lens`: all but the last `tail` tokens whole
    (a whole-prompt prefill), those in chunks of `chunk` (a ring takes
    no more rows a step than it has blocks to turn over), then n_dec
    single tokens."""
    rng = np.random.default_rng(seed)
    full = [rng.integers(0, hf["vocab_size"], n + n_dec).astype(np.int32)
            for n in lens]
    cuts = [[min(c, n) for c in range(n - tail, n + chunk, chunk)]
            + [n + j + 1 for j in range(n_dec)] for n in lens]
    return F.feed(FAMILY, model, eng, full, cuts, hf=hf)


@pytest.fixture(scope="module")
def served(model, engines):
    """Prompts of 100 and 107 tokens (over three turns of a 32-token
    ring): 95 / 102 whole, a 5-token chunk, 12 single steps."""
    return _ring_feeds(model, engines(), [100, 107], 5, 5, 12)


def test_the_ring_engages_and_is_sized_as_derived(model, engines):
    mcfg, shared_engine = model[0], engines()
    assert mcfg.mixed_windows
    assert set(mcfg.serving_only) == {"rope_scaling_full_only",
                                      "rope_scaling_type"}
    assert mcfg.attention_window_pattern == (16, 16, 16, 0)
    assert M.ring_blocks(mcfg, 8, 32) == RING
    assert E.pool_kinds(mcfg) == ("kv", "ring")
    assert E.ring_geometry(mcfg, shared_engine.config) == (4, RING)
    # windowed layers: 4 rings x 4 blocks + the pad rows' one; full
    # layers: num_kv_blocks + theirs
    assert [k.shape[0] for k in shared_engine.cache.k] == [17, 17, 17, 97] * 2
    pools = E.pool_bytes(mcfg, shared_engine.config, jnp.float32)
    block = 8 * 2 * 16 * 4 * 2                          # K and V, float32
    assert pools == {"kv": 2 * 97 * block, "ring": 6 * 17 * block,
                     "state": 0}


def test_prefill_a_chunk_and_single_steps_match_the_reference(served):
    got, want, _, _ = served
    assert np.isfinite(got).all()
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


@pytest.mark.parametrize("tail,chunk", [(72, 7), (74, 8), (45, 5), (11, 1)])
def test_chunks_through_the_ring_at_every_offset(model, engines, tail,
                                                 chunk):
    """The whole of a 75-token sequence through the decode rows, in
    chunks whose ends fall at every offset of a block (7, 5), on its
    boundaries (8: the most a ring of this size takes) and one token at
    a time: every windowed layer writes into blocks it has turned over
    (two turns and more) and reads across the turn."""
    got, want, _, _ = _ring_feeds(model, engines(), [75, 75], tail, chunk, 2,
                                  seed=chunk)
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


@pytest.mark.parametrize("heads,kv,pack", [(4, 2, 1), (8, 4, 2)],
                         ids=["two_kv_heads", "four_kv_heads_in_two_wide"])
def test_the_paged_kernels_write_and_walk_the_ring(pallas_interpret, heads,
                                                   kv, pack):
    """The Pallas path (interpreted): `paged_kv_write` into the ring's
    blocks and the shared-table walk over the table made from the
    ring's number, a chunk's rows riding as ONE group though each row's
    window starts a token later. The rehearsal configuration (heads of
    128, window 16 over blocks of 16: a ring of 3 blocks), a sequence
    of 70 tokens: 3 whole, four chunks of 16 (the most a ring of this
    size takes), a ragged last chunk of 3 and single steps. With the
    published 4 KV heads, pages and rings alike hold them two a wide
    head (kv_pack, PR 64) and the set-up's span says so."""
    hf = dict(_tiny("tiny-mellum2"), num_attention_heads=heads,
              num_key_value_heads=kv)
    model = _model(hf)  # another model: its own engine
    profiler.spans(clear=True)
    eng = F.Engines(model, hf["serve"]["engine"]).fresh(
        max_seq_len=128, max_batch_size=16, decode_impl="pallas")
    pool = next(s for s in profiler.spans(clear=True)
                if s.name == "init.pool")
    assert (pool.ids["kv_pack"], pool.ids["kv_heads_padded"]) == (pack, 0)
    assert (pool.ids["kv_write"], pool.ids["ring_kv_write"]) == ("rows",) * 2
    assert {k.shape[2:] for k in eng.cache.k} == {(kv // pack, 128 * pack)}
    assert len({k.shape[0] for k in eng.cache.k}) == 2  # pages and rings
    assert eng.resolved_impl == "pallas" and eng.state.ring_blocks == 3
    got, want, _, _ = _ring_feeds(model, eng, [70], 67, 16, 3, hf=hf)
    # interpreted kernels multiply at the CPU's default precision
    assert np.abs(want).max() > 3.0
    assert np.abs(got - want).max() < 1e-2, np.abs(got - want).max(-1)


def test_a_dense_entry_is_a_swiglu_of_intermediate_size(engines):
    """`mlp_layer_types` `dense` (none in the published file): a leading
    SwiGLU of `intermediate_size`, through the same path."""
    hf = dict(HF, mlp_layer_types=["dense"] + ["sparse"] * 7)
    model = _model(hf, seed=1)
    mcfg, params = model
    assert (mcfg.n_dense_layers, mcfg.dense_d_ff, mcfg.n_layers) == (1, 96, 7)
    assert mcfg.attention_window_pattern == (16, 16, 16, 0)
    assert params["dense_w_in"].shape == (1, 64, 96)
    # another model: its own engine
    got, want, _, _ = _ring_feeds(model, engines.fresh(model=model), [60], 19,
                                  5, 3, hf=hf)
    assert np.abs(got - want).max() < LOGITS_ATOL


# -- the rotary tables ---------------------------------------------------

def test_yarn_matches_its_closed_form_at_the_published_values():
    hf = json.loads(CUT.read_text())
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    assert T.yarn_band_range(cfg) == (18, 35)
    assert cfg.rope_attention_factor == 1.2772588722239782 \
        == 0.1 * np.log(16) + 1
    d = np.arange(64)
    extrap = 500000.0 ** (-2 * d / 128)
    ramp = np.clip((d - 18) / 17, 0, 1)
    want = extrap / 16 * ramp + extrap * (1 - ramp)
    np.testing.assert_allclose(T.rope_inv_freq(cfg), want, rtol=2e-6)
    np.testing.assert_allclose(T.rope_inv_freq(cfg, scaled=False), extrap,
                               rtol=2e-6)
    inv, factor = ref.rotary_table(hf["rope_parameters"]["full_attention"],
                                   128)
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert factor == 1.2772588722239782
    # by layer type: the windowed layers rotate by the plain table
    assert [cfg.rope_scaled_at(li) for li in range(8)] == \
        [False, False, False, True] * 2
    assert [cfg.window_for_layer(li) for li in range(8)] == \
        [1024, 1024, 1024, 0] * 2
    x = jnp.ones((3, 1, 128))
    pos = jnp.asarray([0, 5, 4000])
    plain = M._rope_at(x, pos, cfg, scaled=False)
    yarn = M._rope_at(x, pos, cfg, scaled=True)
    np.testing.assert_allclose(plain[0], 1.0)               # position 0
    np.testing.assert_allclose(yarn[0], 1.2772588722239782, rtol=1e-6)
    assert not np.allclose(plain[2], yarn[2] / 1.2772588722239782, atol=1e-3)


# -- the ring ------------------------------------------------------------

def test_a_windowed_layers_blocks_never_exceed_the_ring():
    """A 5,000-token sequence at the published sizes (window 1,024,
    blocks of 128, chunks of 32): the table a windowed layer walks
    names R = 10 blocks, the sequence's own, at every length; they turn
    over as the window passes; the ring goes back at flush."""
    hf = json.loads(CUT.read_text())
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    R = M.ring_blocks(cfg, 128, 80)
    assert R == 10 == -(-(1024 + 128 - 1) // 128) + 1
    state = StateManager(num_blocks=64, block_size=128, num_rings=3,
                         ring_blocks=R)
    other = state.extend(7, 100)
    seq = state.extend(1, 32)
    assert (other.ring, seq.ring) == (0, 1) and state.free_rings == 1
    cache = M.PagedCache(k=[jnp.zeros((3 * R + 1, 128, 4, 128)),
                            jnp.zeros((65, 128, 4, 128))] * 4, v=[])
    seen_blocks = set()
    for start in range(0, 5000, 32):
        n = min(32, 5000 - start)
        state.extend(1, n)
        table = np.asarray(M._ring_tables(
            jnp.asarray([seq.ring, -1]), cache, cfg, 80))
        assert set(table[0]) == set(range(R, 2 * R))     # its ring alone
        assert set(table[1]) == {3 * R}                  # pad rows
        # what this chunk's rows still see and what it writes: distinct
        # positions never share a block slot
        live = range(max(start + 1 - 1024, 0), start + n)
        slots = {}
        for p in live:
            assert slots.setdefault(table[0, p // 128], p // 128) == p // 128
        seen_blocks |= set(table[0, p // 128] for p in live)
        state.commit(1, n)
    assert seq.seen_tokens == 5000 and seen_blocks == set(range(R, 2 * R))
    # 40 blocks of tokens through 10 slots: 30 turned over
    assert state.rings_recycled == -(-5000 // 128) - R
    assert len(seq.blocks) == -(-5000 // 128)   # the full layers' pages
    state.flush(1)
    assert state.free_rings == 2 and state.rings_live == 1
    assert state.allocator.free_blocks == 63


def test_a_sequence_without_a_free_ring_is_not_tracked():
    state = StateManager(num_blocks=8, block_size=8, num_rings=1,
                         ring_blocks=RING)
    state.extend(1, 4)
    assert not state.can_fit(2, 4) and state.can_fit(1, 4)
    with pytest.raises(KVRingsExhaustedError) as short:
        state.extend(2, 4)
    assert short.value.pool == "window" and state.n_tracked == 1
    with pytest.raises(KVCacheExhaustedError) as short:
        state.extend(1, 100)
    assert short.value.pool == "full" and state.n_tracked == 1
    state.flush(1)
    assert state.extend(2, 4).ring == 0
    # the paged blocks run out AFTER the ring was taken: both go back
    with pytest.raises(KVCacheExhaustedError):
        state.extend(3, 100)
    assert state.n_tracked == 1 and state.free_rings == 0
    state.flush(2)
    assert state.free_rings == 1


def _scheduler(eng, **over):
    return ServingScheduler(eng, ServingSchedulerConfig(**dict(
        dict(max_num_batched_tokens=32, prefill_chunk=4,
             prefill_mode="chunked", decode_chunk=1), **over)))


@pytest.mark.parametrize("short,engine", [
    ("window", dict()),  # the module's engine as it is: four rings
    ("full", dict(num_kv_rings=8, num_kv_blocks=12)),
])
def test_a_request_that_a_pool_cannot_take_waits_and_none_fails(engines, short,
                                                                engine):
    """Six requests of 20 + 4 tokens (3 blocks each) against four rings,
    or against 12 paged blocks: admission takes a request only where
    BOTH pools can, the others wait in the queue, the counter names the
    pool, and every one finishes at its asked length with the tokens a
    scheduler with room gives."""
    def run(**cfg):
        eng = engines(**cfg)
        sched = _scheduler(eng)
        rng = np.random.default_rng(3)
        rids = [sched.submit(rng.integers(0, 128, 20).tolist(),
                             max_new_tokens=4) for _ in range(6)]
        sched.run()
        assert eng.state.n_tracked == 0
        assert eng.state.free_rings == eng.state.num_rings
        return sched, [sched.finished[r] for r in rids]

    sched, reqs = run(**engine)
    roomy, want = run(num_kv_rings=8)
    assert all(r.finish_reason == "length" and len(r.output) == 4
               for r in reqs)
    assert [r.output for r in reqs] == [r.output for r in want]
    other = {"window": "full", "full": "window"}[short]
    assert sched.counters[f"admit_waits_{short}_pool"] > 0
    assert sched.counters[f"admit_waits_{other}_pool"] == 0
    assert sched.counters["preemptions"] == 0
    assert roomy.counters["admit_waits_window_pool"] \
        == roomy.counters["admit_waits_full_pool"] == 0


def test_the_counters_count_a_sequence_once_a_step(engines):
    """A prompt of 41 tokens in chunks of 8 and 3 answers: a chunk's
    rows are one read; a windowed layer's read stops at the window."""
    eng = engines()
    sched = _scheduler(eng, prefill_chunk=8)
    sched.submit(list(range(41)), max_new_tokens=3)
    sched.run()
    c = sched.counters
    contexts = [8, 16, 24, 32, 40, 41, 42, 43]
    assert c["steps"] == len(contexts)
    assert c["kv_full_tokens"] == sum(contexts)
    assert c["kv_window_tokens"] == sum(min(x, 16) for x in contexts)
    assert c["kv_rings_live"] == len(contexts)
    # 43 tokens are 6 blocks of 8 through a ring of 4
    assert c["kv_ring_blocks_recycled"] == 2
    # another model's scheduler counts none of it
    assert c["kv_live_blocks"] > 0 and c["state_slots_live"] == 0


def test_what_a_ring_cannot_do_is_refused_where_the_engine_is_built(model,
                                                                     engines):
    mcfg, params = model
    assert E._POOL_CANNOT["ring"] == {"mesh", "int8_kv", "page_transfer",
                                      "prefix_credit", "speculation"}
    for feature in E._POOL_CANNOT["ring"]:
        assert not E.pools_can(mcfg, feature)
        with pytest.raises(NotImplementedError, match="ring pool"):
            E.refuse_for_pools(mcfg, feature)
    assert E.pools_can(mcfg, "weight_quantization")
    with pytest.raises(NotImplementedError, match="int8_kv"):
        engines.fresh(kv_cache_dtype="int8")  # the build raises
    eng = engines(prefix_cache={"enabled": True})
    assert not eng.state.credit_prefix
    eng.put([1], [np.arange(20, dtype=np.int32)])
    with pytest.raises(NotImplementedError, match="page_transfer"):
        eng.export_kv(1)
    with pytest.raises(NotImplementedError, match="speculation"):
        ServingScheduler(eng, ServingSchedulerConfig(),
                         speculative={"k": 2, "ngram": 2})
    # a chunk of more rows than a ring takes in one step
    with pytest.raises(ValueError, match="more than a ring takes"):
        eng.put([1], [np.arange(9, dtype=np.int32)])
    eng.flush(1)
    # a model of ONE window, or none, has no ring and takes its old path
    for hf in (dict(HF, layer_types=["full_attention"] * 8),
               dict(HF, layer_types=["sliding_attention"] * 8)):
        one = config_from_hf(hf, max_seq=512)
        assert not one.mixed_windows and E.pool_kinds(one) == ("kv",)
        assert M.ring_blocks(one, 8, 32) == 0
        assert one.attention_window_pattern is None
    assert one.sliding_window == 16 and one.rope_scaling_type == "none"


# -- the configuration and the import --------------------------------------

def test_the_cut_keeps_the_published_widths_and_builds():
    hf = json.loads(CUT.read_text())
    published = json.loads(PUBLISHED.read_text())
    assert set(hf["reduced"]) == {"num_hidden_layers", "layer_types",
                                  "mlp_layer_types"}
    for key, value in published.items():
        if key.startswith("_"):
            continue
        if key in hf["reduced"]:
            assert hf["reduced"][key]["published"] == value
            assert hf["reduced"][key]["here"] == hf[key]
        else:
            assert hf[key] == value, key
    assert hf["layer_types"] == PERIOD * 2 and hf["stands_for"]
    assert {"ring_blocks", "full_pool", "window_pool", "yarn_truncate",
            "no_qk_norm", "mtp_head"} <= set(hf["assumed"])
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == \
        (2304, 32, 4, 128)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.d_ff) == (64, 8, 896)
    assert cfg.moe_norm_topk_prob and cfg.moe_dropless
    assert not cfg.tie_embeddings and cfg.vocab_size == 98304
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == 3_794_966_784
    assert shapes["layers"]["w_in"].shape == (8, 64, 2304, 896)
    config = E.InferenceConfig(**hf["serve"]["engine"])
    assert E.ring_geometry(cfg, config) == (96, 10)
    pools = E.pool_bytes(cfg, config, jnp.bfloat16)
    block = 128 * 2048
    assert pools == {"kv": 2 * 3073 * block, "ring": 6 * 961 * block,
                     "state": 0}


UNREAD = [("use_qk_norm", True), ("attn_logit_softcapping", 50.0),
          ("attention_sinks", 4), ("n_shared_experts", 1),
          ("num_shared_experts", 1), ("routed_scaling_factor", 2.5),
          ("kv_lora_rank", 64), ("conv_L_cache", 3)]


@pytest.mark.parametrize("key,value", UNREAD, ids=[k for k, _ in UNREAD])
def test_the_import_refuses_a_block_key_it_does_not_read(key, value):
    with pytest.raises(ValueError, match=f"does not read '{key}'"):
        config_from_hf(dict(HF, **{key: value}))


OTHERS = ["tiny-mistral", "tiny-olmoe", "tiny-pangu", "tiny-lfm2",
          "tiny-qwen3next", "tiny-granite4h"]
# the families whose step programs are pinned: the six above, this
# file's own (its hashes are what the tree gave at 518e737, PR 50) and
# the newest, pinned as PR 51 left it
PINNED = OTHERS + ["tiny-mellum2", "tiny-nemotron3"]


@pytest.mark.parametrize("key", ["rope_parameters", "mlp_layer_types"])
@pytest.mark.parametrize("name", OTHERS)
def test_the_keys_stay_an_error_for_every_other_architecture(name, key):
    hf = dict(_tiny(name), **{key: HF[key]})
    with pytest.raises(ValueError, match=key):
        config_from_hf(hf)


# -- the older families' programs -------------------------------------------

@pytest.mark.parametrize("kernels", [True, False], ids=["pallas", "xla"])
@pytest.mark.parametrize("name", PINNED)
def test_an_older_familys_step_program_is_the_parents(name, kernels):
    """The families the benchmark holds take the path they took: their
    step programs' text hashes as it did at the parent commit (the file
    holds what `_step_program.step_program` gave with that tree on the
    path; the `pallas` entries are PR 49's, whose kernels went behind a
    jit of their own). PR 51 threaded groups of B and C through
    ops/pallas/ssm_state.py: every `pallas` entry, Granite's too, came
    out as it was (what the chip runs); `tiny-granite4h/xla` alone was
    re-captured (the loop over rows in XLA, the CPU's oracle, reads B
    and C a head now), and `tiny-nemotron3` is pinned as that PR left
    it. PR 52 re-captured the four `pallas` entries whose K/V pools take
    paged_kv_write's row copies (Mistral, OLMoE, Qwen3-Next, Mellum 2:
    the write is another kernel body; the tiny LFM2, Granite and
    Nemotron-H pools are not whole tiles and keep the block path, and no
    `xla` entry moved). PR 59 re-captured every routed family's two
    entries (all but Mistral's): the gates' chosen scores come from a
    comparison (moe/dropless.py chosen_scores) where the programs held
    `take_along_axis` or top_k's own values; the same floats. A PR that
    changes a family's program ON PURPOSE
    re-captures the
    file (`PYTHONPATH=. python tests/test_mellum2.py`) and says so;
    JAX's version changes it too."""
    text = step_program(name, kernels)[1]
    pinned = json.loads(HASHES.read_text())
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        pinned[f"{name}/{'pallas' if kernels else 'xla'}"]


if __name__ == "__main__":
    HASHES.write_text(json.dumps({
        f"{name}/{'pallas' if k else 'xla'}": hashlib.sha256(
            step_program(name, k)[1].encode()).hexdigest()[:16]
        for name in PINNED for k in (True, False)}, indent=1) + "\n")
