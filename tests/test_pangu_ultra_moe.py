"""openPangu-Ultra-MoE (`pangu_ultra_moe`) on the normal serving path at
a tiny size on the CPU, against the plain float32 reference of
benchmarks/reference/pangu_ultra_moe.py: latent attention through the
paged latent cache (whole-prompt prefill in the naive form, chunks and
single-token steps in the absorbed form, a shared-table iteration of
mixed rows), sandwich norm, a leading dense layer, a shared expert
beside a HELD share of the routed experts, the share test, the mutants
that must fail, the latent kernels against their oracles, and the
cut's file.

Everything is float32 with seeded weights: 1 dense + 2 routed layers,
d 64, 4 heads of [nope 16; rope 8], value 16, latent 32, 16 routed
experts top-4 of which this chip holds 4 (experts 4..7), one shared.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import pytest
from _family import engines, family, model  # noqa: F401 - the contract's fixtures

from benchmarks.reference import pangu_ultra_moe as ref
from deepspeed_tpu.inference import ServingScheduler, ServingSchedulerConfig
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import paged_attention as PA
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

ROOT, BENCH = F.ROOT, F.BENCH
HF = {"attention_bias": False, "first_k_dense_replace": 1,
      "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
      "kv_lora_rank": 32, "max_position_embeddings": 256,
      "model_type": "pangu_ultra_moe", "moe_intermediate_size": 32,
      "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
      "num_attention_heads": 4, "num_experts_per_tok": 4,
      "num_hidden_layers": 3, "num_key_value_heads": 4,
      "num_nextn_predict_layers": 0, "q_lora_rank": 24,
      "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-05,
      "rope_theta": 25600000, "routed_scaling_factor": 2.5,
      "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 16,
      "vocab_size": 256,
      "reduced": {"n_routed_experts": {"published": 16, "here": 4}},
      "experts_held": {"start": 4, "count": 4, "of": 16}}
# the same model with every routed expert on one chip
UNCUT = dict(HF, n_routed_experts=16, reduced={}, experts_held={})

# float32 on both sides, logits up to 2.7. The system reassociates (the
# absorbed form multiplies W_uk into the query before the key, the
# expert scan's running sum, the online softmax of the latent walk),
# which moves a logit by ~1e-6 (measured here: 1.1e-6 at the prefill,
# 1.3-1.5e-6 at the absorbed steps); a router tie flipped by that noise
# would move one by ~0.05, and none is at these seeds. The reference on
# weights rounded to bf16 differs by 1.8e-2, the mutants by 4.4e-2
# (softmax for sigmoid), 0.12 (one expert fewer), 0.16 (a float8
# cache), 0.31 (no scaling factor) and 1.8 (no post-sublayer norm): all
# at least 89 x the limit.
LOGITS_ATOL = 2e-4
# the routed block alone, outputs up to 0.2: float32 reassociation
# (measured 1.5e-8); a dropped scaling factor moves them by 0.097
BLOCK_ATOL = 1e-6
ENGINE = dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=32,
              max_batch_size=16, min_prefill_bucket=32)


# every norm scale matters (T.init gives ones)
FAMILY = F.Family(
    hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE,
    jig=lambda k, v, key: (1 + 0.3 * jax.random.normal(key, v.shape)
                           if "scale" in k else v))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, HF["vocab_size"], (2, 80))


# -- the configuration ---------------------------------------------------

def test_the_cut_builds_the_share_at_published_widths():
    hf = json.loads(
        (BENCH / "configs/openpangu-ultra-moe-serve-l5-ep32.json").read_text())
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    assert (cfg.n_dense_layers, cfg.n_layers, cfg.depth) == (1, 4, 5)
    assert (cfg.d_model, cfg.n_heads, cfg.ff_dim, cfg.dense_d_ff) == \
        (7680, 128, 2048, 18432)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert cfg.latent_dim == 576 and PA.latent_lanes(cfg.latent_dim) == 640
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (256, 8, (0, 8))
    assert cfg.moe_scoring == "sigmoid" and cfg.routed_scaling_factor == 2.5
    assert cfg.sandwich_norm and cfg.n_shared_experts == 1
    assert cfg.vocab_size == 19200 and not cfg.tie_embeddings
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["w_router"].shape == (4, 7680, 256)
    assert shapes["layers"]["w_in"].shape == (4, 8, 7680, 2048)
    assert shapes["dense_w_in"].shape == (1, 7680, 18432)
    # the file's own count: matrices alone, norms apart
    flat = F.one_stack(cfg, shapes)
    n = sum(int(np.prod(s.shape)) for k, s in flat.items() if "scale" not in k)
    assert n == 3_409_018_880


def test_the_cuts_file_keeps_the_published_widths():
    from benchmarks.tests import helpers

    helpers.check_cell(ROOT, "serve-pangu-longchat-saturated")
    cfg = json.loads(
        (BENCH / "configs/openpangu-ultra-moe-serve-l5-ep32.json").read_text())
    helpers.check_published_widths(cfg, BENCH)
    assert sorted(cfg["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "num_nextn_predict_layers", "vocab_size"]
    with pytest.raises(AssertionError):       # a width may never be cut
        helpers.check_published_widths(dict(cfg, kv_lora_rank=256), BENCH)


def test_the_mtp_block_is_refused_not_dropped():
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        config_from_hf(dict(HF, num_nextn_predict_layers=1))


@pytest.mark.parametrize("key,value", [
    ("kv_lora_rank", 512), ("n_shared_experts", 1),
    ("first_k_dense_replace", 3), ("sandwich_norm", True),
    ("routed_scaling_factor", 2.5), ("q_lora_rank", 1536)])
def test_a_block_key_a_known_architecture_does_not_read_is_refused(key, value):
    """A Llama-family name carrying a key that changes what a block
    computes would otherwise be served as a plain dense model."""
    llama = {"architectures": ["LlamaForCausalLM"], "vocab_size": 128,
             "hidden_size": 64, "intermediate_size": 96,
             "num_hidden_layers": 2, "num_attention_heads": 4}
    config_from_hf(llama)                                   # as it is: fine
    config_from_hf(dict(llama, **{key: None}))              # absent or null
    with pytest.raises(ValueError, match=key):
        config_from_hf(dict(llama, **{key: value}))


def test_the_training_forward_refuses_this_family(model, tokens):
    mcfg, params = model
    with pytest.raises(NotImplementedError, match="kv_lora_rank"):
        T.forward(params, jnp.asarray(tokens), mcfg)


# -- serving against the reference -----------------------------------------

@pytest.fixture(scope="module")
def served(engines, tokens):
    """Whole-prompt prefill (naive form), a 3-token chunk and two
    single-token steps (absorbed form through the latent cache) of both
    rows; the logits each put() returned."""
    eng = engines()
    f, n, k = tokens.astype(np.int32), 50, 3
    got = [eng.put([0, 1], [r[:n - k] for r in f]),
           eng.put([0, 1], [r[n - k:n] for r in f]),
           eng.put([0, 1], [r[n:n + 1] for r in f]),
           eng.put([0, 1], [r[n + 1:n + 2] for r in f])]
    eng.flush(0), eng.flush(1)
    return eng, [np.asarray(g) for g in got], [n - k - 1, n - 1, n, n + 1]


def test_serving_through_the_latent_cache_matches_the_reference(
        model, tokens, served):
    mcfg, params = model
    eng, got, pos = served
    assert eng.cache.v == [] and len(eng.cache.k) == mcfg.depth == 3
    assert eng.cache.k[0].shape == (33, 32, 128)      # 40 values, one lane tile
    assert eng.kv_bytes_per_token() == 3 * 128 * 4
    want = F.ref_logits(FAMILY, params, tokens)
    assert np.abs(want).max() > 0.5                   # the logits are not flat
    for step, p in enumerate(pos):
        assert np.abs(got[step] - want[:, p]).max() < LOGITS_ATOL, step


def test_the_absorbed_form_agrees_with_the_naive_form(engines, tokens, served):
    """The same positions' logits from a whole-prompt prefill (keys and
    values up-projected for every head) and from chunk rows over the
    cache (queries moved into the latent space)."""
    eng, got, _ = served
    f = tokens.astype(np.int32)
    naive = np.asarray(eng.put([7, 8], [r[:50] for r in f]))   # one prefill
    eng.flush(7), eng.flush(8)
    assert np.abs(naive - got[1]).max() < LOGITS_ATOL          # 47 + chunk of 3


@pytest.mark.parametrize("mutant", ref.MUTANTS)
def test_a_wrong_model_fails_the_written_tolerance(model, tokens, served,
                                                   mutant):
    """No scaling factor, softmax for sigmoid, one expert fewer, no
    post-sublayer norm, a float8 cache: far outside the limit."""
    _, params = model
    _, got, pos = served
    wrong = F.ref_logits(FAMILY, params, tokens, mutant)
    for step, p in enumerate(pos):
        assert np.abs(got[step] - wrong[:, p]).max() > 80 * LOGITS_ATOL


def test_a_bf16_reference_fails_the_written_tolerance(model, tokens, served):
    _, params = model
    _, got, pos = served
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    wrong = F.ref_logits(FAMILY, rounded, tokens)
    for step, p in enumerate(pos):
        assert np.abs(got[step] - wrong[:, p]).max() > 50 * LOGITS_ATOL


def test_a_shared_table_iteration_of_mixed_rows_matches_the_reference(
        model, engines, tokens):
    """The scheduler's own program: decode rows and prefill-chunk rows
    of several requests in one call, every chunk's rows on one table.
    Its greedy tokens are the reference's argmax at every position."""
    mcfg, params = model
    eng = engines.fresh()  # its own: what ITS tracker saw after ITS warm-up
    eng.warmup(widths=[8, 16], footprint=False)
    sched = ServingScheduler(
        eng, ServingSchedulerConfig(max_num_batched_tokens=16,
                                    prefill_chunk=6, warmup=False), seed=0)
    prompts = [tokens[0, :40].astype(np.int32), tokens[1, :23].astype(np.int32),
               tokens[0, 40:75].astype(np.int32)]
    rids = [sched.submit(p, max_new_tokens=5) for p in prompts]
    sched.run()
    c = sched.counters
    assert c["moe_token_expert_pairs"] == c["batched_tokens"] * mcfg.moe_top_k
    assert c["mla_cache_tokens"] > c["batched_tokens"] > 0
    # the K/V walk's rule groups nothing in a latent model, and four
    # heads stack no tile; a table's blocks are fetched once a table
    assert c["kv_grouped_rows"] == c["mla_grouped_rows"] == 0
    assert 0 < c["kv_block_reads"] < c["kv_live_blocks"]
    assert not eng.recompile_tracker.findings
    for rid, p in zip(rids, prompts):
        out = sched.finished[rid].output
        seq = np.concatenate([p, out]).astype(np.int32)
        want = F.ref_logits(FAMILY, params, seq[None])[0]
        assert out == [int(want[len(p) - 1 + j].argmax()) for j in range(5)]


def test_the_scheduler_counts_a_latent_step_by_the_latent_walks_rule(engines):
    """mla_grouped_rows / kv_block_reads of a dispatched step are
    latent_walk_reads of its host arrays (at sixteen heads a tile is
    eight rows), and the K/V rule's kv_grouped_rows stays 0."""
    eng = engines.fresh()  # its own: the test gives it sixteen heads
    sched = ServingScheduler(eng, ServingSchedulerConfig(warmup=False), seed=0)
    eng.cfg = dataclasses.replace(eng.cfg, n_heads=16)
    NB = eng.config.blocks_per_seq
    tables = np.arange(16 * NB, dtype=np.int32).reshape(16, NB) % 31
    tables[3:] = tables[3]                # a chunk of 13 rows on one table
    ctx = np.asarray([40, 7, 99, *range(50, 63)], np.int32)
    sched._count_tokens(16, 16, ctx, tables=tables)
    reads, tiled = PA.latent_walk_reads(tables, ctx, 32, 16)
    assert tiled == 8                     # rows 8..15: the one whole tile
    c = sched.counters
    assert (c["mla_grouped_rows"], c["kv_block_reads"]) == (tiled, reads)
    assert c["kv_grouped_rows"] == 0
    assert c["kv_live_blocks"] - reads == 12 * 2   # twelve rows rode


# -- the latent kernels ----------------------------------------------------

def test_the_latent_kernels_match_their_oracles(pallas_interpret):
    """Rows of one chunk on one table (contexts rising, one crossing a
    block), decode rows with tables of their own, padding rows between
    them; the write with dropped slots and two rows into one block."""
    rng = np.random.default_rng(0)
    NBLK, bs, C, V, H = 40, 16, 128, 96, 4
    pool = jnp.asarray(rng.normal(size=(NBLK, bs, C)), jnp.float32)
    tA, tB, tC, pad = [3, 7, 9, 0, 0, 0], [1, 2, 4, 5, 6, 0], \
        [11, 12, 0, 0, 0, 0], [39] * 6
    tables = jnp.asarray([tA] * 5 + [tB] + [pad] + [tC] * 4 + [pad], jnp.int32)
    ctx = jnp.asarray([30, 31, 32, 33, 34, 70, 0, 14, 15, 16, 17, 0], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(PA.table_groups(tables)),
        [0, 0, 0, 0, 0, 1, 2, 3, 3, 3, 3, 4])
    q = jnp.asarray(rng.normal(size=(12, H, C)) * 0.3, jnp.float32)
    want = PA.paged_latent_attention_xla(q, pool, tables, ctx, V)
    got = jax.jit(lambda *a: PA.paged_latent_attention(*a, V))(
        q, pool, tables, ctx)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.all(np.asarray(got)[[6, 11]] == 0)        # padding rows: zeros
    new = jnp.asarray(rng.normal(size=(6, C)), jnp.float32)
    slots = jnp.asarray([35, -1, 3 * 16 + 2, 3 * 16 + 3, 100, -1], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(PA.paged_latent_write)(pool, new, slots)),
        np.asarray(PA.paged_latent_write_xla(pool, new, slots)))


def _chunk(table, first_ctx, n):
    return [(table, first_ctx + i) for i in range(n)]


_PAD = ("pad", 0)
# name: (heads, query rows a tile stacks, query rows a sub-tile, the rows
# as (table, context), rows the walk visits as tiles). Blocks of 16
# tokens, trips of 4 blocks, tiles of 4 rows but where the case says
_TILE_CASES = {
    # rows 2..12 on one table: tiles 1 and 2 are its inner rows, tiles 0
    # and 3 hold its edges beside decode rows and padding
    "a_chunk_off_the_tiles_boundary": (
        8, 32, 128,
        [("d0", 70), ("d1", 5), *_chunk("A", 20, 11), _PAD, _PAD, _PAD], 8),
    # one row a block longer than its neighbours (32 | 33) and, in the
    # second tile, a trip longer (64 | 65); sub-tiles of one row
    "a_tile_across_a_block_boundary": (
        8, 32, 8, [*_chunk("A", 30, 4), *_chunk("B", 62, 4)], 8),
    # the longest row fills its last block, and its last trip, exactly;
    # sub-tiles of two rows
    "a_tile_that_ends_on_a_block_boundary": (
        8, 32, 16, [*_chunk("A", 29, 4), *_chunk("B", 61, 4)], 8),
    # a tile of padding is one table's and visits nothing: zeros; a row
    # of context 0 on a live table beside it
    "a_tile_of_padding": (
        8, 32, 128, [_PAD] * 4 + [("A", 0), *_chunk("A", 17, 3)], 3),
    "decode_rows_alone": (
        8, 32, 128, [(f"d{i}", c) for i, c in
                     enumerate([70, 1, 16, 33, 64, 65, 90, 17])], 0),
    # six rows are not whole tiles of four: a row a step, from the shape
    "rows_that_are_not_whole_tiles": (8, 32, 128, _chunk("A", 30, 6), 0),
    # fewer heads stack more rows: 8 at the constants as they are
    "sixteen_heads_and_tiles_of_eight": (
        16, None, None, [*_chunk("A", 30, 8), ("d0", 40), *_chunk("B", 1, 7)],
        8),
}


@pytest.mark.parametrize("case", _TILE_CASES)
def test_a_tile_of_one_tables_rows_is_visited_together(
        case, monkeypatch, pallas_interpret):
    """The tiled latent walk against its oracle, against the walk of a
    row a step (bit for bit where no tile is one table's), and the
    host's count of tiled rows against the tiles the kernel's entry
    marks."""
    H, query_rows, sub_rows, rows, tiled = _TILE_CASES[case]
    rng = np.random.default_rng(5)
    NBLK, bs, NB, C, V = 64, 16, 6, 128, 64
    pool = jnp.asarray(rng.normal(size=(NBLK, bs, C)), jnp.float32)
    names = dict.fromkeys(t for t, _ in rows if t != "pad")
    blocks = iter(rng.permutation(NBLK - 1))
    table_of = {t: [int(next(blocks)) for _ in range(NB)] for t in names}
    table_of["pad"] = [NBLK - 1] * NB
    tables = np.asarray([table_of[t] for t, _ in rows], np.int32)
    ctx = np.asarray([c for _, c in rows], np.int32)
    q = jnp.asarray(rng.normal(size=(len(rows), H, C)) * 0.3, jnp.float32)

    def walk(query_rows):
        if query_rows is not None:
            monkeypatch.setattr(PA, "_LATENT_QUERY_ROWS", query_rows)
        if sub_rows is not None:
            monkeypatch.setattr(PA, "_LATENT_SUB", sub_rows)
        PA._latent_attention.clear_cache()
        try:
            return np.asarray(PA.paged_latent_attention(
                q, pool, jnp.asarray(tables), jnp.asarray(ctx), V))
        finally:
            PA._latent_attention.clear_cache()

    got = walk(query_rows)
    # (the oracle ONE program: op by op it is twenty small compiles)
    want = np.asarray(jax.jit(PA.paged_latent_attention_xla, static_argnums=4)(
        q, pool, jnp.asarray(tables), jnp.asarray(ctx), V))
    assert np.abs(got - want).max() < 1e-5
    assert np.all(got[ctx == 0] == 0)
    R = PA.latent_tile(len(rows), H)
    assert R == {"rows_that_are_not_whole_tiles": 1,
                 "sixteen_heads_and_tiles_of_eight": 8}.get(case, 4)
    # the host's counter and the kernel's entry mark the same rows
    marked = np.repeat(np.asarray(PA.latent_tiles(
        PA.table_groups(jnp.asarray(tables)), R)), R)
    reads, counted = PA.latent_walk_reads(tables, ctx, bs, H)
    assert counted == int(np.sum(marked & (ctx > 0)) * (R > 1)) == tiled
    longest = {}
    for (t, c), g in zip(rows, np.asarray(PA.table_groups(tables, np))):
        longest[g] = max(longest.get(g, 0), -(-c // bs))
    assert reads == sum(longest.values())
    alone = walk(H)  # a tile of one row: the walk of a row a step
    assert PA.latent_tile(len(rows), H) == 1
    assert np.abs(alone - want).max() < 1e-5
    if tiled == 0:
        np.testing.assert_array_equal(got, alone)


def test_the_kernel_engine_agrees_with_the_oracle_engine(
        engines, tokens, served, pallas_interpret):
    _, got, _ = served
    eng = engines()
    assert eng.resolved_impl == "pallas"
    f, n, k = tokens.astype(np.int32), 50, 3
    out = [eng.put([0, 1], [r[:n - k] for r in f]),
           eng.put([0, 1], [r[n - k:n] for r in f]),
           eng.put([0, 1], [r[n:n + 1] for r in f])]
    eng.flush(0), eng.flush(1)
    for a, b in zip(out, got):
        assert np.abs(np.asarray(a) - b).max() < LOGITS_ATOL


def test_a_context_longer_than_the_walks_buffers_is_refused(model, engines):
    pool = jax.ShapeDtypeStruct((8, 128, 640), jnp.bfloat16)
    # the two buffer sets beside a tile's scratch: 132 blocks at the most
    assert PA.latent_walk_fits(72, pool) and PA.latent_walk_fits(132, pool)
    assert not PA.latent_walk_fits(133, pool)
    assert not PA.latent_walk_fits(256, pool)
    assert not PA.latent_walk_fits(
        8, jax.ShapeDtypeStruct((8, 128, 576), jnp.bfloat16))
    # the engine says so when it is built, whatever the call would do
    mcfg, params = model
    long = dict(kv_block_size=128, max_seq_len=512 * 128,
                num_kv_blocks=520, decode_impl="pallas")
    longer = dataclasses.replace(mcfg, max_seq=512 * 128), params
    with pytest.raises(ValueError, match="latent walk's VMEM"):
        engines.fresh(model=longer, **long)
    # another configuration
    engines.fresh(model=longer, **dict(long, decode_impl="xla"))


# -- the routed block and the share ----------------------------------------

def test_the_held_share_scans_its_experts_whatever_the_rows(model):
    mcfg, _ = model
    assert [M.expert_path(t, mcfg) for t in (1, 8, 128, 8192)] == ["scan"] * 4
    whole = dataclasses.replace(mcfg, experts_held=None)
    assert M.expert_path(4, whole) == "ragged"     # every expert held: by rows
    # where kernels run, the cell's held experts (8 of 7680 x 2048) take
    # the one streamed pass at every width its buffers fit, and the
    # scan beyond (a whole prompt of the logits check)
    lp = F.expert_stacks(8, 7680, 2048)
    # (never the grouped entry, whatever the rows: how many pairs reach
    # the held experts is known on the device alone)
    assert [M.expert_path(t, mcfg, lp, True)
            for t in (1, 8, 128, 304, 352, 8192)] == ["stream"] * 5 + ["scan"]
    assert M.expert_path(128, mcfg, lp, False) == "scan"


def test_the_routed_block_alone_matches_the_reference(model):
    mcfg, params = model
    lw, h = F.block(params, 24)
    got = np.asarray(M._mlp(h, lw, mcfg))
    with jax.default_matmul_precision("highest"):
        routed, shared, _ = ref.moe_parts(h, lw, HF)
        want = np.asarray(routed + shared)
        unscaled = np.asarray(ref.moe_parts(h, lw, HF, "no_scaling")[0] + shared)
    assert np.abs(want).max() > 0.05
    assert np.abs(got - want).max() < BLOCK_ATOL
    assert np.abs(got - unscaled).max() > 1000 * BLOCK_ATOL


def test_the_shares_add_up_to_the_uncut_layer(model):
    """THE share test: the four shares' routed parts (experts 0..3,
    4..7, 8..11, 12..15, each computed by the PROGRAM's expert layer
    told which it holds), with the shared expert counted once, are what
    the uncut reference gives for the whole layer."""
    mcfg, params = model
    lw, h = F.block(params, 24)
    rng = jax.random.PRNGKey(9)
    every = {n: jax.random.normal(jax.random.fold_in(rng, i),
                                  (16,) + lw[n].shape[1:]) * 0.08
             for i, n in enumerate(("w_gate", "w_in", "w_out"))}
    with jax.default_matmul_precision("highest"):
        r, s, _ = ref.moe_parts(h, dict(lw, **every), UNCUT)
        whole, shared = np.asarray(r + s), np.asarray(s)
        # the reference's own shares add up too
        parts = [ref.moe_parts(
            h, dict(lw, **{n: w[a:a + 4] for n, w in every.items()}),
            dict(HF, experts_held={"start": a}))[0] for a in (0, 4, 8, 12)]
        assert np.abs(np.asarray(sum(parts)) + shared - whole).max() < BLOCK_ATOL
    total = np.zeros_like(whole)
    for start in (0, 4, 8, 12):
        cfg = dataclasses.replace(mcfg, experts_held=(start, 4))
        mine = dict(lw, **{n: w[start:start + 4] for n, w in every.items()})
        total += np.asarray(M._mlp(h, mine, cfg)) - shared
    assert np.abs(whole - shared).max() > 0.05       # the routed part matters
    assert np.abs(total + shared - whole).max() < 4 * BLOCK_ATOL
    # and one share alone is NOT the layer
    assert np.abs(np.asarray(M._mlp(h, lw, mcfg)) - whole).max() > 0.01


# -- the rehearsal: the cell's runner kind ----------------------------------

def test_the_rehearsal_cell_names_the_real_cells_reference_and_kind():
    """`tiny-pangu-serve-sat` rehearses the real cell: the same
    reference through the same runner kind. The run itself, and every
    test the accepted benchmark holds kind `serve` to, is
    benchmarks/tests/test_serve_aliases.py's (this family's mix states
    `serve_longctx`, the `serve` runner under the kind that a context
    over 4,096 needs: runners/serve_longctx.py)."""
    from benchmarks import harness
    from benchmarks.tests import helpers

    rc = next(rc for rc in helpers.rehearsal_cells()
              if rc["name"] == "tiny-pangu-serve-sat")
    assert (rc["reference"], rc["runner"]) == ("pangu_ultra_moe", "serve_longctx")
    real = harness.load_cell("serve-pangu-longchat-saturated")
    assert real.traffic["runner"] == rc["runner"]
    assert real.config["reference"] == rc["reference"]
    assert harness.load_module(
        BENCH / "runners" / "serve_longctx.py").run.__module__.endswith("serve")
    # the mix the real cell offers is longer than the older cells' context
    lens = real.traffic["prompt_len"]["max"] + real.traffic["answer_len"]["max"]
    assert 4096 < lens <= real.config["serve"]["engine"]["max_seq_len"]
