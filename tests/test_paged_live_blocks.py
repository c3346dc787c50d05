"""The shared-table decode attention (`paged_decode_attention`, unfused
and unquantised: trace name `paged_decode_grid`) walks, for each row,
the LIVE blocks of that row's table and nothing else.

- the kernel against the gather oracle, interpreted, as cases of one
  test: a prefill chunk's rows on one table among distinct rows, the
  context edges around a block boundary, both serving cells' head
  layouts, a window shorter than the context, ALiBi, head dim 64
  (which the (S, NB) grid keeps); and the GROUPS the walk makes of
  adjacent rows of one table (PR 46): every size around the static
  bound, several groups between decode and pad rows, rows whose live
  blocks differ inside a group, equal tables that are not adjacent;
- dead table slots are not read, not merely masked; a group's blocks
  are fetched once, and rows walked alone are bit for bit what they are
  without a group beside them;
- whole-tile pools of fewer than 8 KV heads in two wide heads (PR 64:
  Mellum 2's 4 x 128 and LFM2's 8 x 64 as [.., 2, 256]): the walk and
  the fused write against the unpacked float32 attention, over decode
  rows, a chunk of 32 (two groups of 16), a ring two turns deep and
  dead slots that name a block of NaN;
- which case takes which kernel, read from the traced program;
- AOT compiles for a DESCRIBED v5e at both serving cells' shapes (no
  chip; the topology is described inside a module-scoped fixture and
  every compile runs in this process, as benchmarks/tests/
  test_aot_kernels.py does);
- the scheduler's `kv_live_blocks` counter and its reader.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import deepspeed_tpu.models.transformer as T
from deepspeed_tpu.inference import (
    ServingScheduler, ServingSchedulerConfig, init_inference)
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.ops.attention import alibi_slopes
import deepspeed_tpu.ops.pallas.paged_attention as PA
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_decode_attention_xla)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _case(H=4, KV=2, D=128, bs=16, NB=4, ctx=(5, 33, 64), chunk=None,
          window=0, alibi=False, dtype=jnp.float32, chunks=(), same=(),
          packed=False):
    """chunk / chunks: (first row, rows) runs that share the first row's
    table; same: (row, other) pairs of NON-adjacent rows on one table;
    packed: the pool is [blocks, bs, KV / 2, 2 * D] (kv_pack)."""
    chunks = tuple(chunks) + ((chunk,) if chunk else ())
    return dict(H=H, KV=KV, D=D, bs=bs, NB=NB, ctx=tuple(ctx), chunks=chunks,
                window=window, alibi=alibi, dtype=dtype, same=same,
                packed=packed)


def _rising(first, n):
    """Contexts of a chunk's rows: first, first + 1, ..."""
    return tuple(range(first, first + n))


CASES = {
    # a 5-token chunk (rows 2-6: one table, ctx rising by one across a
    # block boundary) between distinct rows
    "chunk_among_distinct_rows": _case(
        ctx=(9, 40, 14, 15, 16, 17, 18, 64), chunk=(2, 5)),
    # pad row, first token, and the edges of a 128-token block
    "ctx_edges_block_128": _case(
        bs=128, NB=2, ctx=(0, 1, 127, 128, 129, 256, 0, 200)),
    "gqa_32q_8kv_bf16": _case(
        H=32, KV=8, ctx=(1, 16, 17, 49, 64), dtype=jnp.bfloat16),
    "mha_16q_16kv_group_padded": _case(
        H=16, KV=16, ctx=(3, 31, 32, 64)),
    "window_shorter_than_context": _case(
        ctx=(5, 33, 50, 64, 64), chunk=(3, 2), window=20),
    "window_not_a_block_multiple": _case(ctx=(7, 41, 64), window=37),
    "alibi": _case(H=8, KV=2, ctx=(2, 30, 64), alibi=True),
    # Mosaic takes no manual DMA of a 64-wide block: the grid keeps it
    "head_dim_64": _case(D=64, ctx=(0, 5, 33, 64)),
    "head_dim_64_window": _case(D=64, ctx=(5, 33, 64), window=20),
    # groups (PR 46): a run of n adjacent rows on one table between two
    # decode rows; the walk's static bound at Gp 8 is 32 rows, so 33
    # walk as 32 + 1 and 70 as 32 + 32 + 6
    **{f"group_of_{n}": _case(ctx=(40, *_rising(3, n), 64), chunk=(1, n),
                              NB=6)
       for n in (1, 2, 5, 31, 32, 33, 70)},
    # the chunk's tail: 5 rows where the chunk has 8, the batch padded
    # with rows of context 0 on the pad table (a group of their own)
    "chunk_tail_then_pad_rows": _case(
        ctx=(30, 50, *_rising(20, 5), 0, 0, 0, 0, 0),
        chunks=((2, 5), (7, 5))),
    "two_groups_between_decode_and_pad_rows": _case(
        ctx=(9, *_rising(1, 6), 40, 0, *_rising(30, 4), 64, 0, 0),
        chunks=((1, 6), (9, 4), (14, 2))),
    "three_groups_back_to_back": _case(
        ctx=(*_rising(14, 4), *_rising(1, 3), *_rising(60, 5), 33),
        chunks=((0, 4), (4, 3), (7, 5))),
    # the last group reaches the batch's end: its tile of rows starts
    # before its first row
    "group_at_the_end_of_the_batch": _case(
        ctx=(64, 12, 31, 7, 48, *_rising(15, 3)), chunk=(5, 3)),
    # rows of one group with one, two and three live blocks
    "group_rows_differ_in_live_blocks": _case(
        ctx=(5, *_rising(15, 20), 64), chunk=(1, 20)),
    "group_block_128": _case(
        bs=128, NB=3, ctx=(300, *_rising(120, 12), 1), chunk=(1, 12)),
    # every row its own window; the group's span starts at its
    # shortest row's window and ends at its longest row's context
    "group_window": _case(
        ctx=(5, *_rising(30, 25), 64), chunk=(1, 25), window=20),
    "group_window_not_a_block_multiple": _case(
        ctx=(*_rising(44, 9), 50), chunk=(0, 9), window=37, NB=5),
    "group_alibi": _case(H=8, KV=2, ctx=(2, *_rising(9, 12), 64),
                         chunk=(1, 12), alibi=True),
    "group_g1_mha_16kv": _case(H=16, KV=16, ctx=(31, *_rising(10, 10), 3),
                               chunk=(1, 10)),
    "group_g4_32q_8kv_bf16": _case(
        H=32, KV=8, ctx=(49, *_rising(12, 9), 64), chunk=(1, 9),
        dtype=jnp.bfloat16),
    "group_g8_16q_2kv_d256_bf16": _case(
        H=16, KV=2, D=256, ctx=(17, *_rising(28, 7), 1), chunk=(1, 7),
        dtype=jnp.bfloat16),
    # Gp 16: the bound is 256 / 16 = 16 rows, 20 walk as 16 + 4
    "group_g16_bound_16_rows": _case(
        H=32, KV=2, ctx=(9, *_rising(5, 20), 40), chunk=(1, 20)),
    # G 12 is not whole sublane tiles: no groups, every row alone
    "g12_rows_walk_alone": _case(
        H=24, KV=2, ctx=(9, *_rising(14, 5), 40), chunk=(1, 5)),
    # the packed head-dim-64 pool: 8 KV heads as 4 rows of 128 lanes
    "group_packed_d64": _case(
        H=32, KV=8, D=64, ctx=(33, *_rising(10, 11), 64), chunk=(1, 11),
        packed=True, dtype=jnp.bfloat16),
    "packed_d64_rows_alone": _case(
        H=8, KV=4, D=64, ctx=(0, 5, 33, 64), packed=True),
    # what a group's visit decides (PR 50): a block is masked only where
    # it can hold a dead column for some row of the group.
    # contexts 70..78 all end in block 4: blocks 0-3 wholly live
    "group_whole_blocks_but_the_last": _case(
        ctx=(40, *_rising(70, 9), 64), chunk=(1, 9), NB=6),
    # contexts 60..68 cross the edge at 64 inside the chunk: the rows
    # end in different blocks, two masked blocks after three whole ones
    "group_context_crosses_a_block_edge": _case(
        ctx=(40, *_rising(60, 9), 64), chunk=(1, 9), NB=6),
    # window 48 = three blocks of 16 over contexts 60..68: the window
    # starts at 12..20, on a block's edge for the row of context 64 and
    # inside a block for the rest; block 2 alone is wholly live
    "group_window_start_on_and_off_a_block_edge": _case(
        ctx=(*_rising(60, 9), 50), chunk=(0, 9), window=48, NB=6),
    # whole blocks in the middle of a windowed span: window 64 over
    # contexts 90..95, blocks 2-4 wholly inside every row's window
    "group_window_whole_blocks_between_its_edges": _case(
        ctx=(17, *_rising(90, 6)), chunk=(1, 6), window=64, NB=7),
    # 5 rows at the end of a batch of 40: the static tile of 32 rows
    # starts 27 rows BEFORE the group, over decode rows of other
    # contexts that the unmasked visit computes and never stores
    "group_of_5_after_35_decode_rows": _case(
        ctx=(*[(7 * i) % 90 + 1 for i in range(35)], *_rising(70, 5)),
        chunk=(35, 5), NB=6),
    # a span of ONE block (a trip takes one): masked, at block 128
    "group_span_of_one_block": _case(
        bs=128, NB=2, ctx=(200, *_rising(5, 12), 1), chunk=(1, 12)),
    # a span that starts whole and a row of context 0 inside the group
    # (its table the chunk's): every block of that group is masked
    "group_with_a_row_of_context_0": _case(
        ctx=(33, 70, 71, 0, 73, 74, 64), chunk=(1, 5), NB=6),
    "group_alibi_whole_blocks": _case(
        H=8, KV=2, ctx=(2, *_rising(70, 9), 64), chunk=(1, 9), alibi=True,
        NB=6),
    "group_window_alibi": _case(
        H=8, KV=2, ctx=(2, *_rising(60, 9), 64), chunk=(1, 9), alibi=True,
        window=32, NB=6),
    # Gp 16 at packed head dim 64: 4 KV heads as 2 rows of 128 lanes, 8
    # query heads a KV head, groups of 16 rows (20 walk as 16 + 4)
    "group_packed_d64_g16": _case(
        H=32, KV=4, D=64, ctx=(33, *_rising(70, 20), 64), chunk=(1, 20),
        packed=True, dtype=jnp.bfloat16, NB=6),
    # a block that is not whole sublane tiles (4 tokens)
    "group_block_of_4_tokens": _case(
        bs=4, NB=8, ctx=(9, *_rising(10, 6), 30), chunk=(1, 6)),
    "group_d256_whole_blocks": _case(
        H=16, KV=2, D=256, ctx=(17, *_rising(70, 7), 1), chunk=(1, 7),
        dtype=jnp.bfloat16, NB=6),
    # equal tables that are NOT adjacent do not group and stay correct
    "equal_tables_not_adjacent": _case(
        ctx=(20, 9, 21, 40, 22, *_rising(30, 3), 23),
        chunk=(5, 3), same=((0, 2), (0, 4), (0, 8))),
}


def _inputs(rng, c):
    S, NBLK = len(c["ctx"]), len(c["ctx"]) * c["NB"] + 1
    shape = (NBLK, c["bs"], c["KV"], c["D"])
    if c["packed"]:
        shape = (NBLK, c["bs"], c["KV"] // 2, 2 * c["D"])
    q = jnp.asarray(rng.normal(size=(S, c["H"], c["D"])), c["dtype"])
    kc = jnp.asarray(rng.normal(size=shape), c["dtype"])
    vc = jnp.asarray(rng.normal(size=shape), c["dtype"])
    tbl = rng.permutation(NBLK - 1)[: S * c["NB"]].reshape(S, c["NB"])
    tbl = tbl.astype(np.int32)
    for first, n in c["chunks"]:
        tbl[first:first + n] = tbl[first]
    for row, other in c["same"]:
        tbl[other] = tbl[row]
    return q, kc, vc, tbl, np.asarray(c["ctx"], np.int32)


@functools.lru_cache(maxsize=None)
def _one_program(fn, window):
    """Each side of the comparison as ONE program, as a step calls it (op
    by op, the oracle's and the entry's jnp are thirty small compiles a
    case), kept for the cases whose shapes are the same."""
    return jax.jit(functools.partial(fn, window=window))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("name", sorted(CASES))
def test_shared_table_attention_matches_oracle(rng, name):
    c = CASES[name]
    q, kc, vc, tbl, ctx = _inputs(rng, c)
    kw = {}
    if c["alibi"]:
        kw["alibi_slopes"] = jnp.asarray(alibi_slopes(c["H"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, ref = (_one_program(fn, c["window"])(
            q, kc, vc, jnp.asarray(tbl), jnp.asarray(ctx), **kw)
            for fn in (paged_decode_attention, paged_decode_attention_xla))
    tol = 3e-2 if c["dtype"] == jnp.bfloat16 else 2e-3
    real = ctx > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(ref, np.float32)[real],
                               rtol=tol, atol=tol)
    if c["D"] % 128 == 0 or c["packed"]:  # the walk: a pad row stores zeros
        assert not np.asarray(out, np.float32)[~real].any()


@pytest.mark.usefixtures("pallas_interpret")
def test_dead_slots_are_not_read(rng):
    """Table entries beyond a row's live blocks point at a block full of
    NaN: masking alone would still let 0 x NaN into the accumulator."""
    c = _case(NB=4, ctx=(0, 1, 16, 17, 40, 41, 42, 64), chunk=(4, 3))
    q, kc, vc, tbl, ctx = _inputs(rng, c)
    poison = kc.shape[0] - 1  # the one block no table names
    kc = kc.at[poison].set(jnp.nan)
    vc = vc.at[poison].set(jnp.nan)
    clean = tbl.copy()
    for s in range(len(ctx)):
        tbl[s, -(-int(ctx[s]) // c["bs"]):] = poison
    assert (tbl == poison).sum() >= 12
    with jax.default_matmul_precision("highest"):
        out = paged_decode_attention(q, kc, vc, jnp.asarray(tbl),
                                     jnp.asarray(ctx))
        want = paged_decode_attention(q, kc, vc, jnp.asarray(clean),
                                      jnp.asarray(ctx))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def _count_block_loads(monkeypatch):
    """Every block the walk fetches passes _arena_block once (K and V
    move together): count the calls the interpreted kernel makes."""
    loads = []
    clip = PA._arena_block

    def counting(idx, n_blocks):
        jax.debug.callback(lambda i: loads.append(int(i)), idx)
        return clip(idx, n_blocks)

    monkeypatch.setattr(PA, "_arena_block", counting)
    PA._attend_live_blocks.clear_cache()  # traced with the plain clamp before
    return loads


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("name,reads", [
    # 40 -> 3 blocks, the group 3..7 -> 1, 64 -> 4
    ("group_of_5", 3 + 1 + 4),
    # 33 rows of contexts 3..35: 32 rows (3..34: 3 blocks) + 1 (35: 3)
    ("group_of_33", 3 + 3 + 3 + 4),
    # rows of 15..34: one, two and three live blocks, fetched as three
    ("group_rows_differ_in_live_blocks", 1 + 3 + 4),
    # windows of 20 over contexts 30..54: slots 0 (30 - 20 = 10) to 3
    ("group_window", 1 + 4 + 2),
    # no groups where the tables are equal and apart: every row's own
    ("equal_tables_not_adjacent", 2 + 1 + 2 + 3 + 2 + 2 + 2),
    ("g12_rows_walk_alone", 1 + 5 * 1 + 2 + 3),
])
def test_a_groups_blocks_are_fetched_once(rng, monkeypatch, name, reads):
    c = CASES[name]
    q, kc, vc, tbl, ctx = _inputs(rng, c)
    loads = _count_block_loads(monkeypatch)
    try:
        out = paged_decode_attention(q, kc, vc, jnp.asarray(tbl),
                                     jnp.asarray(ctx), window=c["window"])
        out.block_until_ready()
        jax.effects_barrier()
    finally:
        PA._attend_live_blocks.clear_cache()
    assert len(loads) == reads
    # and each fetched block is one the fetching row's table names
    assert set(loads) <= set(tbl.ravel().tolist())
    lead = PA.walk_groups(tbl, PA._group_rows(max(c["H"] // c["KV"], 8),
                                              len(ctx)), np)
    grouped = {first + i for first, n in c["chunks"] for i in range(1, n)}
    if name == "g12_rows_walk_alone":  # G 12: not whole sublane tiles
        grouped = set()
    assert set(np.flatnonzero(lead != np.arange(len(ctx)))) <= grouped
    assert (lead != np.arange(len(ctx))).sum() >= len(grouped) - 2


def _chunk_call(first_ctx, bs, NB, window=0, decode=(), ring=0):
    """Host arrays of one shared-table call: decode rows, then a chunk
    of 32 rows of contexts first_ctx .. first_ctx + 31 on one table (a
    ring's table names its R blocks again and again)."""
    ctx = np.asarray([*decode, *_rising(first_ctx, 32)], np.int64)
    tbl = np.arange(len(ctx) * NB, dtype=np.int32).reshape(len(ctx), NB)
    if ring:
        tbl = tbl[:, :1] + np.arange(NB, dtype=np.int32)[None, :] % ring
    tbl[len(decode):] = tbl[len(decode)]
    return tbl, ctx


@pytest.mark.parametrize("what,call,window,masked,visited", [
    # the last chunk of a 8k prompt in a full layer: 64 blocks, of which
    # the last alone can hold a dead column (8160 = 63.75 blocks)
    ("8k_chunk_full_layer", _chunk_call(8160, 128, 80, decode=(3000, 17)),
     0, 1, 64),
    # contexts 8180..8211 cross into block 64: two masked of 65
    ("8k_chunk_across_an_edge", _chunk_call(8180, 128, 80), 0, 2, 65),
    # the same chunk in a windowed layer, a ring of 10 blocks: window
    # starts 7156..7187 lie inside blocks 55 and 56, the contexts end in
    # 63 and 64: four masked of ten
    ("8k_chunk_ring_of_10", _chunk_call(8180, 128, 80, ring=10),
     1024, 4, 10),
    ("8k_chunk_ring_of_10_aligned", _chunk_call(8160, 128, 80, ring=10),
     1024, 2, 9),
    # a prompt's first chunk: one block, masked
    ("first_chunk", _chunk_call(1, 128, 32, decode=(300,)), 0, 1, 1),
    # the chat cells' second chunk under Mistral's window of 4096
    ("chat_chunk_wide_window", _chunk_call(129, 128, 32), 4096, 1, 2),
    # rows alone are in neither count
    ("decode_rows_alone", (np.arange(12, dtype=np.int32).reshape(3, 4),
                           np.asarray([5, 200, 300])), 0, 0, 0),
])
def test_the_unmasked_visit_is_taken_where_it_should_be(what, call, window,
                                                        masked, visited):
    """walk_masks counts, by the kernel's own predicate, the blocks a
    call's groups visit WITH the compares and the select: the edges of
    a span, never its middle."""
    tbl, ctx = call
    assert PA.walk_masks(tbl, ctx, 128, 8, window) == (masked, visited)
    if visited > 4:
        assert masked <= (4 if window else 2)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("name,masked,visited", [
    ("group_whole_blocks_but_the_last", 1, 5),
    ("group_context_crosses_a_block_edge", 2, 5),
    ("group_window_start_on_and_off_a_block_edge", 4, 5),
    ("group_window_whole_blocks_between_its_edges", 2, 5),
    ("group_with_a_row_of_context_0", 5, 5),
])
def test_the_kernel_masks_the_blocks_the_count_names(rng, monkeypatch, name,
                                                     masked, visited):
    """The interpreted kernel asks _wholly_live once a visit of a group:
    its answers are walk_masks' counts, block for block."""
    c = CASES[name]
    q, kc, vc, tbl, ctx = _inputs(rng, c)
    answers = []
    wholly_live = PA._wholly_live

    def spying(j, *args):
        whole = wholly_live(j, *args)
        if not isinstance(j, np.ndarray):  # the kernel's, not walk_masks'
            jax.debug.callback(lambda w: answers.append(bool(w)), whole)
        return whole

    monkeypatch.setattr(PA, "_wholly_live", spying)
    PA._attend_live_blocks.clear_cache()  # traced with the plain predicate
    try:
        out = paged_decode_attention(q, kc, vc, jnp.asarray(tbl),
                                     jnp.asarray(ctx), window=c["window"])
        out.block_until_ready()
        jax.effects_barrier()
    finally:
        PA._attend_live_blocks.clear_cache()
    assert (answers.count(False), len(answers)) == (masked, visited)
    assert PA.walk_masks(tbl, ctx, c["bs"], c["H"] // c["KV"],
                         c["window"]) == (masked, visited)


@pytest.mark.usefixtures("pallas_interpret")
def test_rows_walked_alone_are_what_they_are_without_a_group(rng):
    """A group of one takes the per-row walk: the decode rows of a call
    with a chunk between them are bit for bit what they are in a call
    of their own, and a chunk of ONE row is such a row too."""
    c = CASES["group_of_31"]
    q, kc, vc, tbl, ctx = _inputs(rng, c)
    # a decode row, the chunk's first, the last
    alone = np.array([0, 1, len(ctx) - 1])
    # the group is rows 2.., the chunk's first row apart on a table of
    # its own (row 0's, backwards: blocks that exist, no run with row 0)
    tbl[1] = tbl[0][::-1]
    tbl[2:-1] = tbl[2]
    with jax.default_matmul_precision("highest"):
        out = paged_decode_attention(q, kc, vc, jnp.asarray(tbl),
                                     jnp.asarray(ctx))
        want = paged_decode_attention(q[alone], kc, vc,
                                      jnp.asarray(tbl[alone]),
                                      jnp.asarray(ctx[alone]))
    np.testing.assert_array_equal(np.asarray(out)[alone], np.asarray(want))


# ---------------------------------------------------------------------------
# whole-tile pools of fewer than 8 KV heads lie in two wide heads (PR 64):
# Mellum 2's 4 heads of 128 and LFM2's 8 of 64 are pools [.., 2, 256]
# ---------------------------------------------------------------------------

def _wide_call(rng, KV, D, what):
    """One call's arrays over kv_pack's pool of KV heads of D, and the
    SAME values a head a head in float32 for the oracle: (q [S, 32, D],
    the pools as the model holds them, the pools unpacked, the table,
    the table the oracle gathers by, ctx, window). `what`: decode rows
    alone; a chunk of 32 rows on one table between decode rows (16 query
    rows a wide head: two groups of 16); Mellum 2's ring of 10 blocks of
    128 tokens two turns deep under its window of 1,024, decode rows
    then a chunk; dead table slots that name a block of NaN (in the
    model's pools alone: the oracle gathers every slot, from its own)."""
    bs, NB, window, ring, chunk = 16, 6, 0, 0, None
    if what == "decode_rows":
        ctx = (0, 1, 15, 16, 17, 40, 96, 0)
    elif what == "chunk_of_32":
        ctx, chunk = (40, *_rising(30, 32), 96), (1, 32)
    elif what == "ring_two_turns":
        # 2,560 tokens fill the ring twice; 1,300 is its second turn
        bs, NB, window, ring = 128, 24, 1024, 10
        ctx, chunk = (2561, 2688, 2817, 1300, 500, *_rising(2650, 32)), (5, 32)
    else:
        ctx = (0, 1, 16, 17, 40, 41, 42, 64)
    S, H = len(ctx), 32
    ctx = np.asarray(ctx, np.int32)
    NBLK = S * (ring or NB) + 1
    if ring:  # row s holds ring s: its R blocks named again and again
        tbl = (np.arange(S)[:, None] * ring
               + np.arange(NB)[None, :] % ring).astype(np.int32)
    else:
        tbl = rng.permutation(NBLK - 1)[:S * NB].reshape(S, NB).astype(
            np.int32)
    if chunk:
        tbl[chunk[0]:sum(chunk)] = tbl[chunk[0]]
    plain = [jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), jnp.float32)
             for _ in range(2)]
    pack = PA.kv_pack(KV, D, 2)
    pools = [p.reshape(NBLK, bs, KV // pack, pack * D) for p in plain]
    assert pools[0].shape[2:] == (2, 256)
    clean = tbl.copy()
    if what == "dead_slots":
        for s in range(S):
            tbl[s, -(-int(ctx[s]) // bs):] = NBLK - 1
        assert (tbl == NBLK - 1).sum() >= 12
        pools = [p.at[NBLK - 1].set(jnp.nan) for p in pools]
    q = jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32)
    return (q, pools, plain, jnp.asarray(tbl), jnp.asarray(clean), ctx,
            window)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("KV,D", [(4, 128), (8, 64)], ids=["4x128", "8x64"])
@pytest.mark.parametrize("what", ["decode_rows", "chunk_of_32",
                                  "ring_two_turns", "dead_slots"])
def test_the_walk_over_two_wide_heads_is_the_unpacked_attention(rng, KV, D,
                                                                what):
    q, pools, plain, tbl, clean, ctx, window = _wide_call(rng, KV, D, what)
    with jax.default_matmul_precision("highest"):
        out = _one_program(paged_decode_attention, window)(
            q, *pools, tbl, jnp.asarray(ctx))
        want = _one_program(paged_decode_attention_xla, window)(
            q, *plain, clean, jnp.asarray(ctx))
    real = ctx > 0
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(want)[real],
                               rtol=2e-3, atol=2e-3)
    assert not np.asarray(out)[~real].any()
    # it IS the walk, at 2 heads of 16 query rows: a chunk's rows in
    # groups of 16, each read once
    pack = PA._packing(q, pools[0])
    qg = PA._group_queries(PA._pack_queries(q, pack, 32 // KV), 2)[0]
    assert qg.shape[1:] == (2, 16, 256) and PA._walks_live_blocks(qg, pools[0])
    bound = PA._walk_group_rows(32 // KV, pack, len(ctx))
    assert bound == min(16, len(ctx))
    if what == "chunk_of_32":  # rows 1-32: rows 1 and 17 walk for them
        lead = np.asarray(PA.walk_groups(tbl, bound))
        assert lead.tolist() == [0] + [1] * 16 + [17] * 16 + [33]


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("KV,D", [(4, 128), (8, 64)], ids=["4x128", "8x64"])
@pytest.mark.parametrize("what", ["decode_rows", "ring_two_turns",
                                  "dead_slots"])
def test_the_fused_write_over_two_wide_heads(rng, KV, D, what):
    """paged_decode_fused at [.., 2, 256]: every row's new K/V lands in
    its slot of the pool as a scatter into the UNPACKED pool puts it,
    and the attention over it is the unpacked one (decode rows: a row a
    sequence, so the ring's chunk is left out)."""
    q, pools, plain, tbl, clean, ctx, window = _wide_call(rng, KV, D, what)
    S = 5 if what == "ring_two_turns" else len(ctx)
    q, tbl, clean, ctx = q[:S], tbl[:S], clean[:S], ctx[:S]
    bs, real = pools[0].shape[1], ctx > 0
    kn, vn = (jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
              for _ in range(2))
    pos = np.maximum(ctx - 1, 0)
    slots = jnp.asarray(np.where(
        real, np.asarray(tbl)[np.arange(S), pos // bs] * bs + pos % bs,
        -1).astype(np.int32))
    with jax.default_matmul_precision("highest"):
        out, fk, fv = jax.jit(functools.partial(
            paged_decode_attention, window=window))(
            q, *pools, tbl, jnp.asarray(ctx), k_new=kn, v_new=vn,
            slots=slots)
        xk, xv = M._write_kv_xla(*plain, kn, vn, slots)
        want = _one_program(paged_decode_attention_xla, window)(
            q, xk, xv, clean, jnp.asarray(ctx))
    np.testing.assert_allclose(np.asarray(out)[real], np.asarray(want)[real],
                               rtol=2e-3, atol=2e-3)
    live = np.unique(np.asarray(slots)[real] // bs)
    for got, scattered in ((fk, xk), (fv, xv)):
        np.testing.assert_array_equal(
            np.asarray(got).reshape(scattered.shape)[live],
            np.asarray(scattered)[live])


@pytest.mark.parametrize("tables,bound,lead", [
    ([1, 2, 2, 2, 3, 3, 4], 32, [0, 1, 1, 1, 4, 4, 6]),
    ([5, 5, 5, 5, 5, 5, 5], 3, [0, 0, 0, 3, 3, 3, 6]),
    ([7, 8, 7, 8, 7, 7, 9], 32, [0, 1, 2, 3, 4, 4, 6]),  # apart: alone
    ([1, 2, 3, 4, 5, 6, 7], 32, [0, 1, 2, 3, 4, 5, 6]),
    ([4, 4, 4, 4, 4, 4, 4], 1, [0, 1, 2, 3, 4, 5, 6]),
])
def test_which_row_walks_for_which(tables, bound, lead):
    """walk_groups: adjacent rows of one table, cut at the bound; the
    same answer over numpy (the scheduler's counters) and jnp (the
    kernel's entry)."""
    tbl = np.asarray(tables, np.int32)[:, None] * np.ones((1, 4), np.int32)
    assert PA.walk_groups(tbl, bound, np).tolist() == lead
    assert np.asarray(PA.walk_groups(jnp.asarray(tbl), bound)).tolist() == lead


def _kernel_grids(fn, *args):
    """Grid of every pallas_call named paged_decode_grid in fn's trace."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                assert "paged_decode_grid" in str(eqn.params["name"])
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("what,KV,D,dtype,quant,rows_only", [
    ("bf16_d128", 8, 128, jnp.bfloat16, False, True),
    ("bf16_mha_d128", 16, 128, jnp.bfloat16, False, True),
    ("f32_d128_3kv", 3, 128, jnp.float32, False, True),
    ("d64", 8, 64, jnp.bfloat16, False, False),
    ("bf16_12kv", 12, 128, jnp.bfloat16, False, False),
    ("bf16_1kv", 1, 128, jnp.bfloat16, False, False),
    ("int8_kv", 8, 128, jnp.bfloat16, True, False),
])
def test_which_case_walks_and_which_keeps_the_grid(what, KV, D, dtype,
                                                   quant, rows_only):
    """Adapting on dtype and shape, nothing else: the per-row walk has a
    grid of (rows,), what it cannot take keeps (rows, slots); both are
    named paged_decode_grid."""
    S, NB, bs, NBLK = 8, 4, 16, 9
    q = jnp.zeros((S, KV, D), dtype)
    cache = jnp.zeros((NBLK, bs, KV, D), jnp.int8 if quant else dtype)
    kw = {}
    if quant:
        kw = dict(k_scale=jnp.ones((NBLK, bs, KV), jnp.float32),
                  v_scale=jnp.ones((NBLK, bs, KV), jnp.float32))
    grids = _kernel_grids(
        lambda q, k, v, t, c: paged_decode_attention(q, k, v, t, c, **kw),
        q, cache, cache, jnp.zeros((S, NB), jnp.int32),
        jnp.ones((S,), jnp.int32))
    assert grids == [(S,) if rows_only else (S, NB)]


# ---------------------------------------------------------------------------
# AOT compiles for a described v5e at the serving cells' shapes
# ---------------------------------------------------------------------------

BLOCK, BLOCKS_PER_SEQ = 128, 32

# the six serving cells that run the walk, at their engines' shapes:
# rows of a step, query / KV heads, head dim, pool blocks; lfm2's and
# mellum2's pools are packed (kv_pack: 8 heads of 64, 4 of 128, as 2
# heads of 256)
CELLS = {
    "serve-chat-saturated": dict(rows=128, H=32, KV=8, D=128, pool=704),
    "serve-olmoe-chat-saturated": dict(rows=128, H=16, KV=16, D=128,
                                       pool=704),
    "serve-lfm2-chat-saturated-r512": dict(rows=512, H=32, KV=8, D=64,
                                           pool=2048),
    "serve-qwen3next-chat-saturated-r256": dict(rows=256, H=16, KV=2, D=256,
                                                pool=1024),
    "serve-granite4h-chat-saturated-r128": dict(rows=128, H=32, KV=8, D=128,
                                                pool=1024),
    "serve-mellum2-mixedlen-saturated-r256": dict(rows=256, H=32, KV=4,
                                                  D=128, pool=3072),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described compile is written to the persistent cache but cannot
    # be read back without a chip: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell,window", [
    ("serve-chat-saturated", 4096), ("serve-olmoe-chat-saturated", 4096),
    ("serve-chat-saturated", 0), ("serve-olmoe-chat-saturated", 0),
    ("serve-lfm2-chat-saturated-r512", 0),
    ("serve-qwen3next-chat-saturated-r256", 0),
    ("serve-granite4h-chat-saturated-r128", 0),
    # its full layers and its windowed ones
    ("serve-mellum2-mixedlen-saturated-r256", 0),
    ("serve-mellum2-mixedlen-saturated-r256", 1024),
])
def test_shared_table_attention_compiles_for_v5e(one_chip, cell, window):
    """The walk WITH its grouped body (groups of 32 rows at a Gp of 8,
    of 16 rows at the 16 query rows of a wide head: Mellum 2's and
    LFM2's pools [.., 2, 256]) is what Mosaic is handed at each cell's
    shapes."""
    c = CELLS[cell]
    rows, H, KV, D = c["rows"], c["H"], c["KV"], c["D"]
    pack = PA.kv_pack(KV, D, 2)
    wide = cell.split("-")[1] in ("lfm2", "mellum2")
    assert ((KV // pack, D * pack) == (2, 256)) == (wide or KV == 2)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = sds((c["pool"] + 1, BLOCK, KV // pack, D * pack), jnp.bfloat16)
    args = (sds((rows, H, D), jnp.bfloat16), cache, cache,
            sds((rows, BLOCKS_PER_SEQ), jnp.int32), sds((rows,), jnp.int32))

    def fn(q, kc, vc, table, ctx):
        return paged_decode_attention(q, kc, vc, table, ctx, window=window)

    assert PA._walk_group_rows(H // KV, pack, rows) == (16 if wide else 32)
    assert _kernel_grids(fn, *args) == [(rows,)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert any('custom_call_target="tpu_custom_call"' in line
               and "paged_decode_grid" in line for line in text.splitlines())


# ---------------------------------------------------------------------------
# the counter the kernel's time should follow, and its reader
# ---------------------------------------------------------------------------

def _tiny_scheduler(prefill_chunk, batched_tokens=16, **engine):
    cfg = T.TransformerConfig(vocab_size=128, n_layers=2, n_heads=4,
                              d_model=64, max_seq=128, variant="llama",
                              use_flash=False)
    eng = init_inference(
        T.init(cfg, jax.random.PRNGKey(0)), cfg,
        dict(dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                  min_prefill_bucket=8, max_batch_size=8), **engine),
        dtype=jnp.float32)
    return ServingScheduler(
        eng, ServingSchedulerConfig(max_num_batched_tokens=batched_tokens,
                                    prefill_chunk=prefill_chunk,
                                    warmup=False), seed=0)


def _spy_on_counts(sched):
    """Every _count_tokens call that handed over a ctx array: (ctx,
    tables, steps, what it added to each KV counter)."""
    seen = []
    count = sched._count_tokens
    keys = ("kv_live_blocks", "kv_block_reads", "kv_grouped_rows")

    def spy(n, width, ctx=None, steps=1, tables=None):
        before = [sched.counters[k] for k in keys]
        count(n, width, ctx, steps, tables)
        if ctx is not None:
            seen.append((np.array(ctx), tables, steps,
                         *(sched.counters[k] - b
                           for k, b in zip(keys, before))))

    sched._count_tokens = spy
    return seen


def test_the_scheduler_counts_the_live_blocks_it_dispatches(rng):
    sched = _tiny_scheduler(prefill_chunk=4)
    seen = _spy_on_counts(sched)
    for n in (11, 5):
        sched.submit(rng.integers(0, 128, n).astype(np.int32),
                     max_new_tokens=6)
    sched.run()
    assert seen, "no dispatcher handed over its ctx array"
    for ctx, _, steps, added, _, _ in seen:
        assert added == sum(-(-(int(c) + k) // 8)
                            for c in ctx[ctx > 0] for k in range(steps))
    assert sched.counters["kv_live_blocks"] == sum(s[3] for s in seen)
    # the first chunks' rows sit inside their first block: one each;
    # later rows (contexts of 9 to 17 tokens) read two or three
    ctx, _, _, added, _, _ = seen[0]
    assert ctx.max() <= 8 and added == (ctx > 0).sum() > 0
    ctx, _, _, added, _, _ = seen[-1]
    assert ctx.max() > 8 and added > (ctx > 0).sum()
    # what the walk fetches: a chunk's rows ride on its first row's walk
    # and the chunk's blocks count once, by its longest row
    chunked = [s for s in seen if s[5]]
    assert chunked, "no step held a chunk"
    for ctx, tables, _, live, reads, rode in chunked:
        assert reads < live and rode <= (ctx > 0).sum() - 1
    # a decode-only step: every row a sequence of its own
    ctx, tables, _, live, reads, rode = seen[-1]
    assert len({t.tobytes() for t in tables[ctx > 0]}) == (ctx > 0).sum()
    assert reads == live and rode == 0
    assert (sched.counters["kv_block_reads"]
            == sum(s[4] for s in seen) < sched.counters["kv_live_blocks"])
    assert sched.counters["kv_grouped_rows"] == sum(s[5] for s in seen)


def test_a_chunk_of_32_over_two_blocks_among_decode_rows(rng):
    """One step of the shared-table program as the chat cells run it:
    decode rows, then a chunk of 32 rows whose contexts cross from the
    table's first block into its second."""
    sched = _tiny_scheduler(prefill_chunk=32, batched_tokens=40,
                            kv_block_size=16, max_seq_len=128,
                            max_batch_size=40)
    seen = _spy_on_counts(sched)
    for n in (5, 9, 13):  # three sequences that will be decoding
        sched.submit(rng.integers(0, 128, n).astype(np.int32),
                     max_new_tokens=12)
    for _ in range(3):
        sched.step()
    del seen[:]
    sched.submit(rng.integers(0, 128, 40).astype(np.int32),
                 max_new_tokens=2)
    while not any(s[5] for s in seen):
        sched.step()
    ctx, tables, steps, live, reads, rode = next(s for s in seen if s[5])
    last = tables[np.flatnonzero(ctx > 0)[-1]]  # the chunk's rows come last
    chunk = np.flatnonzero((tables == last).all(axis=1) & (ctx > 0))
    decode = np.setdiff1d(np.flatnonzero(ctx > 0), chunk)
    assert len(chunk) == 32 and len(decode) == 3
    assert ctx[chunk].tolist() == list(range(1, 33))
    blocks = -(-ctx // 16)
    assert live == blocks.sum() == blocks[decode].sum() + 16 * 1 + 16 * 2
    assert reads == blocks[decode].sum() + 2  # the chunk's two, once
    assert rode == 31


def test_the_live_block_reader():
    spec = importlib.util.spec_from_file_location(
        "paged_live_blocks_per_step",
        os.path.join(REPO, "benchmarks", "metrics",
                     "paged_live_blocks_per_step.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    obs = {"counters_delta": {"steps": 1290, "kv_live_blocks": 554700}}
    assert mod.read(obs) == pytest.approx(430.0)
    # a program without the counter (the parent): nothing, no raise
    assert mod.read({"counters_delta": {"steps": 1290}}) is None
    assert mod.read({}) is None
