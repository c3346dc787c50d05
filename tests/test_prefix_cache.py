"""Prefix-cache control-plane tests (inference/ragged.py): refcounted
allocator + LRU pool invariants, hash-chain block reuse, copy-on-write
tails, eviction, and capacity accounting — pure host-side (no model
forward), so they run in the fast tier-1 lane. Engine end-to-end logit
equality lives in tests/test_inference.py TestPrefixCacheEngine."""

import numpy as np
import pytest

from deepspeed_tpu.inference.ragged import (
    BlockedAllocator,
    PrefixMatch,
    StateManager,
)


def mgr(num_blocks=16, block_size=4, **kw):
    kw.setdefault("enable_prefix_cache", True)
    return StateManager(num_blocks=num_blocks, block_size=block_size, **kw)


def admit(m, uid, prompt, max_suffix_rows=None):
    """Engine-shaped admission: extend with token ids, then commit the
    non-cached remainder (as the forward would after writing KV)."""
    seq, match = m.extend(uid, len(prompt), token_ids=prompt,
                          max_suffix_rows=max_suffix_rows)
    m.commit(uid, len(prompt) - seq.seen_tokens)
    return seq, match


class TestRefcountedAllocator:
    def test_legacy_roundtrip_unchanged(self):
        a = BlockedAllocator(8)
        got = a.allocate(3)
        assert len(got) == 3 and a.free_blocks == 5
        a.free(got)
        assert a.free_blocks == 8
        with pytest.raises(ValueError):
            a.free(got[:1])  # double free

    def test_incref_defers_release(self):
        a = BlockedAllocator(4)
        (b,) = a.allocate(1)
        a.incref(b)
        a.free([b])
        assert a.refcount(b) == 1 and a.free_blocks == 3
        a.free([b])
        assert a.refcount(b) == 0 and a.free_blocks == 4

    def test_incref_dead_block_raises(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError):
            a.incref(2)

    def test_cached_block_parks_and_resurrects(self):
        a = BlockedAllocator(4)
        (b,) = a.allocate(1)
        a.mark_cached(b)
        a.free([b])
        assert a.is_parked(b) and a.cached_blocks == 1
        assert a.free_blocks == 3 and a.available_blocks == 4
        a.acquire_cached(b)
        assert a.refcount(b) == 1 and a.cached_blocks == 0

    def test_allocation_pressure_evicts_lru_cold_first(self):
        evicted = []
        a = BlockedAllocator(4, evict_cb=evicted.append)
        blocks = a.allocate(4)
        for b in blocks:
            a.mark_cached(b)
        a.free([blocks[0]])  # coldest
        a.free([blocks[1]])
        got = a.allocate(1)  # free list empty -> evict LRU
        assert got == [blocks[0]] and evicted == [blocks[0]]
        assert a.evictions == 1 and a.cached_blocks == 1

    def test_pool_cap_bounds_parked_blocks(self):
        a = BlockedAllocator(8, cache_pool_blocks=2)
        blocks = a.allocate(4)
        for b in blocks:
            a.mark_cached(b)
        a.free(blocks)
        assert a.cached_blocks == 2  # oldest two dropped to the free list
        assert a.available_blocks == 8

    def test_exhaustion_counts_parked_as_available(self):
        a = BlockedAllocator(2)
        blocks = a.allocate(2)
        a.mark_cached(blocks[0])
        a.free(blocks)
        assert a.free_blocks == 1
        got = a.allocate(2)  # needs the parked block too
        assert sorted(got) == sorted(blocks)
        with pytest.raises(RuntimeError):
            a.allocate(1)


class TestHashChainReuse:
    def test_second_sequence_shares_full_blocks(self):
        m = mgr()
        p = list(range(10))  # 2 full blocks + 2-token tail
        seq0, match0 = admit(m, 0, p)
        assert isinstance(match0, PrefixMatch) and match0.n_cached == 0
        assert m.indexed_blocks == 2
        seq1, match1 = admit(m, 1, p)
        assert match1.n_cached == 8
        assert match1.reused_blocks == seq0.blocks[:2]
        assert seq1.blocks[:2] == seq0.blocks[:2]
        assert seq1.blocks[2] != seq0.blocks[2]  # private tails
        assert m.allocator.refcount(seq0.blocks[0]) == 2
        st = m.cache_stats()
        assert st["lookup_hits"] == 1 and st["lookup_misses"] == 1
        assert st["cached_tokens"] == 8

    def test_divergent_prompt_shares_only_common_prefix(self):
        m = mgr()
        a = list(range(12))
        b = list(range(8)) + [99, 98, 97, 96]  # diverges in block 2
        seq_a, _ = admit(m, 0, a)
        seq_b, match = admit(m, 1, b)
        assert match.n_cached == 8  # blocks 0-1 chain, block 2 differs
        assert seq_b.blocks[:2] == seq_a.blocks[:2]
        assert seq_b.blocks[2] != seq_a.blocks[2]

    def test_too_short_prompt_never_matches(self):
        m = mgr()
        admit(m, 0, list(range(8)))
        _, match = admit(m, 1, [0, 1, 2])  # < one block
        assert match.n_cached == 0

    def test_max_suffix_rows_degrades_hit_to_miss(self):
        m = mgr()
        admit(m, 0, list(range(12)))
        q = list(range(8)) + [50, 51, 52, 53]
        # a hit would leave a 4-token suffix; budget allows only 3
        seq, match = m.extend(1, 12, token_ids=q, max_suffix_rows=3)
        assert match.n_cached == 0 and len(seq.blocks) == 3
        assert m.allocator.refcount(seq.blocks[0]) == 1  # nothing shared

    def test_reuse_after_flush_resurrects_from_lru(self):
        m = mgr()
        p = list(range(10))
        seq0, _ = admit(m, 0, p)
        shared = seq0.blocks[:2]
        m.flush(0)
        assert all(m.allocator.is_parked(b) for b in shared)
        assert m.free_blocks == 16  # parked blocks stay schedulable
        seq1, match = admit(m, 1, p)
        assert match.n_cached == 8 and seq1.blocks[:2] == shared
        assert not m.allocator.is_parked(shared[0])


class TestRefcountFlush:
    def test_flush_sharing_sequence_no_double_free_no_leak(self):
        m = mgr()
        p = list(range(10))
        seq0, _ = admit(m, 0, p)
        seq1, _ = admit(m, 1, p)
        shared = seq0.blocks[:2]
        m.flush(1)  # sharer leaves: shared blocks stay live for uid 0
        assert m.allocator.refcount(shared[0]) == 1
        assert not m.allocator.is_parked(shared[0])
        m.flush(0)  # last owner: full blocks park, tail recycles
        assert m.allocator.refcount(shared[0]) == 0
        assert m.allocator.cached_blocks == 2
        assert m.free_blocks == 16  # no leak: everything accounted
        with pytest.raises(KeyError):
            m.flush(0)

    def test_failed_admission_rolls_back_acquisitions(self):
        m = mgr(num_blocks=4)
        p = list(range(16))  # 4 full blocks
        admit(m, 0, p)
        m.flush(0)
        parked = m.allocator.cached_blocks
        # 20-token prompt: matches the 16-token chain but needs a 5th
        # block -> allocator raises; the acquired blocks must re-park
        with pytest.raises(RuntimeError):
            m.extend(1, 20, token_ids=p + [1, 2, 3, 4])
        assert m.get(1) is None
        assert m.allocator.cached_blocks == parked
        assert m.free_blocks == 4


class TestCopyOnWrite:
    def test_exact_multiple_match_cows_tail(self):
        m = mgr()
        p = list(range(8))  # exactly 2 blocks
        seq0, _ = admit(m, 0, p)
        seq1, match = m.extend(1, 8, token_ids=p)
        assert match.n_cached == 7  # capped: last token must run
        assert match.cow is not None
        src, dst = match.cow
        assert src == seq0.blocks[1] and dst == seq1.blocks[1]
        assert seq1.blocks[0] == seq0.blocks[0]
        assert dst != src
        # src keeps its owner's refcount only — the sharer holds dst
        assert m.allocator.refcount(src) == 1
        assert m.allocator.refcount(dst) == 1
        assert m.cache_stats()["cow_copies"] == 1

    def test_divergent_tail_after_cow_does_not_corrupt_owner(self):
        m = mgr()
        p = list(range(8))
        seq0, _ = admit(m, 0, p)
        seq1, match = m.extend(1, 8, token_ids=p)
        m.commit(1, 1)  # the recomputed last token
        # both sequences now append different continuations
        m.extend(0, 2)
        m.commit(0, 2)
        m.extend(1, 2)
        m.commit(1, 2)
        assert set(seq0.blocks).isdisjoint(set(seq1.blocks) - {seq0.blocks[0]})
        m.flush(0)
        m.flush(1)
        assert m.free_blocks == 16

    def test_cow_against_parked_source(self):
        m = mgr()
        p = list(range(8))
        seq0, _ = admit(m, 0, p)
        src_orig = seq0.blocks[1]
        m.flush(0)
        seq1, match = m.extend(1, 8, token_ids=p)
        assert match.cow is not None and match.cow[0] == src_orig
        # the source stays parked for future exact hits
        assert m.allocator.is_parked(src_orig)


class TestEviction:
    def test_pressure_evicts_and_drops_index_entries(self):
        m = mgr(num_blocks=4)
        admit(m, 0, list(range(10)))  # 3 blocks, 2 indexed
        m.flush(0)
        assert m.indexed_blocks == 2 and m.allocator.cached_blocks == 2
        seq = m.extend(1, 16)  # all 4 blocks -> evicts both parked
        assert len(seq.blocks) == 4
        assert m.indexed_blocks == 0
        assert m.allocator.evictions == 2
        # the old chain is gone: a re-put of the prompt misses
        m.flush(1)
        _, match = admit(m, 2, list(range(10)))
        assert match.n_cached == 0

    def test_live_shared_blocks_are_never_evicted(self):
        m = mgr(num_blocks=4)
        seq0, _ = admit(m, 0, list(range(8)))  # 2 live indexed blocks
        with pytest.raises(RuntimeError):
            m.extend(1, 12)  # 3 blocks wanted, only 2 free
        assert m.get(0) is seq0 and len(seq0.blocks) == 2
        assert m.indexed_blocks == 2  # untouched


class TestAccounting:
    def test_can_fit_counts_parked_blocks(self):
        m = mgr(num_blocks=4)
        admit(m, 0, list(range(16)))
        assert not m.can_fit(1, 4)
        m.flush(0)  # 4 full blocks -> all park
        assert m.allocator.free_blocks == 0
        assert m.can_fit(1, 16)  # parked blocks are evictable capacity
        assert not m.can_fit(1, 17)

    def test_commit_of_unknown_tokens_stops_registration(self):
        m = mgr()
        seq = m.extend(0, 10)  # fused-decode style: no token ids
        m.commit(0, 10)
        assert not seq.tokens_valid
        assert m.indexed_blocks == 0

    def test_partial_then_unknown_keeps_registered_prefix(self):
        m = mgr()
        seq, _ = m.extend(0, 8, token_ids=list(range(8)))
        m.commit(0, 8)
        assert m.indexed_blocks == 2
        m.extend(0, 4)
        m.commit(0, 4)  # sampled tokens the host never saw
        assert not seq.tokens_valid
        assert m.indexed_blocks == 2  # prompt blocks stay addressable

    def test_supplied_ids_resume_registration(self):
        """The scheduler's look-ahead: a token is committed before the
        host has read it and its id follows one iteration later. The
        index ends up holding what id-carrying commits would have."""
        want = mgr()
        want.extend(0, 6, token_ids=list(range(6)))
        want.commit(0, 6)
        for t in range(6, 13):
            want.extend(0, 1)
            want.commit(0, 1, token_ids=[t])
        m = mgr()
        seq, _ = m.extend(0, 6, token_ids=list(range(6)))
        m.commit(0, 6)
        for t in range(6, 13):
            m.extend(0, 1)
            m.commit(0, 1)              # the id is still on the device
            assert not seq.tokens_valid
            m.supply_tokens(0, [t])     # the readback landed
            assert seq.tokens_valid
        assert m.indexed_blocks == want.indexed_blocks == 3
        assert set(m._index) == set(want._index)
        assert seq.tokens == list(range(13))

    def test_ids_that_do_not_close_the_gap_are_dropped(self):
        m = mgr()
        seq, _ = m.extend(0, 8, token_ids=list(range(8)))
        m.commit(0, 8)
        m.extend(0, 4)
        m.commit(0, 4)                  # a fused chunk: never supplied
        m.extend(0, 1)
        m.commit(0, 1)
        m.supply_tokens(0, [99])        # one id for a gap of five
        assert not seq.tokens_valid and seq.tokens == list(range(8))
        assert m.indexed_blocks == 2
        off = StateManager(num_blocks=8, block_size=4,
                           enable_prefix_cache=False)
        off.extend(0, 2)
        off.commit(0, 2)
        off.supply_tokens(0, [1, 2])    # no index: nothing to keep
        assert off.get(0).tokens == []

    def test_disabled_cache_is_legacy_behavior(self):
        m = StateManager(num_blocks=8, block_size=4,
                         enable_prefix_cache=False)
        p = list(range(8))
        seq0, match0 = m.extend(0, 8, token_ids=p)
        m.commit(0, 8)
        seq1, match1 = m.extend(1, 8, token_ids=p)
        assert match0.n_cached == 0 and match1.n_cached == 0
        assert set(seq0.blocks).isdisjoint(seq1.blocks)
        assert m.indexed_blocks == 0
        m.flush(0)
        assert m.allocator.cached_blocks == 0  # nothing ever parks

    def test_duplicate_commit_key_keeps_first_block(self):
        m = mgr()
        p = list(range(8))
        # two sequences prefill the same prompt CONCURRENTLY (neither
        # sees the other's index entries until commit)
        sa, _ = m.extend(0, 8, token_ids=p)
        sb, _ = m.extend(1, 8, token_ids=p)
        m.commit(0, 8)
        m.commit(1, 8)
        assert m.indexed_blocks == 2
        # index points at uid 0's blocks; uid 1's stay private
        _, match = admit(m, 2, p + [1])
        assert match.reused_blocks == sa.blocks[:2]
        m.flush(0)
        m.flush(1)
        m.flush(2)
        assert m.free_blocks == 16
