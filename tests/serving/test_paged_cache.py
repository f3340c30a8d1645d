"""Block-table memory manager invariants (serving/paged_cache.py).

Pure host-side tests — no JAX. The contract that keeps the paged attention
bitwise equal to the ring row lives here: tables are position-ordered, a block
is on the free list XOR refcounted by the tables that reference it, ensure()
is all-or-nothing so a mid-growth pool-dry never leaks, and the serving-v3
prefix index / copy-on-write machinery never frees a block another table
still references.
"""

import numpy as np
import pytest

from modalities_tpu.serving.paged_cache import (
    BlockPool,
    BlockTableState,
    blocks_for_tokens,
)


def test_blocks_for_tokens_ceil_division():
    assert blocks_for_tokens(0, 4) == 0
    assert blocks_for_tokens(1, 4) == 1
    assert blocks_for_tokens(4, 4) == 1
    assert blocks_for_tokens(5, 4) == 2
    assert blocks_for_tokens(17, 16) == 2


def test_pool_allocate_free_roundtrip():
    pool = BlockPool(4)
    assert pool.free_count == 4
    blocks = [pool.allocate() for _ in range(4)]
    assert sorted(blocks) == [0, 1, 2, 3]
    assert pool.allocate() is None  # exhausted -> None, never an exception
    assert pool.used_count == 4
    for b in blocks:
        assert pool.refcount(b) == 1
        assert pool.free(b)  # last reference -> back on the free list
    assert pool.free_count == 4
    pool.check()


def test_pool_rejects_double_free_and_degenerate_size():
    pool = BlockPool(2)
    b = pool.allocate()
    pool.free(b)
    with pytest.raises(ValueError, match="double free"):
        pool.free(b)
    with pytest.raises(ValueError, match="num_blocks"):
        BlockPool(0)


def test_lifo_reuse_keeps_working_set_hot():
    pool = BlockPool(8)
    first = pool.allocate()
    pool.free(first)
    assert pool.allocate() == first  # freshly freed block is reused first


def test_observer_sees_true_allocations_only():
    """Observer hooks (the quantized pool's scale mirror rides these): fire on
    0->1 allocate and last-ref free ONLY — fork and partial free of a shared
    block are refcount moves, not allocation events."""
    events = []

    class Recorder:
        def on_allocate(self, block):
            events.append(("alloc", block))

        def on_free(self, block):
            events.append(("free", block))

    pool = BlockPool(4)
    pool.add_observer(Recorder())
    b = pool.allocate()
    pool.fork(b)  # refcount 2: invisible to the observer
    assert events == [("alloc", b)]
    assert not pool.free(b)  # drops to refcount 1: still invisible
    assert events == [("alloc", b)]
    assert pool.free(b)  # last reference: NOW the free fires
    assert events == [("alloc", b), ("free", b)]
    assert pool.allocated_blocks() == []


def test_allocated_blocks_is_sorted_refcounted_set():
    pool = BlockPool(5)
    blocks = [pool.allocate() for _ in range(3)]
    assert pool.allocated_blocks() == sorted(blocks)
    pool.free(blocks[1])
    assert pool.allocated_blocks() == sorted(b for b in blocks if b != blocks[1])


def test_pool_refcount_fork_lifecycle():
    pool = BlockPool(4)
    b = pool.allocate()
    pool.fork(b)
    pool.fork(b)
    assert pool.refcount(b) == 3
    assert pool.shared_count == 1
    assert not pool.free(b)  # two references remain
    assert not pool.free(b)
    assert pool.refcount(b) == 1
    assert pool.shared_count == 0
    assert pool.free(b)  # last one returns it
    assert pool.free_count == 4
    with pytest.raises(ValueError, match="unallocated"):
        pool.fork(b)
    pool.check()


def test_table_growth_is_position_ordered_and_padded():
    ts = BlockTableState(num_blocks=8, block_size=4, table_width=4)
    assert ts.max_len == 16
    assert ts.ensure(rid=5, num_tokens=9)  # 3 blocks
    table = ts.table(5)
    assert len(table) == 4  # static width, 0-padded
    owned = table[:3]
    assert len(set(owned)) == 3
    # position -> (block, offset) walks the table in order
    for pos in range(9):
        blk, off = ts.write_coords(5, pos)
        assert blk == owned[pos // 4]
        assert off == pos % 4
    ts.check()
    assert ts.release(5) == 3
    assert ts.pool.free_count == 8
    assert ts.release(5) == 0  # unknown rid is a no-op


def test_ensure_is_all_or_nothing_when_pool_dry():
    ts = BlockTableState(num_blocks=3, block_size=2, table_width=3)
    assert ts.ensure(rid=0, num_tokens=4)  # takes 2 of 3 blocks
    # rid 1 needs 2 blocks but only 1 is free: nothing may be allocated
    assert not ts.ensure(rid=1, num_tokens=4)
    assert ts.pool.free_count == 1
    assert ts.blocks_held(1) == 0
    ts.check()
    # growth past the static width is a scheduler bug, not a soft failure
    with pytest.raises(ValueError, match="table width"):
        ts.ensure(rid=0, num_tokens=7)


def test_prefix_register_match_fork_roundtrip():
    ts = BlockTableState(num_blocks=8, block_size=4, table_width=4)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]  # 2 full blocks + 2 tail tokens
    assert ts.ensure(rid=0, num_tokens=len(prompt))
    assert ts.register_prefix(0, prompt, upto=len(prompt)) == 2
    assert ts.prefix_index_size == 2
    donor = ts.table(0)[:2]

    # full two-block match; tail token never matches a partial block
    assert ts.match_prefix(prompt) == donor
    assert ts.match_prefix(prompt[:8]) == donor
    assert ts.match_prefix(prompt[:7]) == donor[:1]
    assert ts.match_prefix([99] + prompt[1:]) == []

    ts.fork_prefix(rid=1, blocks=donor)
    assert [ts.pool.refcount(b) for b in donor] == [2, 2]
    assert ts.pool.shared_count == 2
    assert ts.ensure(rid=1, num_tokens=len(prompt))  # tail block is private
    ts.check()

    # re-registering the same prefix is first-writer-wins: no new entries
    assert ts.register_prefix(1, prompt, upto=len(prompt)) == 0

    # donor finishes: shared blocks survive, index entries survive
    assert ts.release(0) == 1  # only the private tail block actually frees
    assert ts.match_prefix(prompt) == donor
    ts.check()
    # last holder releases: blocks free and the index prunes
    assert ts.release(1) == 3
    assert ts.prefix_index_size == 0
    assert ts.pool.free_count == 8
    ts.check()


def test_copy_on_write_shared_block():
    ts = BlockTableState(num_blocks=6, block_size=4, table_width=3)
    prompt = list(range(8))  # exactly 2 full blocks
    assert ts.ensure(rid=0, num_tokens=8)
    ts.register_prefix(0, prompt, upto=8)
    shared = ts.table(0)[:2]
    ts.fork_prefix(rid=1, blocks=shared)

    # exclusive block: no CoW needed
    assert ts.ensure(rid=1, num_tokens=9)
    assert ts.ensure_writable(1, 8) is None

    # writing into the SHARED block 1 must copy first
    res = ts.ensure_writable(1, 7)
    assert res is not None and res is not False
    src, dst = res
    assert src == shared[1]
    assert dst not in shared
    assert ts.table(1)[1] == dst  # table now points at the private copy
    assert ts.table(0)[1] == src  # donor untouched
    assert ts.pool.refcount(src) == 1
    assert ts.match_prefix(prompt) == shared  # index still serves the donor
    ts.check()

    # pool dry -> False, table untouched
    assert ts.ensure(rid=9, num_tokens=4 * ts.pool.free_count)  # drain
    assert ts.pool.free_count == 0
    ts.fork_prefix(rid=2, blocks=[ts.table(0)[0]])
    assert ts.ensure_writable(2, 0) is False
    assert ts.table(2)[0] == ts.table(0)[0]
    ts.check()


def test_release_of_shared_holder_never_frees_donor_blocks():
    ts = BlockTableState(num_blocks=6, block_size=2, table_width=3)
    prompt = [7, 8, 9, 10]
    assert ts.ensure(rid=0, num_tokens=4)
    ts.register_prefix(0, prompt, upto=4)
    blocks = ts.table(0)[:2]
    ts.fork_prefix(rid=1, blocks=blocks)
    # the forked holder releases FIRST: nothing may free
    assert ts.release(1) == 0
    assert [ts.pool.refcount(b) for b in blocks] == [1, 1]
    assert ts.match_prefix(prompt) == blocks
    ts.check()
    assert ts.release(0) == 2
    assert ts.pool.free_count == 6


def test_randomized_allocator_fuzz_never_leaks():
    """Random ensure/fork/CoW/release interleavings (serving-v3 surface): the
    audit invariants hold at every step — refcounts match table references, no
    block leaks, prefix-index entries never outlive their block — and a full
    release returns the pool to pristine."""
    from modalities_tpu.quant.kv import KVScaleMirror

    rng = np.random.default_rng(0)
    ts = BlockTableState(num_blocks=12, block_size=4, table_width=6)
    # quantized-pool shadow: the scale mirror rides the SAME fuzz via the
    # pool's observer hooks; scale-slot allocation must track block allocation
    # exactly through every fork/CoW/preempt interleaving
    mirror = KVScaleMirror(12)
    ts.pool.add_observer(mirror)
    live: dict[int, int] = {}  # rid -> tokens ensured so far
    prompts: dict[int, list[int]] = {}  # rid -> token ids backing its prefix
    next_rid = 0
    for _ in range(500):
        roll = rng.random()
        if live and roll < 0.30:
            rid = int(rng.choice(list(live)))
            ts.release(rid)
            del live[rid]
            prompts.pop(rid, None)
        elif live and roll < 0.45:
            rid = int(rng.choice(list(live)))
            grown = min(live[rid] + int(rng.integers(1, 9)), ts.max_len)
            if ts.ensure(rid, grown):
                live[rid] = grown
        elif live and roll < 0.60:
            # CoW probe: pick a live request and make a random held position
            # writable — shared or not, the invariants must hold after
            rid = int(rng.choice(list(live)))
            if live[rid] > 0:
                pos = int(rng.integers(0, live[rid]))
                ts.ensure_writable(rid, pos)
        else:
            rid, next_rid = next_rid, next_rid + 1
            prompt = [int(t) for t in rng.integers(0, 50, size=rng.integers(1, 25))]
            matched = ts.match_prefix(prompt)
            need = blocks_for_tokens(len(prompt), 4) - len(matched)
            if ts.pool.free_count >= need:
                ts.fork_prefix(rid, matched)
                assert ts.ensure(rid, len(prompt))
                live[rid] = len(prompt)
                prompts[rid] = prompt
                if rng.random() < 0.7:
                    ts.register_prefix(rid, prompt, upto=len(prompt))
        ts.check()
        mirror.check(ts.pool)
        # distinct blocks held across tables + free == num_blocks (shared
        # blocks count once) — the serving-v3 leak invariant
        distinct = set()
        for r in live:
            distinct.update(ts.table(r)[: ts.blocks_held(r)])
        assert len(distinct) + ts.pool.free_count == 12
        for rid, tokens in live.items():
            assert ts.blocks_held(rid) == blocks_for_tokens(tokens, 4)
    for rid in list(live):
        ts.release(rid)
    ts.check()
    mirror.check(ts.pool)
    assert ts.pool.free_count == 12
    assert mirror.live == set()  # zero scale-slot leaks after full release
    assert mirror.allocs == mirror.frees > 0
    assert ts.active_requests() == []
    assert ts.prefix_index_size == 0


def test_pool_high_water_mark_counts_distinct_blocks_and_passes_the_audit():
    """`peak_used` is the most distinct blocks in use at once since the last
    `reset_peak()`: a fork adds a reference, not a block; the audit holds it
    between what is in use and the pool's size."""
    pool = BlockPool(6)
    assert pool.peak_used == 0
    a, b, c = pool.allocate(), pool.allocate(), pool.allocate()
    pool.fork(a)
    assert pool.peak_used == 3
    pool.free(b)
    pool.free(c)
    assert pool.allocate() is not None
    assert (pool.used_count, pool.peak_used) == (2, 3)
    pool.check()
    pool.reset_peak()
    assert pool.peak_used == 2
    pool.check()
    pool.peak_used = 1  # below what is in use: the audit has to say so
    with pytest.raises(AssertionError, match="high-water mark"):
        pool.check()
