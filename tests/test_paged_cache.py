"""Property tests for the paged-KV page allocator (DESIGN.md §11).

The allocator is the ownership ledger of the paged serving pool; these
properties (via hypothesis or the deterministic
tests/_hypothesis_compat.py shim) are the invariants the engine's
correctness rests on:

* alloc / free / fork sequences never double-free, and every reserved
  block keeps refcount 1 forever;
* refcounts equal live block-table references exactly, at every step of
  a random operation trace (the prefix index holds no refcount);
* a prefix fork followed by the first divergent write copies exactly
  one block (copy-on-write), and an unshared block is written in place;
* allocator state round-trips through ``checkpoint.Checkpointer``
  snapshot/restore bit-exactly, prefix index included.
"""
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint.checkpointer import Checkpointer
from repro.serve.paged_cache import (N_RESERVED, PageAllocator,
                                     PagedCacheConfig, TRASH_BLOCK,
                                     ZERO_BLOCK)

N_EXAMPLES = 60


def _cfg(num_blocks=18, block_size=4, share=True):
    return PagedCacheConfig(num_blocks=num_blocks, block_size=block_size,
                            prefill_chunk=block_size * 2,
                            share_prefixes=share)


def _random_trace(alloc: PageAllocator, rng: np.random.Generator,
                  n_ops: int):
    """Drive a random alloc/free/fork/register/cow trace, mirroring the
    engine's ownership bookkeeping in `tables` (list of owned-block
    lists).  Consistency is asserted after EVERY op."""
    tables: list[list[int]] = []
    next_token = [0]

    def new_prompt(n):
        out = list(range(next_token[0], next_token[0] + n))
        next_token[0] += n
        return out

    prompts: list[list[int]] = []
    for _ in range(n_ops):
        op = rng.integers(0, 5)
        if op == 0 and alloc.can_alloc(2):            # admit 2 blocks
            blocks = alloc.alloc_n(2)
            tables.append(blocks)
            prompt = new_prompt(2 * alloc.cfg.block_size)
            prompts.append(prompt)
            for i, blk in enumerate(blocks):
                alloc.register_prefix(alloc.block_key(prompt, i), blk)
        elif op == 1 and tables:                      # release a table
            i = int(rng.integers(len(tables)))
            alloc.release(tables.pop(i))
            prompts.pop(i)
        elif op == 2 and tables:                      # fork (share) one
            i = int(rng.integers(len(tables)))
            tables.append(alloc.fork(tables[i]))
            prompts.append(list(prompts[i]))
        elif op == 3 and tables and alloc.can_alloc(1):   # grow one
            i = int(rng.integers(len(tables)))
            tables[i].append(alloc.alloc())
        elif op == 4 and tables and alloc.can_alloc(1):   # COW write
            i = int(rng.integers(len(tables)))
            j = int(rng.integers(len(tables[i])))
            blk, _copied = alloc.ensure_writable(tables[i][j])
            tables[i][j] = blk
        alloc.check_consistency(tables)
        assert alloc.refcounts[ZERO_BLOCK] == 1
        assert alloc.refcounts[TRASH_BLOCK] == 1
    return tables


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       num_blocks=st.sampled_from([6, 10, 18, 34]),
       share=st.booleans())
def test_random_traces_never_double_free(seed, num_blocks, share):
    alloc = PageAllocator(_cfg(num_blocks=num_blocks, share=share))
    rng = np.random.default_rng(seed)
    tables = _random_trace(alloc, rng, n_ops=40)
    for t in tables:
        alloc.release(t)
    alloc.check_consistency([])
    assert alloc.free_blocks() == alloc.cfg.usable_blocks


def test_decref_below_zero_is_double_free():
    alloc = PageAllocator(_cfg())
    blk = alloc.alloc()
    alloc.decref(blk)
    with pytest.raises(AssertionError, match="double free"):
        alloc.decref(blk)


def test_reserved_blocks_never_allocated():
    alloc = PageAllocator(_cfg(num_blocks=4))
    got = {alloc.alloc(), alloc.alloc()}
    assert got == {N_RESERVED, N_RESERVED + 1}
    with pytest.raises(MemoryError):
        alloc.alloc()


@settings(max_examples=N_EXAMPLES, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_fork_then_divergent_write_copies_exactly_one_block(seed):
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(_cfg())
    owner = alloc.alloc_n(3)
    shared = alloc.fork(owner)
    before = alloc.free_blocks()
    j = int(rng.integers(3))
    blk, copied = alloc.ensure_writable(shared[j])
    shared[j] = blk
    assert copied and blk != owner[j]
    assert alloc.free_blocks() == before - 1     # exactly one new block
    alloc.check_consistency([owner, shared])
    # the copied block is now exclusively owned: the second write on it
    # must NOT copy again
    blk2, copied2 = alloc.ensure_writable(shared[j])
    assert blk2 == blk and not copied2
    alloc.release(owner)
    alloc.release(shared)
    alloc.check_consistency([])


def test_unshared_block_writes_in_place():
    alloc = PageAllocator(_cfg())
    blk = alloc.alloc()
    got, copied = alloc.ensure_writable(blk)
    assert got == blk and not copied


def test_match_prefix_stops_one_token_short():
    """The last prompt token is always prefilled locally (its logits
    seed the first sample), and blocks are shared in whole prefill
    chunks, so an exact-multiple prompt shares one chunk less than its
    full length."""
    cfg = _cfg(block_size=4)                  # prefill chunk = 8 tokens
    alloc = PageAllocator(cfg)
    prompt = list(range(16))
    blocks = alloc.alloc_n(4)
    for i, blk in enumerate(blocks):
        alloc.register_prefix(alloc.block_key(prompt, i), blk)
    assert alloc.match_prefix(prompt) == blocks[:2]
    assert alloc.match_prefix(prompt + [99]) == blocks
    assert alloc.match_prefix([7, 6, 5, 4, 3]) == []


def test_block_key_covers_its_whole_prefill_chunk():
    """A block's K/V depend on every token of the chunk that computed it
    (the chunk shares one activation scale), so a history that agrees
    on the block's own tokens but not on the rest of its chunk must not
    reuse it."""
    alloc = PageAllocator(_cfg(block_size=4))
    prompt = list(range(9))
    blocks = alloc.alloc_n(2)
    for i, blk in enumerate(blocks):
        alloc.register_prefix(alloc.block_key(prompt, i), blk)
    assert alloc.match_prefix(prompt) == blocks
    assert alloc.match_prefix(prompt[:4] + [99] * 5) == []


def test_dying_block_leaves_the_prefix_index():
    cfg = _cfg(block_size=4)
    alloc = PageAllocator(cfg)
    prompt = list(range(9))
    blocks = alloc.alloc_n(2)
    for i, blk in enumerate(blocks):
        alloc.register_prefix(alloc.block_key(prompt, i), blk)
    assert alloc.match_prefix(prompt) == blocks
    alloc.decref(blocks[0])
    assert alloc.match_prefix(prompt) == []
    # the id can be recycled for an unrelated request without ghosts
    assert alloc.alloc() == blocks[0]
    assert alloc.match_prefix(prompt) == []


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_state_roundtrips_through_checkpointer(seed):
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(_cfg())
    tables = _random_trace(alloc, rng, n_ops=25)
    state = alloc.state_dict()

    # a directory per example: hypothesis runs the body many times per
    # test call, so a function-scoped tmp_path fixture would be shared
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = Checkpointer(tmp)
        ckpt.save(0, {"refcounts": state["refcounts"]},
                  metadata={"prefix_index": state["prefix_index"]})
        tree, meta = ckpt.restore(
            {"refcounts": np.zeros_like(state["refcounts"])})

    fresh = PageAllocator(_cfg())
    fresh.load_state_dict({"refcounts": tree["refcounts"],
                           "prefix_index": meta["prefix_index"]})
    assert np.array_equal(fresh.refcounts, alloc.refcounts)
    assert fresh._prefix_index == alloc._prefix_index
    assert {k: sorted(v) for k, v in fresh._block_keys.items()} \
        == {k: sorted(v) for k, v in alloc._block_keys.items()}
    fresh.check_consistency(tables)
