"""Block-pool allocator properties (hypothesis-driven): alloc/free/refcount
round-trips under arbitrary interleavings, conservation under
fragmentation (no block is ever lost or double-leased), and block-table
growth matching token counts. Deterministic allocator/engine tests live in
``tests/test_paged_engine.py`` (they run without hypothesis)."""

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install repro[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.serve.block_pool import BlockPool, PagedKVCache  # noqa: E402


@given(st.lists(st.tuples(st.booleans(), st.integers(1, 6)),
                min_size=1, max_size=60),
       st.integers(2, 32))
@settings(max_examples=50, deadline=None)
def test_fragmentation_never_loses_blocks(ops, num_blocks):
    """Arbitrary interleaved alloc/free traffic: every block is always
    exactly free or leased-once, and a full drain restores the pool."""
    pool = BlockPool(num_blocks=num_blocks, block_size=4)
    live = []
    for want_alloc, n in ops:
        if want_alloc and n <= pool.num_free:
            live.append(pool.alloc(n, f"req{len(live)}"))
        elif not want_alloc and live:
            pool.free(live.pop(
                int(np.random.default_rng(n).integers(len(live)))))
        leased = {b for blocks in live for b in blocks}
        assert len(leased) == sum(map(len, live))      # never double-leased
        assert pool.num_free + len(leased) == num_blocks   # conservation
    for blocks in live:
        pool.free(blocks)
    assert pool.num_free == num_blocks


@given(st.lists(st.integers(0, 3), min_size=1, max_size=30),
       st.integers(2, 8))
@settings(max_examples=50, deadline=None)
def test_refcount_roundtrip(extra_refs, num_blocks):
    """A block leased once and ref'd k more times survives exactly k+1
    frees and then double-free raises."""
    pool = BlockPool(num_blocks=num_blocks, block_size=4)
    for k in extra_refs:
        [b] = pool.alloc(1, "first")
        for _ in range(k):
            pool.ref(b)
        assert pool.refcount(b) == k + 1
        for i in range(k + 1):
            assert pool.refcount(b) == k + 1 - i
            pool.free([b])
        assert pool.refcount(b) == 0
        with pytest.raises(Exception):
            pool.free([b])
        assert pool.num_free == num_blocks


@given(st.integers(0, 10_000), st.integers(1, 256))
@settings(max_examples=100, deadline=None)
def test_blocks_needed_matches_token_count(ntokens, block_size):
    pool = BlockPool(num_blocks=1, block_size=block_size)
    nb = pool.blocks_needed(ntokens)
    assert nb * block_size >= ntokens          # covers every token
    assert (nb - 1) * block_size < max(ntokens, 1)   # no spare block


class _StubModel:
    @staticmethod
    def init_paged_cache(num_blocks, block_size, dtype=None, num_rows=0):
        return {"k": np.zeros((1, num_blocks, block_size, 1, 1)),
                "v": np.zeros((1, num_blocks, block_size, 1, 1))}


@given(st.lists(st.integers(1, 30), min_size=1, max_size=12))
@settings(max_examples=50, deadline=None)
def test_table_growth_matches_token_count(token_counts):
    """Each admitted request's table holds exactly ceil(tokens/bs) valid
    entries; freeing returns exactly that many blocks."""
    kv = PagedKVCache(_StubModel(), num_blocks=128, block_size=4,
                      num_slots=12, max_blocks_per_req=8)
    rows = []
    for i, n in enumerate(token_counts):
        free_before = kv.num_free_blocks
        row = kv.alloc(f"req{i}", n)
        nb = -(-n // 4)
        table = kv.table_rows([row])[0]
        assert (table >= 0).sum() == nb
        assert free_before - kv.num_free_blocks == nb
        rows.append((row, nb))
    for row, nb in rows:
        free_before = kv.num_free_blocks
        kv.free(row)
        assert kv.num_free_blocks - free_before == nb
    assert kv.num_free_blocks == 128 and kv.num_live == 0
