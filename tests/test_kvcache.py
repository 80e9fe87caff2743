"""Paged KV-cache subsystem: allocator invariants and page gather /
scatter round-trips (deterministic; always runs).

The hypothesis fuzzed forms of these invariants live in
tests/test_properties.py behind its ``importorskip`` guard; the
engine-level no-leak-after-serve and token-parity properties live in
tests/test_paged_engine.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kvcache import BlockAllocator, blocks_for_tokens
from repro.kvcache.allocator import OutOfBlocksError
from repro.kvcache.paged import (gather_tokens, scatter_prefill,
                                 scatter_token)


# ---------------------------------------------------------------------------
# deterministic allocator coverage
# ---------------------------------------------------------------------------


def test_blocks_for_tokens():
    assert blocks_for_tokens(0, 16) == 0
    assert blocks_for_tokens(1, 16) == 1
    assert blocks_for_tokens(16, 16) == 1
    assert blocks_for_tokens(17, 16) == 2
    assert blocks_for_tokens(-3, 16) == 0


def test_allocator_basics():
    a = BlockAllocator(4, 16)
    b0 = a.allocate(seq_id=7)
    b1, b2 = a.allocate_n(seq_id=9, n=2)
    assert len({b0, b1, b2}) == 3            # all distinct
    assert a.num_used == 3 and a.num_free == 1
    assert a.table(9) == [b1, b2]
    assert a.free_sequence(9) == 2
    assert a.num_free == 3
    assert a.free_sequence(9) == 0           # idempotent
    assert a.free_sequence(7) == 1
    a.check_no_leaks()


def test_allocator_exhaustion():
    a = BlockAllocator(2, 16)
    a.allocate_n(seq_id=0, n=2)
    with pytest.raises(OutOfBlocksError):
        a.allocate(seq_id=1)
    with pytest.raises(OutOfBlocksError):
        a.allocate_n(seq_id=1, n=1)
    # a failed allocate_n must not leak partial grabs
    a.free_sequence(0)
    with pytest.raises(OutOfBlocksError):
        a.allocate_n(seq_id=1, n=3)
    assert a.table(1) == []
    assert a.num_free == 2


def test_freed_blocks_are_reusable():
    a = BlockAllocator(2, 8)
    first = set(a.allocate_n(seq_id=0, n=2))
    a.free_sequence(0)
    second = set(a.allocate_n(seq_id=1, n=2))
    assert first == second


# ---------------------------------------------------------------------------
# deterministic page round-trip
# ---------------------------------------------------------------------------


def test_page_roundtrip_prefill_then_tokens():
    """scatter_prefill + per-token scatter_token reproduce the logical
    sequence exactly under gather_tokens (the contiguous-layout
    equivalence the token-parity engine test relies on)."""
    bs, nb, N = 4, 3, 8
    feat = (2, 5)
    key = jax.random.PRNGKey(0)
    seq = jax.random.normal(key, (nb * bs,) + feat)
    pages = jnp.zeros((N, bs) + feat)
    table = jnp.asarray([5, 1, 6], jnp.int32)
    S = 6
    pages = scatter_prefill(pages, seq[:S], table, S)
    for pos in range(S, nb * bs):
        pages = scatter_token(pages, seq[pos][None],
                              table[None, :], jnp.asarray([pos]))
    got = gather_tokens(pages, table[None, :])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(seq))


def test_roundtrip_with_permuted_table_and_stale_pages():
    """Gather is exact even when physical order != logical order and
    spare pages hold garbage (the stale-content case after eviction)."""
    bs, nb, N = 3, 4, 9
    rng = np.random.default_rng(1)
    table = jnp.asarray([7, 0, 3, 5], jnp.int32)
    pages = jnp.asarray(rng.normal(size=(N, bs, 2)).astype(np.float32))
    S = 10
    seq = jnp.asarray(rng.normal(size=(S, 2)).astype(np.float32))
    pages = scatter_prefill(pages, seq[:4], table, 4)
    for pos in range(4, S):
        pages = scatter_token(pages, seq[pos][None], table[None, :],
                              jnp.asarray([pos]))
    got = gather_tokens(pages, table[None, :])[0, :S]
    np.testing.assert_allclose(np.asarray(got), np.asarray(seq),
                               atol=0, rtol=0)


def test_scatter_token_writes_one_layer_of_a_stack():
    """The decode scan writes a token into layer ``layer`` of the whole
    stacked pool: that layer's pages take the rows (a dead row, its
    table at the trash page and its position past the table, lands in
    the trash page), every other layer is untouched, and the gather at
    that layer reads the sequence back."""
    bs, nb, N, depth, layer = 4, 3, 8, 3, 1
    trash = N - 1
    rng = np.random.default_rng(2)
    stack = jnp.asarray(rng.normal(size=(depth, N, bs, 2)).astype(np.float32))
    tables = jnp.asarray([[5, 1, 6], [trash] * nb], jnp.int32)
    pos = jnp.asarray([5, 40], jnp.int32)          # row 1: dead, stale pos
    vals = jnp.asarray(rng.normal(size=(2, 2)).astype(np.float32))
    out = scatter_token(stack, vals, tables, pos, jnp.int32(layer))
    want = stack.at[layer, 1, 1].set(vals[0]).at[layer, trash, 0].set(vals[1])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # the same write into the unstacked layer, and the gather at the layer
    one = scatter_token(stack[layer], vals, tables, pos)
    np.testing.assert_array_equal(np.asarray(out[layer]), np.asarray(one))
    np.testing.assert_array_equal(
        np.asarray(gather_tokens(out, tables, jnp.int32(layer))),
        np.asarray(gather_tokens(one, tables)))
