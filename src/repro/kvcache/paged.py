"""Device-side paged K/V store: gather/scatter primitives + container.

Pages are arrays of shape ``(num_blocks, block_size, *feat)`` (feat =
``(kv_heads, head_dim)`` for attention caches).  A sequence's tokens
live at logical position ``p`` inside physical block ``table[p // bs]``
at offset ``p % bs`` — exactly the vLLM block-table layout, so the
gathered view of a sequence is bit-identical to what a contiguous
(absolute-position) cache would hold.  That bit-exactness is what the
token-for-token paged-vs-contiguous engine parity test leans on: masked
positions contribute exp(-inf) == 0.0 exactly, so layout padding never
perturbs the softmax.

The primitives are pure jnp (jit/vmap-safe, traced table operands) and
are the semantic reference for the Pallas kernel in
``repro.kernels.paged_decode_attention``; the model's paged decode path
(models/transformer.py) composes them with the existing
``layers.decode_attention``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .allocator import blocks_for_tokens

Array = jax.Array


# ---------------------------------------------------------------------------
# gather / scatter primitives
# ---------------------------------------------------------------------------


def _flat(pages: Array) -> Array:
    """(N, bs, *feat) -> (N*bs, *feat) token-major view."""
    N, bs = pages.shape[:2]
    return pages.reshape((N * bs,) + pages.shape[2:])


def _layer_view(pages: Array, tables: Array, layer) -> tuple:
    """A stacked pool ``(L, N, bs, *feat)`` read at ``layer`` (traced
    scalar): every layer's pages as one ``(L*N, bs, *feat)`` pool (a
    reshape of leading dims, no copy) and the tables shifted to that
    layer's pages.  ``layer=None``: an unstacked pool, as it is."""
    if layer is None:
        return pages, tables
    L, N = pages.shape[:2]
    return (pages.reshape((L * N,) + pages.shape[2:]),
            tables + jnp.asarray(layer, tables.dtype) * N)


def gather_tokens(pages: Array, tables: Array, layer=None) -> Array:
    """Gather each sequence's tokens in logical order.

    pages: (N, bs, *feat), or a stacked (L, N, bs, *feat) read at
    ``layer``; tables: (B, nb) i32 physical block ids.
    Returns (B, nb*bs, *feat) — row b's logical positions 0..nb*bs-1.
    Entries past a sequence's written length are whatever the page
    holds (zeros or stale data); callers mask by valid length.
    """
    pages, tables = _layer_view(pages, tables, layer)
    bs = pages.shape[1]
    B, nb = tables.shape
    idx = (tables[:, :, None] * bs
           + jnp.arange(bs, dtype=tables.dtype)[None, None, :])
    return jnp.take(_flat(pages), idx.reshape(B, nb * bs), axis=0)


def scatter_token(pages: Array, values: Array, tables: Array,
                  pos: Array, layer=None) -> Array:
    """Write one token per sequence at its current logical position.

    pages: (N, bs, *feat), or a stacked (L, N, bs, *feat) written at
    ``layer`` (its other layers untouched); values: (B, *feat); tables:
    (B, nb) i32; pos: (B,) i32 logical positions.  Distinct sequences
    own distinct blocks (allocator invariant), so rows never collide.
    The table lookup clamps ``pos // bs`` to the table width: evicted
    (dead) decode rows keep stepping with a stale, ever-growing ``pos``,
    and their table rows point at the reserved trash page — the clamp
    makes every dead-row write land there instead of indexing out of
    bounds.
    """
    flat, tables = _layer_view(pages, tables, layer)
    bs = flat.shape[1]
    blk_idx = jnp.minimum(pos[:, None] // bs, tables.shape[1] - 1)
    blk = jnp.take_along_axis(tables, blk_idx, axis=1)[:, 0]
    flat_idx = blk * bs + pos % bs
    out = _flat(flat).at[flat_idx].set(values.astype(pages.dtype))
    return out.reshape(pages.shape)


def scatter_chunk(pages: Array, seq: Array, table_row: Array,
                  start: Array) -> Array:
    """Write one sequence's prefill CHUNK at a traced position offset.

    pages: (N, bs, *feat); seq: (T, *feat) the chunk's K/V; table_row:
    (nb,) i32; start: scalar i32 — the chunk covers logical positions
    ``start .. start + T - 1``.  Unlike ``scatter_prefill`` (static
    offset 0, unrolled dynamic-update-slices) the offset is traced, so
    one jitted executable serves every chunk of a prompt; like
    ``scatter_token`` the block lookup clamps to the table width so a
    trash-table row degrades to trash-page writes instead of indexing
    out of bounds.
    """
    bs = pages.shape[1]
    T = seq.shape[0]
    pos = start + jnp.arange(T, dtype=jnp.int32)
    blk_idx = jnp.minimum(pos // bs, table_row.shape[0] - 1)
    blk = jnp.take(table_row, blk_idx)
    flat_idx = blk * bs + pos % bs
    out = _flat(pages).at[flat_idx].set(seq.astype(pages.dtype))
    return out.reshape(pages.shape)


def scatter_packed(pages: Array, seq: Array, tables: Array,
                   token_chunk: Array, positions: Array,
                   valid: Array) -> Array:
    """Write a PACKED multi-chunk K/V stream in one pass.

    pages: (N, bs, *feat); seq: (TT, *feat) — the fused ragged-prefill
    executable's packed token stream (every scheduled chunk of one
    engine iteration back to back, plus padding); tables: (C, nb) i32
    per-chunk block tables; token_chunk: (TT,) i32 mapping each packed
    row to its chunk; positions: (TT,) i32 absolute logical positions;
    valid: (TT,) bool — False rows (padding) are DROPPED, never
    written (out-of-bounds drop-mode scatter), so the pool is
    bit-identical to what per-chunk ``scatter_chunk`` calls would
    produce.  Distinct chunks map distinct sequences (pack_plans merges
    same-job plans), so rows never collide; the block lookup clamps to
    the table width like the other scatter primitives.
    """
    bs = pages.shape[1]
    N = pages.shape[0]
    nb = tables.shape[1]
    blk_idx = jnp.minimum(positions // bs, nb - 1)
    blk = tables[token_chunk, blk_idx]
    flat_idx = jnp.where(valid, blk * bs + positions % bs, N * bs)
    out = _flat(pages).at[flat_idx].set(seq.astype(pages.dtype),
                                        mode="drop")
    return out.reshape(pages.shape)


def copy_block(pages: Array, src: Array, dst: Array) -> Array:
    """Copy one physical page: ``pages[dst] = pages[src]``.

    ``src``/``dst`` are traced scalars, so one jitted executable serves
    every copy-on-write — the prefix cache's full-match admission path
    duplicates the last shared block before the (re)computed final
    prompt position is written into it (``kvcache.prefix``).
    """
    row = lax.dynamic_slice_in_dim(pages, src, 1, axis=0)
    return lax.dynamic_update_slice_in_dim(pages, row, dst, axis=0)


def scatter_prefill(pages: Array, seq: Array, table_row: Array,
                    seq_len: int) -> Array:
    """Write a freshly prefilled sequence into its table's blocks.

    pages: (N, bs, *feat); seq: (S, *feat) with S >= seq_len (the
    prefill cache's leading ``max_len`` rows — only the first
    ``seq_len`` are written); table_row: (nb,) i32.  ``seq_len`` is
    static (the engine's input bucket), so this unrolls into
    ``ceil(seq_len / bs)`` dynamic-update-slices with traced block ids.
    """
    bs = pages.shape[1]
    zeros = (0,) * (pages.ndim - 2)
    for j in range(blocks_for_tokens(seq_len, bs)):
        chunk_len = min(bs, seq_len - j * bs)
        chunk = seq[j * bs:j * bs + chunk_len].astype(pages.dtype)[None]
        pages = lax.dynamic_update_slice(
            pages, chunk, (table_row[j], 0) + zeros)
    return pages


# ---------------------------------------------------------------------------
# container
# ---------------------------------------------------------------------------


class PagedKVCache:
    """Paged KV store for the continuous engine.

    Owns the device-side state pytree (per-layer K/V page arrays plus
    per-slot ``pos``, built by ``transformer.init_paged_cache``) and the
    host-side ``(num_slots, max_blocks_per_seq)`` block-table array the
    jitted prefill/decode executables consume.  Memory formula:

        bytes = layers * 2 * num_blocks * block_size
                       * kv_heads * head_dim * dtype_bytes

    versus ``layers * 2 * num_slots * max_len * ...`` for the contiguous
    slot cache — paged capacity scales with *live tokens* (allocated
    blocks), not with worst-case sequence length per slot.
    """

    def __init__(self, cfg, num_slots: int, num_blocks: int,
                 block_size: int, max_len: int, dtype=jnp.bfloat16):
        from repro.models import transformer  # lazy: avoid import cycle
        self.num_slots = num_slots
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_len = max_len
        self.max_blocks_per_seq = blocks_for_tokens(max_len, block_size)
        # one extra physical page the allocator never hands out: the
        # decode step writes a KV entry for EVERY row, and evicted
        # (dead) rows must not scribble over blocks that may already
        # belong to a newly admitted sequence — their tables point here.
        # It is the pool's LAST page: the paged decode call site
        # (transformer._attn_decode_paged) reads a table that starts
        # here as a dead row and gives the kernel a length of 0.
        self.trash_block = num_blocks
        self.state = transformer.init_paged_cache(
            cfg, num_slots, num_blocks + 1, block_size, dtype)
        # host-side table copy; rows are rewritten at admission and
        # extended at block-boundary crossings, then shipped to the
        # jitted executables as a (num_slots, nb_max) i32 operand.
        self.tables = np.full((num_slots, self.max_blocks_per_seq),
                              self.trash_block, np.int32)

    # -- table management (host) ---------------------------------------
    def set_table(self, slot: int, blocks) -> None:
        """Install a freshly admitted sequence's table into ``slot``."""
        row = np.full((self.max_blocks_per_seq,), self.trash_block,
                      np.int32)
        row[:len(blocks)] = blocks
        self.tables[slot] = row

    def extend_table(self, slot: int, block_index: int, block: int) -> None:
        """Record a boundary-crossing allocation for ``slot``."""
        self.tables[slot, block_index] = block

    def clear_table(self, slot: int) -> None:
        """Point an evicted slot back at the trash page."""
        self.tables[slot] = self.trash_block

    def tables_device(self) -> Array:
        return jnp.asarray(self.tables)

    def table_row(self, slot: int) -> Array:
        return jnp.asarray(self.tables[slot])


def default_num_blocks(num_slots: int, max_len: int,
                       block_size: int) -> int:
    """Block count matching a contiguous ``(num_slots, max_len)`` slot
    cache's KV-token budget — the equal-budget comparison the
    paged-vs-contiguous benchmark and capacity tests are built on."""
    return max(1, num_slots * max_len // block_size)
