"""Paged KV-cache subsystem (vLLM-style block tables + prefix reuse).

PR 1's continuous engine reserves a contiguous ``(slots, max_len)`` KV
cache, so concurrency is pinned to the worst-case output length — the
exact uncertainty-inflated bound RT-LM identifies.  This package
decouples the two: KV memory is a pool of fixed-size blocks, sequences
own *block tables*, and memory scales with live tokens instead of
slots.  On top of that indirection, shared prompt PREFIXES (personas,
system prompts) can map many sequences to the same physical blocks.

  allocator.BlockAllocator — host-side free-list allocator with
      per-sequence block tables, per-block REFERENCE COUNTS (sharing /
      copy-on-write via ``share``/``cow_block``; a block frees only at
      refcount zero) and a ``reclaim`` hook for cache eviction under
      pool pressure.
  allocator.blocks_for_tokens — the shared memory formula
      ``ceil(tokens / block_size)`` used by the engine's admission gate
      and the simulator's block-budget model (they must agree exactly
      for engine-vs-sim parity).
  allocator.window_target_tokens — the multi-step decode-window
      extension target (eviction-lag accounting for the async host
      pipeline): pre-window allocation covers every USEFUL write of an
      N-step launch, clamped at the admission reservation so overhang
      writes past EOS/cap never touch foreign blocks and rejection
      decisions are independent of N.
  allocator.decode_pages_walked — the pages the paged decode kernel
      reads for one slot over an N-step window (its live table
      entries), shared by the engine's and the simulator's count.
  paged.PagedKVCache — device-side paged K/V store (one
      ``(num_blocks, block_size, kv_heads, head_dim)`` array pair per
      layer) plus the pure-jnp gather/scatter/copy primitives the
      model's paged decode path and the Pallas paged kernels are built
      on.
  prefix.PrefixCache — content-hash prefix index over written prompt
      blocks: longest-cached-prefix matching at block granularity
      (``block_hashes`` hash chain), LRU eviction of unreferenced
      cached blocks only under allocator pressure, and copy-on-write
      on the one divergent write the engine performs (the recomputed
      final position of a fully matched prompt).  Pure host-side,
      driven identically by the real engine and the simulator.

Wiring: models/transformer.py (``init_paged_cache`` / ``write_paged`` /
``copy_paged_block`` / paged decode + chunk attention),
serving/engine.py (``kv="paged"``, ``prefix_cache=True`` for
``mode="continuous"``), core/simulator.py (block-budget admission and
the same host-side prefix-cache model), kernels/ (Pallas
``paged_decode_attention`` and ``chunked_prefill_attention`` over block
tables).  See docs/ARCHITECTURE.md for the full configuration matrix.
"""

from .allocator import (BlockAllocator, blocks_for_tokens,  # noqa: F401
                        decode_pages_walked, window_target_tokens)
from .paged import PagedKVCache  # noqa: F401
from .prefix import PrefixCache, block_hashes  # noqa: F401
