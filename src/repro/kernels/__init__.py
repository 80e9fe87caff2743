"""Pallas TPU kernels for the serving substrate's compute hot spots.

RT-LM itself is a scheduling layer (no kernel-level contribution), but
the LM substrate it manages has three hot spots that a production TPU
deployment tiles by hand; each has a pl.pallas_call implementation with
explicit VMEM BlockSpecs, a jitted wrapper (ops.py) and a pure-jnp
oracle (ref.py):

  flash_attention          — FA2-style prefill attention (causal /
                             sliding window), online softmax in VMEM
  decode_attention         — flash-decode GQA attention over long KV
                             caches
  paged_decode_attention   — flash-decode over a block table (paged KV
                             cache; a row's live pages DMA'd by hand
                             from the pool's own layout, 128 tokens
                             a chunk, double-buffered)
  chunked_prefill_attention — one prompt chunk over a paged prefix
                             (block-table scalar prefetch, (T*G, D)
                             query tile)
  ragged_chunked_prefill   — EVERY scheduled prefill chunk of an
                             engine iteration in ONE launch: packed
                             ragged queries, per-chunk
                             [slot, ctx_len, chunk_len, q_offset]
                             scalar-prefetch metadata rows, and the
                             chunk K/V scatter fused in via aliased
                             page outputs
  rmsnorm                  — fused normalization (one HBM round-trip)

Validated in interpret mode on CPU (tests/test_kernels.py sweeps
shapes/dtypes against ref.py); compiled on TPU targets.
"""

from . import ops, ref  # noqa: F401
