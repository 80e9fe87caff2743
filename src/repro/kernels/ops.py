"""Jitted public wrappers around the Pallas kernels.

Dispatch contract:
  * on TPU: compiled Pallas kernels (the production path);
  * elsewhere (this CPU container): ``interpret=True`` executes the same
    kernel bodies in Python for correctness validation, unless
    ``use_pallas=False`` falls back to the chunked-jnp implementations in
    ``repro.models.layers`` (the path the multi-pod dry-run lowers).

All wrappers are shape-polymorphic jit functions; block sizes are static
arguments so benchmarks can sweep them.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as jlayers

from . import (chunked_prefill_attention as _cpa,
               decode_attention as _fd, flash_attention as _fa,
               paged_decode_attention as _pfd,
               ragged_chunked_prefill as _rcp, ref as _ref, rmsnorm as _rn)


def _default_interpret() -> bool:
    """The one place that decides whether a Pallas kernel runs compiled
    (on TPU) or in interpret mode (elsewhere).  The wrappers below and
    the model's kernel sites (models/transformer.py) call it at trace
    time, so a compile for a described TPU steers it here."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "use_pallas", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128, use_pallas: bool = True,
                    interpret: Optional[bool] = None):
    """Prefill/train attention. q: (B,S,H,D); k/v: (B,S,KV,D)."""
    if not use_pallas:
        S = q.shape[1]
        pos = jnp.arange(S)
        return jlayers.chunked_attention(
            q, k, v, q_positions=pos, kv_positions=pos, causal=causal,
            window=window)
    interp = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interp)


@functools.partial(jax.jit, static_argnames=(
    "block_k", "use_pallas", "interpret"))
def flash_decode_attention(q, k_cache, v_cache, mask, *, block_k: int = 512,
                           use_pallas: bool = True,
                           interpret: Optional[bool] = None):
    """One-token decode attention. q: (B,H,D); caches: (B,S,KV,D);
    mask: (B,S) bool — valid cache slots (ring positions pre-resolved)."""
    if not use_pallas:
        B, H, D = q.shape
        S = k_cache.shape[1]
        # emulate via the layers decode path: mask -> positions trick
        kv_pos = jnp.where(mask[0], 0, 2**30)
        out = jlayers.decode_attention(
            q[:, None], k_cache, v_cache,
            q_position=jnp.int32(0), kv_positions=kv_pos,
            valid_len=jnp.int32(S))
        return out[:, 0]
    interp = _default_interpret() if interpret is None else interpret
    return _fd.flash_decode_attention(q, k_cache, v_cache, mask,
                                      block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                           use_pallas: bool = True,
                           interpret: Optional[bool] = None):
    """One-token decode attention over a paged KV cache.

    q: (B,H,D); pages: (N,bs,KV,D); block_tables: (B,nb) i32;
    seq_lens: (B,) i32.  ``use_pallas=False`` gathers the contiguous
    view in pure jnp (the path the model's paged decode lowers on CPU).
    """
    if not use_pallas:
        return _ref.paged_decode_attention_ref(q, k_pages, v_pages,
                                               block_tables, seq_lens)
    interp = _default_interpret() if interpret is None else interpret
    return _pfd.paged_flash_decode_attention(q, k_pages, v_pages,
                                             block_tables, seq_lens,
                                             interpret=interp)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def chunked_prefill_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                              *, use_pallas: bool = True,
                              interpret: Optional[bool] = None):
    """Chunked-prefill attention over a paged KV prefix.

    q: (B,T,H,D) chunk queries; pages: (N,bs,KV,D); block_tables:
    (B,nb) i32; ctx_lens: (B,) i32 prior-context lengths (pages already
    hold the chunk's K/V at ``ctx_lens .. ctx_lens+T-1``).
    ``use_pallas=False`` gathers the contiguous view in pure jnp (the
    path the model's chunked prefill lowers on CPU).
    """
    if not use_pallas:
        return _ref.chunked_prefill_attention_ref(q, k_pages, v_pages,
                                                  block_tables, ctx_lens)
    interp = _default_interpret() if interpret is None else interpret
    return _cpa.chunked_prefill_attention(q, k_pages, v_pages,
                                          block_tables, ctx_lens,
                                          interpret=interp)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ragged_chunked_prefill(q, k_new, v_new, k_pages, v_pages, block_tables,
                           meta, *, use_pallas: bool = True,
                           interpret: Optional[bool] = None):
    """Fused ragged chunked prefill: ALL scheduled chunks in one launch.

    q: (C,T_pad,H,D) per-chunk padded queries; k_new/v_new:
    (C,T_pad,KV,D) each chunk's fresh K/V; pages: (N,bs,KV,D);
    block_tables: (C,nb) i32; meta: (C,4) i32 rows
    ``[slot, ctx_len, chunk_len, q_offset]``.  Returns (out,
    new_k_pages, new_v_pages) — the chunk K/V scatter is fused in
    (aliased page outputs in the kernel; a drop-mode jnp scatter in the
    ``use_pallas=False`` oracle path).  Output rows past ``chunk_len``
    are undefined padding.
    """
    if not use_pallas:
        return _ref.ragged_chunked_prefill_ref(q, k_new, v_new, k_pages,
                                               v_pages, block_tables, meta)
    interp = _default_interpret() if interpret is None else interpret
    return _rcp.ragged_chunked_prefill(q, k_new, v_new, k_pages, v_pages,
                                       block_tables, meta,
                                       interpret=interp)


@functools.partial(jax.jit, static_argnames=(
    "eps", "block_rows", "use_pallas", "interpret"))
def rms_norm(x, weight, *, eps: float = 1e-6, block_rows: int = 256,
             use_pallas: bool = True, interpret: Optional[bool] = None):
    if not use_pallas:
        return jlayers.rms_norm(x, weight, eps)
    interp = _default_interpret() if interpret is None else interpret
    return _rn.rms_norm(x, weight, eps=eps, block_rows=block_rows,
                        interpret=interp)
