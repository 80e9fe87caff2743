"""Paged flash-decode over MLA latent pages: every query head against
one shared latent head.

The pool is the model's stacked ``(num_layers, num_pages, block_size,
L)``: one latent of ``L = rank + rope`` values a token and layer
(``models/mla.py``).  Scores run over all ``L`` lanes (absorbed query
against ``[c, k_pe]``), values over the first ``rank`` lanes of the
SAME page, so each page is read once.

Same walk as ``paged_decode_attention.py``: the block table, the
lengths and the layer are scalar-prefetch operands, the pool stays in
HBM (``memory_space=pl.ANY``) and the kernel DMAs the layer's live
pages of each row by hand, ``P = min(128 // bs, nb)`` pages a chunk,
into a two-slot VMEM buffer while the other slot is computed; the next
row's first chunk is started at the end of a row.  A ``seq_len == 0``
row (dead slot) fetches nothing and returns zeros.

  grid = (B,) ("arbitrary");
  per chunk: (H, L) x (P * bs, L) scores, masked by
  ``logical_pos < seq_len``, then (H, P * bs) x (P * bs, rank) values;
  running (m, l, acc) in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mla_fd_kernel(tables_ref, lens_ref, layer_ref, q_ref, pool_hbm, o_ref,
                   buf, sem, chunk_ref, *, scale: float, block_size: int,
                   pages_per_chunk: int, nb: int, rank: int):
    b = pl.program_id(0)
    nrows = pl.num_programs(0)
    bs, P = block_size, pages_per_chunk
    _, H, L = q_ref.shape
    layer = layer_ref[0]

    def live_pages(row):
        return jnp.minimum(pl.cdiv(lens_ref[row], bs), nb)

    def chunk_copies(row, c, slot):
        """One DMA per live page of chunk ``c`` of ``row``, each guarded
        by the same predicate at start and at wait."""
        n = live_pages(row)
        out = []
        for j in range(P):
            page_idx = c * P + j
            page = tables_ref[row * nb + jnp.minimum(page_idx, nb - 1)]
            out.append((page_idx < n, pltpu.make_async_copy(
                pool_hbm.at[layer, page], buf.at[slot, j], sem.at[slot])))
        return out

    def start(row, c, slot):
        for live, cp in chunk_copies(row, c, slot):
            pl.when(live)(cp.start)

    def wait(row, c, slot):
        for live, cp in chunk_copies(row, c, slot):
            pl.when(live)(cp.wait)

    @pl.when(b == 0)
    def _first():
        # a dead page's value lanes meet probability 0, and 0 * NaN is
        # not zero: start the buffer finite
        buf[...] = jnp.zeros_like(buf)
        chunk_ref[0] = 0
        start(0, 0, 0)

    eff_len = jnp.minimum(lens_ref[b], nb * bs)
    n_chunks = pl.cdiv(live_pages(b), P)
    first_chunk = chunk_ref[0]        # chunks consumed by earlier rows
    q = q_ref[0].astype(jnp.float32)  # (H, L)

    def body(c, carry):
        m_prev, l_prev, acc_prev = carry
        slot = (first_chunk + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _next():
            start(b, c + 1, 1 - slot)

        wait(b, c, slot)
        kv = buf[slot].reshape(P * bs, L).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (H, P*bs)
        pos = c * (P * bs) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < eff_len
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        acc = acc_prev * corr + jax.lax.dot_general(
            p, kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_prev * corr + p.sum(axis=-1, keepdims=True), acc

    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, rank), jnp.float32))
    _, l_fin, acc = jax.lax.fori_loop(0, n_chunks, body, init)
    o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)

    next_chunk = first_chunk + n_chunks
    chunk_ref[0] = next_chunk

    @pl.when(b + 1 < nrows)
    def _next_row():
        start(b + 1, 0, next_chunk % 2)


def mla_decode_attention(q, pool, block_tables, seq_lens, layer, *,
                         rank: int, interpret: bool = False):
    """q: (B, H, L) absorbed queries (pre-scaled: the kernel divides by
    sqrt(L), as the jnp path does); pool: stacked (num_layers, N, bs, L),
    of which layer ``layer`` (i32 scalar, may be traced) is read — an
    unstacked pool comes as ``pool[None]`` and layer 0; block_tables:
    (B, nb) i32; seq_lens: (B,) i32, clamped to ``nb * bs``.  Returns
    (B, H, rank): attention over the first ``rank`` lanes of each
    page."""
    B, H, L = q.shape
    _, _, bs, _ = pool.shape
    _, nb = block_tables.shape
    P = max(1, min(128 // bs, nb))
    kernel = functools.partial(_mla_fd_kernel, scale=1.0 / (L ** 0.5),
                               block_size=bs, pages_per_chunk=P, nb=nb,
                               rank=rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # block_tables, seq_lens, layer
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, L), lambda b, t, s, l: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, t, s, l: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, L), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),         # chunks consumed so far
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode_kernel",      # the device op's name in a trace
    )(block_tables.astype(jnp.int32).reshape(-1),
      seq_lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, pool)
