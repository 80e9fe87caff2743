"""Paged flash-decode: single-token GQA attention over a block table.

Same memory-bound regime and online-softmax structure as
``decode_attention.py``, but the KV cache is a pool of fixed-size pages
(``(num_pages, block_size, KV, D)``) and each sequence names its pages
through a ``(B, num_blocks)`` block table — KV memory scales with live
tokens, not ``B * max_len`` (vLLM's PagedAttention, here as a Pallas
TPU kernel).

The pools are the model's whole stacked pools ``(L, N, bs, KV, D)``,
one ``(N, bs, KV, D)`` pool a layer; they stay in HBM in their own
layout (``memory_space=pl.ANY``) and the kernel fetches layer
``layer``'s pages by hand: the block table, the lengths and the layer
ride in as scalar-prefetch operands, so the page ids are known before
any DMA is issued, and no layer's pool is sliced out of the stack.

  grid = (B,) — one step a row, sequential ("arbitrary": a v5e has one
  TensorCore, so a parallel axis buys nothing);
  per row: the live pages ``min(cdiv(seq_len, bs), nb)`` only, in chunks
  of ``P = min(128 // bs, nb)`` pages so a score tile spans the 128
  lanes.  Each chunk's whole K and V pages (both heads' halves together)
  are DMA'd into one slot of a two-slot VMEM buffer while the other
  slot is computed; the next row's first chunk is started at the end of
  the row.  A ``seq_len == 0`` row fetches nothing and returns zeros.
  Per chunk and KV head: q-group tile (G, D) x (P * bs, D) on the MXU,
  masked by ``logical_pos < seq_len``; running (m, l, acc) in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_fd_kernel(tables_ref, lens_ref, layer_ref, q_ref, k_hbm, v_hbm,
                     o_ref, k_buf, v_buf, sem, chunk_ref, *, scale: float,
                     block_size: int, pages_per_chunk: int, nb: int):
    b = pl.program_id(0)
    nrows = pl.num_programs(0)
    bs, P = block_size, pages_per_chunk
    KV = k_buf.shape[3]
    _, H, D = q_ref.shape
    G = H // KV
    layer = layer_ref[0]

    def live_pages(row):
        return jnp.minimum(pl.cdiv(lens_ref[row], bs), nb)

    def chunk_copies(row, c, slot):
        """The DMAs of chunk ``c`` of ``row`` into buffer ``slot``: one
        per live page and pool, each guarded by the same predicate at
        start and at wait (a dead page is neither fetched nor waited
        for; its buffer entry keeps earlier, finite contents, which the
        mask zeroes)."""
        n = live_pages(row)
        out = []
        for j in range(P):
            page_idx = c * P + j
            live = page_idx < n
            # read whether or not the page is live: keep it in the row
            page = tables_ref[row * nb + jnp.minimum(page_idx, nb - 1)]
            out.append((live, pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[slot, j], sem.at[0, slot])))
            out.append((live, pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[slot, j], sem.at[1, slot])))
        return out

    def start(row, c, slot):
        for live, cp in chunk_copies(row, c, slot):
            pl.when(live)(cp.start)

    def wait(row, c, slot):
        for live, cp in chunk_copies(row, c, slot):
            pl.when(live)(cp.wait)

    @pl.when(b == 0)
    def _first():
        # a dead page's V entry meets probability 0, and 0 * NaN is not
        # zero: start the buffer finite (a masked K entry never counts)
        v_buf[...] = jnp.zeros_like(v_buf)
        chunk_ref[0] = 0
        start(0, 0, 0)

    eff_len = jnp.minimum(lens_ref[b], nb * bs)
    n_chunks = pl.cdiv(live_pages(b), P)
    first_chunk = chunk_ref[0]        # chunks consumed by earlier rows
    q = q_ref[0].astype(jnp.float32)  # (H, D): KV head h owns rows h*G..

    def body(c, carry):
        slot = (first_chunk + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _next():
            start(b, c + 1, 1 - slot)

        wait(b, c, slot)
        pos = (c * (P * bs)
               + jax.lax.broadcasted_iota(jnp.int32, (G, P * bs), 1))
        valid = pos < eff_len
        out = []
        for h in range(KV):
            m_prev, l_prev, acc_prev = carry[h]
            k = k_buf[slot, :, :, h, :].reshape(P * bs, D).astype(jnp.float32)
            v = v_buf[slot, :, :, h, :].reshape(P * bs, D).astype(jnp.float32)
            s = jax.lax.dot_general(
                q[h * G:(h + 1) * G], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (G, P*bs)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # re-mask after the shift: when every position so far is
            # masked, m_new == s == NEG_INF and exp(s - m_new) would be 1
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            out.append((m_new,
                        l_prev * corr + p.sum(axis=-1, keepdims=True),
                        acc_prev * corr + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)))
        return tuple(out)

    init = tuple((jnp.full((G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((G, 1), jnp.float32),
                  jnp.zeros((G, D), jnp.float32)) for _ in range(KV))
    res = jax.lax.fori_loop(0, n_chunks, body, init)
    for h in range(KV):
        _, l_fin, acc = res[h]
        o_ref[0, h * G:(h + 1) * G] = (
            acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)

    next_chunk = first_chunk + n_chunks
    chunk_ref[0] = next_chunk

    @pl.when(b + 1 < nrows)
    def _next_row():
        start(b + 1, 0, next_chunk % 2)


def paged_flash_decode_attention(q, k_pages, v_pages, block_tables,
                                 seq_lens, layer, *, interpret: bool = False):
    """q: (B, H, D); pages: stacked (L, N, bs, KV, D), of which layer
    ``layer`` (i32 scalar, may be traced) is read — an unstacked pool
    comes as ``pool[None]`` and layer 0; block_tables: (B, nb) i32
    physical page ids (pad with any valid id, e.g. 0); seq_lens: (B,)
    i32 valid logical lengths, clamped to ``nb * bs``.  Returns
    (B, H, D).

    Only a row's first ``min(cdiv(seq_len, bs), nb)`` table entries are
    read.  A ``seq_len == 0`` row attends to nothing and returns zeros
    (the pure-jnp oracle softmaxes over all -inf and yields NaN there,
    so only rows with ``seq_len >= 1`` are comparable against it).
    """
    B, H, D = q.shape
    _, _, bs, KV, _ = k_pages.shape
    _, nb = block_tables.shape
    G = H // KV
    P = max(1, min(128 // bs, nb))

    kernel = functools.partial(_paged_fd_kernel, scale=1.0 / (D ** 0.5),
                               block_size=bs, pages_per_chunk=P, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # block_tables, seq_lens, layer
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, t, s, l: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, t, s, l: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, KV, D), k_pages.dtype),
            pltpu.VMEM((2, P, bs, KV, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),     # (K|V, slot)
            pltpu.SMEM((1,), jnp.int32),         # chunks consumed so far
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_kernel",    # the device op's name in a trace
    )(block_tables.astype(jnp.int32).reshape(-1),
      seq_lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, k_pages, v_pages)
    return out
