"""Ragged chunked prefill over MLA latent pages: every scheduled chunk of
one launch, each chunk's query rows against its own block table.

The chunks' latents are written into the pool before the call
(``kvcache.paged.scatter_packed``), so a chunk attends fully over its
prefix and causally within itself with one mask: query row ``r`` of
chunk ``c`` is token ``t = r // H`` at logical position ``ctx_len + t``
and sees positions ``<= ctx_len + t``.  Scores run over all ``L`` lanes
of a page, values over its first ``rank`` lanes (``mla_decode.py``).

  grid = (C, nq, nb): chunk, a tile of ``tq`` query rows (rows are
  t-major, ``t * H + h``), then the table's entries, innermost and
  sequential.  The block table and the chunk metadata
  ``[slot, ctx_len, chunk_len, q_offset]`` are scalar-prefetch operands;
  the page index_map clamps past a chunk's live pages
  (``cdiv(ctx_len + chunk_len, bs)``) to its last live one, so no dead
  page is fetched, and the steps past it compute nothing.  Running
  (m, l, acc) in float32 scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _live(meta_ref, c, bs):
    return pl.cdiv(meta_ref[c * 4 + 1] + meta_ref[c * 4 + 2], bs)


def _mla_prefill_kernel(tables_ref, meta_ref, q_ref, page_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, scale: float,
                        block_size: int, heads: int, rank: int):
    c, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    tq = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki < _live(meta_ref, c, block_size))
    def _step():
        q = q_ref[0].astype(jnp.float32)              # (tq, L)
        kv = page_ref[0].astype(jnp.float32)          # (bs, L)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (tq, bs)
        kv_pos = (ki * block_size
                  + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        t = (qi * tq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)) \
            // heads
        valid = kv_pos <= meta_ref[c * 4 + 1] + t
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def mla_prefill_attention(q, pool, block_tables, meta, *, rank: int,
                          interpret: bool = False):
    """q: (C, Tp, H, L) each chunk's absorbed, pre-scaled queries (row
    ``t`` at position ``ctx_len + t``); pool: (N, bs, L) holding every
    chunk's latents already; block_tables: (C, nb) i32; meta: (C, 4) i32
    rows ``[slot, ctx_len, chunk_len, q_offset]``.  Returns
    (C, Tp, H, rank).  Rows past ``chunk_len`` are padding (computed,
    finite, unused); a chunk with no live page returns zeros."""
    C, Tp, H, L = q.shape
    N, bs, _ = pool.shape
    _, nb = block_tables.shape
    R = Tp * H
    tq = R if R <= 256 else 256
    if R % tq:
        raise ValueError(f"query rows {R} are not a multiple of {tq}")
    qr = q.reshape(C, R, L)

    def page(c, qi, i, t, m):
        last = jnp.maximum(_live(m, c, bs), 1) - 1
        return (t[c * nb + jnp.minimum(i, last)], 0, 0)

    kernel = functools.partial(_mla_prefill_kernel, scale=1.0 / (L ** 0.5),
                               block_size=bs, heads=H, rank=rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block_tables, meta
        grid=(C, R // tq, nb),
        in_specs=[
            pl.BlockSpec((1, tq, L), lambda c, qi, i, t, m: (c, qi, 0)),
            pl.BlockSpec((1, bs, L), page),
        ],
        out_specs=pl.BlockSpec((1, tq, rank),
                               lambda c, qi, i, t, m: (c, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, rank), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, R, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="mla_prefill_kernel",     # the device op's name in a trace
    )(block_tables.astype(jnp.int32).reshape(-1),
      meta.astype(jnp.int32).reshape(-1), qr, pool)
    return out.reshape(C, Tp, H, rank)
