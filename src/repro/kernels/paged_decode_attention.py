"""Paged flash-decode: single-token GQA attention over a block table.

Same memory-bound regime and online-softmax structure as
``decode_attention.py``, but the KV cache is a pool of fixed-size pages
(``(num_pages, block_size, KV, D)``) and each sequence names its pages
through a ``(B, num_blocks)`` block table — KV memory scales with live
tokens, not ``B * max_len`` (vLLM's PagedAttention, here as a Pallas
TPU kernel).

The indirection happens in the BlockSpec index_map, not the kernel
body: the block table rides in as a scalar-prefetch operand
(``PrefetchScalarGridSpec``), so when the sequential innermost grid
dimension walks a sequence's logical blocks, Mosaic's pipeline DMAs the
*physical* page ``tables[b, i]`` into VMEM — an indirect gather at full
copy bandwidth, with no (B, max_len) contiguous view ever materialized
(the pure-jnp fallback in ``kernels/ref.py`` materializes exactly that
view; it is the semantic oracle, not the production path).

  grid = (B, KV, nb) — innermost sequential over table entries;
  per step: q-group tile (G, D) x page (block_size, D) on the MXU,
  masked by ``logical_pos < seq_len`` (table padding resolves to page 0,
  fully masked); running (m, l, acc) scratch identical to decode_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_fd_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                     m_scr, l_scr, acc_scr, *, scale: float,
                     block_size: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (bs, D) — page tables[b,ki]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # (G, bs)
    # logical positions covered by this table entry; padding entries
    # (ki >= ceil(seq_len / bs)) mask out entirely
    pos = (ki * block_size
           + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    valid = pos < lens_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # re-mask after the shift: when every position so far is masked,
    # m_new == s == NEG_INF and exp(s - m_new) would be 1, averaging
    # garbage page contents into the row (a seq_len == 0 row then
    # returns zeros instead)
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_flash_decode_attention(q, k_pages, v_pages, block_tables,
                                 seq_lens, *, interpret: bool = False):
    """q: (B, H, D); pages: (N, bs, KV, D); block_tables: (B, nb) i32
    physical page ids (pad with any valid id, e.g. 0); seq_lens: (B,)
    i32 valid logical lengths.  Returns (B, H, D).

    A ``seq_len == 0`` row attends to nothing and returns zeros (the
    pure-jnp oracle softmaxes over all -inf and yields NaN there, so
    only rows with ``seq_len >= 1`` are comparable against it).
    """
    B, H, D = q.shape
    N, bs, KV, _ = k_pages.shape
    _, nb = block_tables.shape
    G = H // KV
    scale = 1.0 / (D ** 0.5)

    qt = q.reshape(B, KV, G, D)
    kt = k_pages.transpose(2, 0, 1, 3)           # (KV, N, bs, D)
    vt = v_pages.transpose(2, 0, 1, 3)
    tables = block_tables.astype(jnp.int32)
    lens = seq_lens.astype(jnp.int32)

    kernel = functools.partial(_paged_fd_kernel, scale=scale,
                               block_size=bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block_tables, seq_lens
        grid=(B, KV, nb),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, i, t, s: (b, h, 0, 0)),
            # the indirection: page tables[b, i] streams into VMEM
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, i, t, s: (h, t[b, i], 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, i, t, s: (h, t[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, i, t, s: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
        name="paged_decode_kernel",    # the device op's name in a trace
    )(tables, lens, qt, kt, vt)
    return out.reshape(B, H, D)
