"""Fused ragged chunked-prefill: every scheduled chunk in ONE launch.

The chunked-prefill engine used to issue one jnp scatter PLUS one
``chunked_prefill_attention`` launch per chunk per request — O(#chunks)
dispatches per iteration, which is exactly the dispatch-overhead regime
where the measured p99 ITL wins shrink on small-batch hosts.  This
kernel executes the whole per-iteration ``ChunkPlan`` batch at once:

  * queries arrive as a per-chunk padded view of the engine's PACKED
    ``(total_tokens, D)`` layout — chunk ``c`` owns rows
    ``q_offset[c] .. q_offset[c] + chunk_len[c] - 1`` of the packed
    stream, re-tiled host-side to ``(C, T_pad, H, D)`` (``T_pad`` is
    the launch's padded max chunk length; rows past ``chunk_len`` are
    padding whose output is undefined);
  * per-chunk metadata rides as a scalar-prefetch operand ``meta``
    with rows ``[slot, ctx_len, chunk_len, q_offset]`` next to the
    per-chunk block tables — the same indirection recipe as
    ``paged_decode_attention``;
  * the chunk's K/V SCATTER is fused in: page blocks are ALIASED
    outputs, and while the innermost grid dimension walks a chunk's
    table entries, any page overlapping logical positions
    ``ctx_len .. ctx_len + chunk_len - 1`` is rewritten with the
    chunk's fresh K/V rows (a one-hot MXU matmul, not a gather) —
    no separate ``kvcache.paged.scatter_*`` pass, no second HBM walk;
  * attention is split into two online-softmax phases: PREFIX pages
    (logical position < ctx_len) stream from the (pre-scatter) pool,
    and the CAUSAL-IN-CHUNK part runs against the chunk's own K/V
    inputs in the last grid steps — summing to exactly the
    full-over-prefix / causal-in-chunk mask of the per-chunk kernel.

  grid = (C, KV, nq, nb + nk) — the last axis is sequential: its first
  ``nb`` steps walk the chunk's table entries (aliased scatter write +
  prefix attention of one query tile against the page, masked by
  ``kv_pos < ctx_len[c]``), the last ``nk`` steps walk the chunk's own
  K/V in tiles of ``kv_tile`` rows (causal in-chunk attention, tiles
  above the diagonal skipped), all folding into one running
  (m, l, acc) scratch before the finalize.

VMEM is bounded independently of the chunk pad ``T_pad``: a step holds
one query tile of ``q_tile * G`` rows (about ``_Q_ROWS``), one in-chunk
K/V tile, one page and the two page-sized windows of chunk K/V that the
scatter draws from — never a ``(T_pad * G, T_pad)`` score block or a
whole ``(T_pad * G, D)`` query block.  With a query-tile axis every
page is visited once per tile, and every visit rewrites the page with
the same rows, so the pool ends the same whichever visit lands last.

Safety of the in-place page writes: distinct sequences own distinct
blocks (allocator invariant) and prefix-cache SHARED blocks are never
scatter targets (matches are block-granular and CoW covers the
full-match edge), so no grid step writes a page another chunk reads as
prefix; trash-table padding entries resolve to fully masked, unchanged
page copies.  The pure-jnp oracle is
``ref.ragged_chunked_prefill_ref`` (drop-mode packed scatter + the
gathered-view mask); the model's CPU fallback runs the same math
through ``layers.chunked_attention`` (models/transformer.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

META_SLOT, META_CTX, META_LEN, META_QOFF = 0, 1, 2, 3


# target query rows (tokens * group size) per tile and in-chunk K/V
# rows per step: a tile's f32 scores and accumulator stay well under a
# MiB each at any chunk pad
_Q_ROWS = 1024
_KV_TILE = 128


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _window(ki, ctx, block_size: int, num_windows: int):
    """Index of the first of the two page-sized windows of chunk K/V
    rows that cover page entry ``ki``: its rows sit at chunk offsets
    ``ki * bs - ctx .. + bs - 1``.  Shared by the index maps and the
    kernel body so both pick the same window."""
    j0 = jnp.maximum(ki * block_size - ctx, 0) // block_size
    return jnp.minimum(j0, num_windows - 1)


def _online_update(m_scr, l_scr, acc_scr, s, valid, v):
    """Fold one masked (rows, n) score block and its (n, D) values into
    the running (m, l, acc) scratch."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # re-mask after the shift (see paged_decode_attention: an all-masked
    # row would otherwise average garbage contents)
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _rcp_kernel(meta_ref, tables_ref, q_ref, kc_ref, vc_ref, kw0_ref,
                kw1_ref, vw0_ref, vw1_ref, k_ref, v_ref,
                o_ref, ok_ref, ov_ref, m_scr, l_scr, acc_scr, *,
                scale: float, block_size: int, groups: int, q_tile: int,
                kv_tile: int, num_pages: int, num_windows: int):
    c = pl.program_id(0)
    qi = pl.program_id(2)
    st = pl.program_id(3)
    ctx = meta_ref[c, META_CTX]
    clen = meta_ref[c, META_LEN]
    bs = block_size
    q_lo = qi * q_tile                           # first query row's token
    live_q = q_lo < clen                         # tile holds a real token

    @pl.when(st == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(st < num_pages)
    def _page():
        ki = st
        # ---- fused scatter: rewrite this page's rows that fall inside
        # the chunk's logical span with the chunk's fresh K/V, drawn
        # from the two windows at chunk offsets j0*bs .. j0*bs + 2bs - 1.
        # The one-hot matmuls are the TPU-friendly gather (each selected
        # row sums exactly one chunk row, so values are bit-identical
        # to a direct scatter).  Every query tile's visit writes the
        # same rows.
        j0 = _window(ki, ctx, bs, num_windows)
        local = (ki * bs
                 + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)[:, 0]
                 - ctx)                          # (bs,) chunk offsets
        sel = (local >= 0) & (local < clen)
        col = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
        off = local - j0 * bs
        oh0 = ((off[:, None] == col) & sel[:, None]).astype(jnp.float32)
        oh1 = ((off[:, None] == col + bs) & sel[:, None]).astype(jnp.float32)

        def gather(w0_ref, w1_ref):
            dims = (((1,), (0,)), ((), ()))
            return (jax.lax.dot_general(
                        oh0, w0_ref[0, 0].astype(jnp.float32), dims,
                        preferred_element_type=jnp.float32)
                    + jax.lax.dot_general(
                        oh1, w1_ref[0, 0].astype(jnp.float32), dims,
                        preferred_element_type=jnp.float32))

        ok_ref[0, 0] = jnp.where(sel[:, None],
                                 gather(kw0_ref, kw1_ref).astype(ok_ref.dtype),
                                 k_ref[0, 0])
        ov_ref[0, 0] = jnp.where(sel[:, None],
                                 gather(vw0_ref, vw1_ref).astype(ov_ref.dtype),
                                 v_ref[0, 0])

        # ---- prefix phase: attend the (pre-scatter) page, masked to
        # logical positions strictly below the chunk's first position
        # (so the rows this or an earlier visit scattered are never
        # read); pages wholly at or past ctx_len are skipped
        @pl.when(live_q & (ki * bs < ctx))
        def _prefix():
            q = q_ref[0, 0].astype(jnp.float32)  # (q_tile*G, D)
            k = k_ref[0, 0].astype(jnp.float32)  # (bs, D) page tables[c,ki]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            kv_pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            _online_update(m_scr, l_scr, acc_scr, s, kv_pos < ctx,
                           v_ref[0, 0].astype(jnp.float32))

    # ---- in-chunk phase: causal attention of the query tile against
    # the chunk's own K/V inputs (already page-dtype, so numerics match
    # the post-scatter page contents the per-chunk path would read);
    # tiles wholly above the diagonal or past chunk_len are skipped
    j = st - num_pages
    kv_lo = j * kv_tile

    @pl.when((st >= num_pages) & live_q & (kv_lo < q_lo + q_tile)
             & (kv_lo < clen))
    def _chunk():
        q = q_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kc_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        t_q = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // groups
        t_kv = kv_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_update(m_scr, l_scr, acc_scr, s,
                       (t_kv <= t_q) & (t_kv < clen),
                       vc_ref[0, 0].astype(jnp.float32))

    @pl.when(st == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def ragged_chunked_prefill(q, k_new, v_new, k_pages, v_pages, block_tables,
                           meta, *, interpret: bool = False):
    """q: (C, T_pad, H, D) per-chunk padded queries; k_new/v_new:
    (C, T_pad, KV, D) each chunk's fresh K/V (cast to the page dtype by
    the caller so in-chunk attention matches post-scatter numerics);
    pages: (N, bs, KV, D); block_tables: (C, nb) i32 physical page ids
    (pad with any valid id — typically the trash page); meta: (C, 4)
    i32 rows ``[slot, ctx_len, chunk_len, q_offset]``.

    Returns (out (C, T_pad, H, D), new_k_pages, new_v_pages): the
    attention output for rows ``0 .. chunk_len-1`` of each chunk (rows
    past ``chunk_len`` are undefined padding) and the page pools with
    every chunk's K/V scattered at logical positions
    ``ctx_len .. ctx_len + chunk_len - 1``.  A ``chunk_len == 0`` row
    is a padding chunk: it writes nothing and its output is undefined.
    """
    C, T, H, D = q.shape
    N, bs, KV, _ = k_pages.shape
    _, nb = block_tables.shape
    G = H // KV
    scale = 1.0 / (D ** 0.5)
    tq = math.gcd(T, _pow2_floor(max(1, _Q_ROWS // G)))
    tk = math.gcd(T, _KV_TILE)
    nq, nk = T // tq, T // tk
    nw = -(-T // bs)                             # page-sized windows

    # row layout t-major: row = t * G + g, so row // G recovers t and a
    # query tile of tq tokens is a contiguous block of tq * G rows
    qt = (q.reshape(C, T, KV, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(C, KV, T * G, D))
    knt = k_new.transpose(0, 2, 1, 3)            # (C, KV, T, D)
    vnt = v_new.transpose(0, 2, 1, 3)
    pad = ((0, 0), (0, 0), (0, nw * bs - T), (0, 0))
    knw, vnw = jnp.pad(knt, pad), jnp.pad(vnt, pad)  # scatter windows
    kt = k_pages.transpose(2, 0, 1, 3)           # (KV, N, bs, D)
    vt = v_pages.transpose(2, 0, 1, 3)
    tables = block_tables.astype(jnp.int32)
    meta = meta.astype(jnp.int32)

    def page(c, s, t):
        return t[c, jnp.minimum(s, nb - 1)]

    def window(c, s, m, second):
        j0 = _window(jnp.minimum(s, nb - 1), m[c, META_CTX], bs, nw)
        return jnp.minimum(j0 + 1, nw - 1) if second else j0

    def chunk_tile(i, s):
        # clamp skipped steps onto the last live tile: no refetch
        return jnp.minimum(jnp.maximum(s - nb, 0), (i * tq + tq - 1) // tk)

    q_spec = pl.BlockSpec((1, 1, tq * G, D),
                          lambda c, h, i, s, m, t: (c, h, i, 0))
    tile_spec = pl.BlockSpec((1, 1, tk, D),
                             lambda c, h, i, s, m, t: (c, h, chunk_tile(i, s),
                                                       0))
    win_specs = [pl.BlockSpec((1, 1, bs, D),
                              lambda c, h, i, s, m, t, w=w: (
                                  c, h, window(c, s, m, w), 0))
                 for w in (False, True)]
    # the indirection: page tables[c, s] streams into VMEM (and back
    # out, aliased, for the fused scatter)
    page_spec = pl.BlockSpec((1, 1, bs, D),
                             lambda c, h, i, s, m, t: (h, page(c, s, t), 0,
                                                       0))
    kernel = functools.partial(_rcp_kernel, scale=scale, block_size=bs,
                               groups=G, q_tile=tq, kv_tile=tk,
                               num_pages=nb, num_windows=nw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # meta, block_tables
        grid=(C, KV, nq, nb + nk),
        in_specs=[q_spec, tile_spec, tile_spec, *win_specs, *win_specs,
                  page_spec, page_spec],
        out_specs=[q_spec, page_spec, page_spec],
        scratch_shapes=[
            pltpu.VMEM((tq * G,), jnp.float32),
            pltpu.VMEM((tq * G,), jnp.float32),
            pltpu.VMEM((tq * G, D), jnp.float32),
        ],
    )
    out, new_kt, new_vt = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((C, KV, T * G, D), q.dtype),
            jax.ShapeDtypeStruct(kt.shape, kt.dtype),
            jax.ShapeDtypeStruct(vt.shape, vt.dtype),
        ],
        # operand indices include the scalar-prefetch args: meta=0,
        # tables=1, qt=2, knt=3, vnt=4, knw=5,6, vnw=7,8, kt=9, vt=10
        input_output_aliases={9: 1, 10: 2},
        interpret=interpret,
        name="ragged_prefill_kernel",  # the device op's name in a trace
    )(meta, tables, qt, knt, vnt, knw, knw, vnw, vnw, kt, vt)
    out = (out.reshape(C, KV, T, G, D).transpose(0, 2, 1, 3, 4)
           .reshape(C, T, H, D))
    return (out, new_kt.transpose(1, 2, 0, 3), new_vt.transpose(1, 2, 0, 3))
