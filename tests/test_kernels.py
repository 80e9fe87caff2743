"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret
mode (the kernel bodies execute in Python on CPU; on TPU the same bodies
compile via Mosaic)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (chunked_prefill_attention as cpa,
                           decode_attention as fd, flash_attention as fa,
                           paged_decode_attention as pfd,
                           ragged_chunked_prefill as rcp, ref,
                           rmsnorm as rn)

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,H,KV,D,causal,window", [
    (1, 64, 4, 2, 32, True, None),
    (2, 48, 4, 1, 16, True, None),     # MQA + padding (48 % 32 != 0)
    (1, 96, 8, 8, 64, True, 24),       # MHA sliding window
    (1, 32, 2, 2, 128, False, None),   # bidirectional (encoder)
])
def test_flash_attention_sweep(B, S, H, KV, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32).astype(dtype)
    out = fa.flash_attention(q, k, v, causal=causal, window=window,
                             block_q=32, block_k=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    assert out.dtype == dtype
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,S,H,KV,D,block_k", [
    (2, 128, 4, 2, 32, 32),
    (1, 100, 8, 1, 64, 64),     # padding (100 % 64)
    (3, 64, 4, 4, 16, 16),
    (1, 512, 8, 2, 128, 128),   # long cache
])
def test_flash_decode_sweep(B, S, H, KV, D, block_k, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    kc = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32).astype(dtype)
    mask = jax.random.bernoulli(ks[3], 0.8, (B, S)).at[:, 0].set(True)
    out = fd.flash_decode_attention(q, kc, vc, mask, block_k=block_k,
                                    interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, mask=mask)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


def _paged_case(B, N, nb, block_size, layout, rng):
    """Block tables and lengths for one sweep case.  ``random``:
    permuted tables, lengths in [1, nb * block_size].  The kernel walks
    a row's live pages in chunks of ``P = min(128 // block_size, nb)``,
    so the others aim at its edges: lengths on page and chunk
    boundaries, lengths past the table (clamped to ``nb * block_size``),
    two rows sharing their prefix pages, and a row whose table is one
    page throughout with length 0 (a dead slot: returns zeros)."""
    tables = np.stack([rng.permutation(N)[:nb]
                       for _ in range(B)]).astype(np.int32)
    lens = rng.integers(1, nb * block_size + 1, (B,))
    P = min(128 // block_size, nb)
    if layout == "boundary":
        lens = np.array([block_size, P * block_size, nb * block_size])[:B]
    elif layout == "clamped":
        lens = nb * block_size + np.array([7, 100])[:B]
    elif layout == "shared":
        tables[1, :2] = tables[0, :2]
        lens[:2] = [2 * block_size + 3, 2 * block_size]
    elif layout == "empty":
        tables[0] = N - 1
        lens[0] = 0
    return jnp.asarray(tables), jnp.asarray(lens.astype(np.int32))


def _stack_at(pages, layer, depth=3):
    """``pages`` as layer ``layer`` of a ``depth``-layer stack whose
    other layers hold NaN: a read of the wrong layer poisons the
    output."""
    stack = jnp.full((depth,) + pages.shape, jnp.nan, pages.dtype)
    return stack.at[layer].set(pages)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,H,KV,D,block_size,nb,layout,layer", [
    pytest.param(2, 4, 2, 32, 16, 4, "random", None,
                 id="2-4-2-32-16-4"),        # GQA, 4-entry tables
    pytest.param(1, 8, 1, 64, 32, 3, "random", None,
                 id="1-8-1-64-32-3"),        # MQA
    pytest.param(3, 4, 4, 16, 64, 2, "random", None,
                 id="3-4-4-16-64-2"),        # MHA, big pages
    pytest.param(2, 8, 2, 128, 16, 5, "random", None,
                 id="2-8-2-128-16-5"),       # long table, wide heads
    # StarCoder2-3B's heads and the cell's 24-entry tables
    pytest.param(3, 24, 2, 128, 16, 24, "random", None, id="sc2-heads"),
    pytest.param(2, 4, 2, 32, 16, 11, "random", None,
                 id="nb-not-multiple-of-P"),  # chunks of 8 and 3
    pytest.param(3, 8, 2, 64, 16, 10, "boundary", None,
                 id="page-boundaries"),
    pytest.param(2, 8, 2, 64, 16, 11, "clamped", None, id="len-past-table"),
    pytest.param(2, 8, 2, 64, 16, 6, "shared", None, id="shared-prefix-page"),
    pytest.param(2, 8, 2, 64, 16, 6, "empty", None, id="dead-row"),
    # layer 1 of a 3-layer stack, the others NaN (the decode scan's pools)
    pytest.param(3, 24, 2, 128, 16, 24, "random", 1, id="sc2-heads-layer1"),
    pytest.param(3, 8, 2, 64, 16, 10, "boundary", 1,
                 id="page-boundaries-layer1"),
    pytest.param(2, 8, 2, 64, 16, 6, "empty", 1, id="dead-row-layer1"),
])
def test_paged_decode_sweep(B, H, KV, D, block_size, nb, layout, layer,
                            dtype):
    """Paged flash-decode vs the block-table gather oracle across block
    sizes and RAGGED per-sequence lengths (tables deliberately permuted
    so physical order != logical order).  ``layer``: the pools are that
    layer of a stack whose other layers hold NaN; ``None``: an
    unstacked pool, passed as a one-layer stack at layer 0."""
    N = B * nb + 3               # spare pages: stale/garbage content
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (N, block_size, KV, D),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (N, block_size, KV, D),
                           jnp.float32).astype(dtype)
    rng = np.random.default_rng(B * 131 + block_size)
    tables, lens = _paged_case(B, N, nb, block_size, layout, rng)
    if layer is None:
        kstack, vstack, at = kp[None], vp[None], 0
    else:
        kstack, vstack, at = _stack_at(kp, layer), _stack_at(vp, layer), layer
    out = pfd.paged_flash_decode_attention(q, kstack, vstack, tables, lens,
                                           jnp.int32(at), interpret=True)
    assert out.shape == (B, H, D) and out.dtype == dtype
    live = np.asarray(lens) > 0
    # the oracle is NaN on a length-0 row; the kernel returns zeros
    want = ref.paged_decode_attention_ref(q, kp, vp, tables,
                                          jnp.maximum(lens, 1))
    np.testing.assert_array_equal(
        np.asarray(out.astype(jnp.float32))[~live], 0.0)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32))[live],
                               np.asarray(want.astype(jnp.float32))[live],
                               **_tol(dtype))


def test_paged_decode_matches_contiguous_decode():
    """Triangle closure: a paged cache holding the same logical KV as a
    contiguous cache gives the same attention output (paged ref vs the
    contiguous decode oracle)."""
    B, H, KV, D, bs, nb = 2, 4, 2, 32, 16, 4
    S = nb * bs
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kc = jax.random.normal(ks[1], (B, S, KV, D))
    vc = jax.random.normal(ks[2], (B, S, KV, D))
    lens = jnp.asarray([S - 7, 9], jnp.int32)
    # lay the contiguous caches out into per-sequence pages
    kp = kc.reshape(B * nb, bs, KV, D)
    vp = vc.reshape(B * nb, bs, KV, D)
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    mask = jnp.arange(S)[None, :] < lens[:, None]
    want = ref.decode_attention_ref(q, kc, vc, mask=mask)
    got = ref.paged_decode_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    got_kernel = pfd.paged_flash_decode_attention(q, kp[None], vp[None],
                                                  tables, lens, 0,
                                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_empty_row_returns_zeros():
    """A seq_len == 0 row (nothing valid to attend to) must yield zeros,
    not an average of garbage page contents; other rows are unaffected."""
    B, H, KV, D, bs, nb = 2, 4, 2, 32, 16, 3
    N = B * nb
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (N, bs, KV, D))
    vp = jax.random.normal(ks[2], (N, bs, KV, D))
    tables = jnp.arange(N, dtype=jnp.int32).reshape(B, nb)
    lens = jnp.asarray([0, 11], jnp.int32)
    out = pfd.paged_flash_decode_attention(q, kp[None], vp[None], tables,
                                           lens, 0, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.zeros((H, D), np.float32))
    want = ref.paged_decode_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want[1]),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("T,B,H,KV,D,block_size,nb", [
    (16, 2, 4, 2, 32, 16, 4),    # GQA, smallest chunk
    (16, 1, 8, 2, 128, 64, 2),   # wide heads, big pages
    (64, 1, 8, 1, 64, 32, 4),    # MQA, mid chunk
    (128, 2, 4, 4, 16, 16, 12),  # MHA, acceptance chunk sweep top end
])
def test_chunked_prefill_sweep(T, B, H, KV, D, block_size, nb, dtype):
    """Chunked-prefill kernel vs the block-table gather oracle across
    chunk sizes {16, 64, 128} and RAGGED prior-context lengths,
    including the zero-prior-context (first chunk) edge; tables are
    permuted so physical order != logical order."""
    N = B * nb + 3               # spare pages: stale/garbage content
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (N, block_size, KV, D),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (N, block_size, KV, D),
                           jnp.float32).astype(dtype)
    rng = np.random.default_rng(T * 7 + B * 131 + block_size)
    tables = jnp.asarray(np.stack(
        [rng.permutation(N)[:nb] for _ in range(B)]).astype(np.int32))
    # row 0 is always the first-chunk edge (zero prior context); others
    # ragged in [0, nb*bs - T]
    maxc = nb * block_size - T
    clens = jnp.asarray(
        [0] + [int(rng.integers(0, maxc + 1)) for _ in range(B - 1)],
        jnp.int32)
    out = cpa.chunked_prefill_attention(q, kp, vp, tables, clens,
                                        interpret=True)
    want = ref.chunked_prefill_attention_ref(q, kp, vp, tables, clens)
    assert out.shape == (B, T, H, D) and out.dtype == dtype
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


def test_chunked_prefill_matches_full_causal():
    """Triangle closure: when the pages hold a full sequence and the
    chunk is its tail, chunked-prefill attention equals rows
    [ctx:ctx+T] of ordinary causal attention over the sequence."""
    B, H, KV, D, bs, nb, T = 1, 4, 2, 32, 16, 4, 16
    S = nb * bs
    ctx = S - T
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q_full = jax.random.normal(ks[0], (B, S, H, D))
    kc = jax.random.normal(ks[1], (B, S, KV, D))
    vc = jax.random.normal(ks[2], (B, S, KV, D))
    want = ref.attention_ref(q_full, kc, vc, causal=True)[:, ctx:]
    kp = kc.reshape(B * nb, bs, KV, D)
    vp = vc.reshape(B * nb, bs, KV, D)
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    clens = jnp.asarray([ctx], jnp.int32)
    got = ref.chunked_prefill_attention_ref(q_full[:, ctx:], kp, vp,
                                            tables, clens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    got_kernel = cpa.chunked_prefill_attention(q_full[:, ctx:], kp, vp,
                                               tables, clens,
                                               interpret=True)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _ragged_case(lens, ctxs, *, H=4, KV=2, D=32, bs=16, seed=0,
                 dtype=jnp.float32):
    """Build a fused ragged-prefill case: C chunks with the given
    lengths and prior-context lengths, each owning its own permuted
    block table (plus spare garbage pages), queries padded to the
    power-of-two chunk bucket like the engine's packed layout."""
    C = len(lens)
    Tp = 1
    while Tp < max(lens):
        Tp *= 2
    nb = max(-(-(c + l) // bs) for c, l in zip(ctxs, lens)) + 1
    N = C * nb + 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (C, Tp, H, D), jnp.float32).astype(dtype)
    kn = jax.random.normal(ks[1], (C, Tp, KV, D), jnp.float32).astype(dtype)
    vn = jax.random.normal(ks[2], (C, Tp, KV, D), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[3], (N, bs, KV, D), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[4], (N, bs, KV, D), jnp.float32).astype(dtype)
    rng = np.random.default_rng(seed * 7 + C)
    perm = rng.permutation(N)
    tables = jnp.asarray(perm[:C * nb].reshape(C, nb).astype(np.int32))
    off, meta = 0, []
    for c, (ln, ctx) in enumerate(zip(lens, ctxs)):
        meta.append([c, ctx, ln, off])
        off += ln
    return q, kn, vn, kp, vp, tables, jnp.asarray(meta, jnp.int32)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("lens,ctxs", [
    ([1, 1, 1], [0, 5, 31]),          # single-token chunks
    ([10, 24], [13, 7]),              # chunks crossing page boundaries
    ([16, 8, 4], [0, 0, 0]),          # zero prior context everywhere
    ([32], [9]),                      # one-request degenerate batch
    ([16, 64, 128, 64, 16], [3, 0, 40, 16, 128]),  # mixed {16,64,128}
])
def test_ragged_chunked_prefill_sweep(lens, ctxs, dtype):
    """Fused ragged kernel vs the jnp oracle: attention output on every
    VALID row (rows past chunk_len are undefined padding) and the page
    pools — the in-kernel scatter must match the oracle's drop-mode
    packed scatter bit for bit."""
    q, kn, vn, kp, vp, tables, meta = _ragged_case(lens, ctxs, dtype=dtype)
    out, nk, nv = rcp.ragged_chunked_prefill(q, kn, vn, kp, vp, tables,
                                             meta, interpret=True)
    want, wk, wv = ref.ragged_chunked_prefill_ref(q, kn, vn, kp, vp,
                                                  tables, meta)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(wv))
    assert out.shape == q.shape and out.dtype == dtype
    for c, ln in enumerate(lens):
        np.testing.assert_allclose(
            np.asarray(out[c, :ln]).astype(np.float32),
            np.asarray(want[c, :ln]).astype(np.float32), **_tol(dtype))


@pytest.mark.parametrize("q_rows,kv_tile", [(32, 16), (64, 128), (256, 32)])
def test_ragged_chunked_prefill_tiled(monkeypatch, q_rows, kv_tile):
    """Query and in-chunk K/V tiles smaller than the chunk pad, as at the
    engine's pads on the chip: every page is visited once per query tile
    and in-chunk tiles above the diagonal are skipped, yet output and
    pools still match the oracle."""
    monkeypatch.setattr(rcp, "_Q_ROWS", q_rows)
    monkeypatch.setattr(rcp, "_KV_TILE", kv_tile)
    lens, ctxs = [16, 64, 128, 64, 16], [3, 0, 40, 16, 128]
    q, kn, vn, kp, vp, tables, meta = _ragged_case(lens, ctxs, seed=5)
    out, nk, nv = rcp.ragged_chunked_prefill(q, kn, vn, kp, vp, tables,
                                             meta, interpret=True)
    want, wk, wv = ref.ragged_chunked_prefill_ref(q, kn, vn, kp, vp,
                                                  tables, meta)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(wv))
    for c, ln in enumerate(lens):
        np.testing.assert_allclose(np.asarray(out[c, :ln]),
                                   np.asarray(want[c, :ln]),
                                   **_tol(jnp.float32))


def test_ragged_matches_per_chunk_kernel():
    """Triangle closure: one fused launch over C chunks equals C
    separate ``chunked_prefill_attention`` launches run after a
    separate scatter pass (same pages, same masks)."""
    lens, ctxs = [16, 64, 128], [5, 0, 30]
    q, kn, vn, kp, vp, tables, meta = _ragged_case(lens, ctxs, seed=3)
    out, nk, nv = rcp.ragged_chunked_prefill(q, kn, vn, kp, vp, tables,
                                             meta, interpret=True)
    # per-chunk reference: scatter each chunk, then run the per-chunk
    # kernel against the post-scatter pages
    _, sk, sv = ref.ragged_chunked_prefill_ref(q, kn, vn, kp, vp,
                                               tables, meta)
    for c, ln in enumerate(lens):
        got_c = cpa.chunked_prefill_attention(
            q[c:c + 1, :ln], sk, sv, tables[c:c + 1],
            meta[c:c + 1, 1], interpret=True)
        np.testing.assert_allclose(np.asarray(out[c, :ln]),
                                   np.asarray(got_c[0]),
                                   atol=2e-5, rtol=2e-5)


def test_ragged_padding_chunk_writes_nothing():
    """A padding chunk (chunk_len == 0, trash-only table — the engine's
    contract: a scattered page is never revisited by another chunk)
    must leave every page bit-identical and not disturb its batch
    siblings."""
    lens, ctxs = [8, 4], [0, 16]
    q, kn, vn, kp, vp, tables, meta = _ragged_case(lens, ctxs, seed=5)
    # append a padding chunk whose table points only at a spare (trash)
    # page no real chunk owns, exactly as the engine builds it
    meta_pad = jnp.concatenate(
        [meta, jnp.asarray([[2, 0, 0, 12]], jnp.int32)])
    # _ragged_case keeps 3 spare pages; pick one no chunk's table uses
    spare = (set(range(kp.shape[0])) - set(np.asarray(tables).ravel()
                                           .tolist())).pop()
    tables_pad = jnp.concatenate(
        [tables, jnp.full_like(tables[:1], spare)])
    q3 = jnp.concatenate([q, q[:1]])
    kn3 = jnp.concatenate([kn, kn[:1]])
    vn3 = jnp.concatenate([vn, vn[:1]])
    out3, nk3, nv3 = rcp.ragged_chunked_prefill(
        q3, kn3, vn3, kp, vp, tables_pad, meta_pad, interpret=True)
    out, nk, nv = rcp.ragged_chunked_prefill(q, kn, vn, kp, vp, tables,
                                             meta, interpret=True)
    np.testing.assert_array_equal(np.asarray(nk3), np.asarray(nk))
    np.testing.assert_array_equal(np.asarray(nv3), np.asarray(nv))
    for c, ln in enumerate(lens):
        np.testing.assert_array_equal(np.asarray(out3[c, :ln]),
                                      np.asarray(out[c, :ln]))


def test_ops_ragged_wrapper_dispatch():
    """ops.ragged_chunked_prefill: kernel (interpret) vs oracle path."""
    from repro.kernels import ops
    lens, ctxs = [4, 16], [0, 9]
    q, kn, vn, kp, vp, tables, meta = _ragged_case(lens, ctxs, seed=11)
    a_out, a_k, a_v = ops.ragged_chunked_prefill(
        q, kn, vn, kp, vp, tables, meta, use_pallas=True, interpret=True)
    b_out, b_k, b_v = ops.ragged_chunked_prefill(
        q, kn, vn, kp, vp, tables, meta, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(b_k))
    np.testing.assert_array_equal(np.asarray(a_v), np.asarray(b_v))
    for c, ln in enumerate(lens):
        np.testing.assert_allclose(np.asarray(a_out[c, :ln]),
                                   np.asarray(b_out[c, :ln]),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape,block_rows", [
    ((8, 128), 4), ((3, 5, 256), 8), ((17, 64), 8), ((1, 1024), 1),
])
def test_rmsnorm_sweep(shape, block_rows, dtype):
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, shape, jnp.float32).astype(dtype)
    w = (jax.random.normal(key, shape[-1:], jnp.float32) * 0.2).astype(dtype)
    out = rn.rms_norm(x, w, block_rows=block_rows, interpret=True)
    want = ref.rms_norm_ref(x, w)
    assert out.shape == x.shape and out.dtype == dtype
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


def test_ops_wrappers_dispatch():
    """use_pallas=False falls back to the layers implementations."""
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    a = ops.flash_attention(q, k, v, use_pallas=True, interpret=True,
                            block_q=16, block_k=16)
    b = ops.flash_attention(q, k, v, use_pallas=False)
    np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
    x = jax.random.normal(ks[0], (4, 64))
    w = jnp.zeros(64)
    np.testing.assert_allclose(
        ops.rms_norm(x, w, use_pallas=True, interpret=True),
        ops.rms_norm(x, w, use_pallas=False), atol=1e-5, rtol=1e-5)
    qd = jax.random.normal(ks[0], (2, 4, 16))
    kp = jax.random.normal(ks[1], (6, 8, 2, 16))
    vp = jax.random.normal(ks[2], (6, 8, 2, 16))
    tables = jnp.asarray([[0, 2, 4], [1, 3, 5]], jnp.int32)
    lens = jnp.asarray([17, 9], jnp.int32)
    np.testing.assert_allclose(
        ops.paged_decode_attention(qd, kp, vp, tables, lens,
                                   use_pallas=True, interpret=True),
        ops.paged_decode_attention(qd, kp, vp, tables, lens,
                                   use_pallas=False),
        atol=1e-4, rtol=1e-4)
    qc = jax.random.normal(ks[0], (2, 8, 4, 16))
    clens = jnp.asarray([0, 9], jnp.int32)
    np.testing.assert_allclose(
        ops.chunked_prefill_attention(qc, kp, vp, tables, clens,
                                      use_pallas=True, interpret=True),
        ops.chunked_prefill_attention(qc, kp, vp, tables, clens,
                                      use_pallas=False),
        atol=1e-4, rtol=1e-4)
