"""Grouped matmul for a dropless expert layer: rows sorted by expert,
each group multiplied by its own expert's weight.

``lhs`` (M, K) holds the routed token copies sorted by expert, group
``e`` at rows ``offsets[e] .. offsets[e + 1] - 1``; ``rhs`` (E, K, N) the
experts' weights, or (layers, E, K, N) with a ``layer`` index: a layer
scan hands the kernel the whole stack and the index, since an operand
sliced out of the stack inside the scan would be copied whole each
layer.  Every row of every group is computed: there is no capacity and
no row is dropped.

  grid = (N // tn, E), experts innermost and sequential.  The weight
  block of expert ``e`` is its (K, tn) slice; an expert with no rows
  maps to the block of the last expert before it that has rows (or the
  first that has any), so consecutive steps name the same block and the
  pipeline fetches nothing for it: the weight bytes moved are those of
  the experts reached.  ``lhs`` and the (M, tn) output stay in VMEM
  across the experts.  Expert ``e`` multiplies the ``tm``-row tiles
  that cover its rows (the first starts at its first row rounded down
  to 16) and writes back only its own rows; rows of no group read zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_ALIGN = 16                # bf16 rows a sublane tile packs
VMEM_LIMIT = 64 * 2**20


def _gmm_kernel(offs_ref, fetch_ref, layer_ref, lhs_ref, rhs_ref, o_ref, *,
                tm: int):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    start = offs_ref[e]
    end = offs_ref[e + 1]

    @pl.when(end > start)
    def _group():
        first = (start // ROW_ALIGN) * ROW_ALIGN
        w = rhs_ref[0, 0]                                # (K, tn)

        def tile(i, carry):
            r0 = pl.multiple_of(first + i * tm, ROW_ALIGN)
            y = jnp.dot(lhs_ref[pl.ds(r0, tm), :], w,
                        preferred_element_type=jnp.float32)
            rows = r0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
            mine = (rows >= start) & (rows < end)
            cur = o_ref[pl.ds(r0, tm), :]
            o_ref[pl.ds(r0, tm), :] = jnp.where(mine, y.astype(cur.dtype),
                                                cur)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(end - first, tm), tile, 0)


def _tile_n(K: int, N: int, itemsize: int) -> int:
    """The widest 128-multiple divisor of N whose (K, tn) block stays
    under 8 MiB (the whole N where it fits)."""
    if K * N * itemsize <= 8 * 2**20 or N % 128:
        return N
    tn = N
    while tn % 256 == 0 and K * tn * itemsize > 8 * 2**20:
        tn //= 2
    return tn


def group_fetch(group_sizes):
    """(offsets (E + 1,), fetch (E,)): row offsets of the groups, and
    for each expert the expert whose weight block its grid step names."""
    E = group_sizes.shape[0]
    gs = group_sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(gs)])
    idx = jnp.arange(E, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(gs > 0, idx, -1))
    first = jnp.argmax(gs > 0).astype(jnp.int32)
    return offs, jnp.where(last >= 0, last, first)


def gmm(lhs, rhs, group_sizes, *, layer=None, tm: int = 32,
        interpret: bool = False):
    """lhs: (M, K); rhs: (E, K, N), or (layers, E, K, N) and ``layer`` an
    i32 scalar; group_sizes: (E,) i32 with ``sum <= M``.  Returns (M, N)
    in lhs.dtype: row ``r`` of group ``e`` is ``lhs[r] @ rhs[layer, e]``
    (float32 accumulation); rows past the last group are zero."""
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    M, K = lhs.shape
    _, E, _, N = rhs.shape
    Mp = -(-M // ROW_ALIGN) * ROW_ALIGN + tm
    lhs_p = jnp.pad(lhs, ((0, Mp - M), (0, 0)))
    offs, fetch = group_fetch(group_sizes)
    tn = _tile_n(K, N, rhs.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                   # offsets, fetch, layer
        grid=(N // tn, E),
        in_specs=[
            pl.BlockSpec((Mp, K), lambda n, e, o, f, l: (0, 0)),
            pl.BlockSpec((1, 1, K, tn),
                         lambda n, e, o, f, l: (l[0], f[e], 0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, tn), lambda n, e, o, f, l: (0, n)),
    )
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_kernel",         # the device op's name in a trace
    )(offs, fetch, jnp.asarray(layer, jnp.int32).reshape(1), lhs_p, rhs)
    return out[:M]
