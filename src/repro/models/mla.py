"""Multi-head latent attention (MLA, DeepSeek-V2 [arXiv:2405.04434]).

Each token caches ONE latent of ``kv_lora_rank + qk_rope_head_dim``
values a layer, shared by every head: the RMS-normed compressed KV ``c``
(``kv_lora_rank``) and the rope key ``k_pe`` (``qk_rope_head_dim``),
stored zero-padded to ``L = cfg.latent_lanes`` (whole 128-lane tiles).
The published (expanded) form computes per head

    k = [c W_kb, k_pe],  v = c W_vb,  o = softmax(q k^T * scale) v.

Every path here computes the ABSORBED form instead:
``q_nope W_kb^T`` gives each head a query over the latent, so the scores
are one dot over the L cached lanes, ``[q_nope W_kb^T, q_pe] . [c,
k_pe]``, and the values are the latent's first ``kv_lora_rank`` entries,
with ``W_vb`` applied after attention.  Decode and prefill then read the
same latent pages and never expand them: a prefix hit reuses cached
latents as they are.

``project`` gives the absorbed queries already multiplied by ``scale *
sqrt(L)`` so that the attention helpers, which divide by ``sqrt(L)``
(the query width), compute ``scale`` exactly; the Pallas kernels take
the same queries.  ``scale`` is ``(qk_nope + qk_rope)^-0.5`` times yarn's
``mscale_all_dim`` factor squared.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import mla_decode as mla_decode_kernel
from repro.kernels import mla_prefill as mla_prefill_kernel
from repro.kernels import ops as kernel_ops
from repro.kvcache import paged as paged_lib

from . import layers
from .layers import rms_norm

Array = jax.Array


def init_mla(key: Array, cfg, dtype) -> dict:
    D, H, R = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    N, P, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "q_proj": layers.dense_init(ks[0], (D, H, N + P), dtype),
        "kv_a_proj": layers.dense_init(ks[1], (D, R + P), dtype),
        "kv_a_norm": jnp.zeros((R,), dtype),
        "kv_b_k": layers.dense_init(ks[2], (R, H, N), dtype),
        "kv_b_v": layers.dense_init(ks[3], (R, H, V), dtype),
        "o_proj": layers.dense_init(ks[4], (H, V, D), dtype),
    }


def softmax_scale(cfg) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_factor and cfg.rope_mscale_all_dim:
        m = layers.yarn_get_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
        s *= m * m
    return s


def project(p: dict, h: Array, positions: Array, cfg):
    """h: (B, S, D) normed input; positions broadcastable to (B, S).
    Returns (q (B, S, H, L) absorbed and pre-scaled, latent (B, S, L)),
    both zero past ``cfg.latent_dim``."""
    R, N = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    L = cfg.latent_lanes
    pad = [(0, 0)] * (h.ndim - 1) + [(0, L - cfg.latent_dim)]
    freqs, ms = layers.rope_for(cfg, cfg.qk_rope_head_dim)
    q = jnp.einsum("bsd,dhk->bshk", h, p["q_proj"])
    q_pe = layers.apply_rope(q[..., N:], positions, freqs=freqs, mscale=ms)
    kv = jnp.einsum("bsd,dl->bsl", h, p["kv_a_proj"])
    c = rms_norm(kv[..., :R], p["kv_a_norm"], cfg.norm_eps)
    k_pe = layers.apply_rope(kv[..., None, R:], positions, freqs=freqs,
                             mscale=ms)[..., 0, :]
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :N], p["kv_b_k"],
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], axis=-1)
    q = jnp.pad(q * (softmax_scale(cfg) * math.sqrt(L)), pad[:1] + pad)
    lat = jnp.pad(jnp.concatenate([c, k_pe], -1), pad)
    return q.astype(h.dtype), lat.astype(h.dtype)


def attn_out(p: dict, o_lat: Array) -> Array:
    """(B, S, H, kv_lora_rank) attention over latents -> (B, S, D)."""
    o = jnp.einsum("bshr,rhv->bshv", o_lat, p["kv_b_v"])
    return jnp.einsum("bshv,hvd->bsd", o, p["o_proj"])


def _values(lat: Array, rank: int) -> Array:
    """The latents as attention values: the rope key's lanes zeroed, so
    the helpers' (query-width) output carries ``o_lat`` in its first
    ``rank`` lanes."""
    keep = jnp.arange(lat.shape[-1]) < rank
    return jnp.where(keep, lat, jnp.zeros((), lat.dtype))


# ---------------------------------------------------------------------------
# attention over latents: full sequence, contiguous ring, paged pool
# ---------------------------------------------------------------------------


def attend_seq(p: dict, h: Array, positions: Array, cfg):
    """Causal attention of a whole sequence.  Returns (out (B, S, D),
    latent (B, S, L))."""
    R = cfg.kv_lora_rank
    q, lat = project(p, h, positions, cfg)
    o = layers.chunked_attention(
        q, lat[:, :, None], _values(lat, R)[:, :, None],
        q_positions=positions, kv_positions=positions, causal=True)
    return attn_out(p, o[..., :R]), lat


def attend_ring(p: dict, q: Array, cache: Array, pos, slot_pos, cfg):
    """One token's queries (``project``) against a contiguous latent ring
    ``cache`` (B, W, L), written already; pos/slot_pos as in
    ``transformer._attn_decode``."""
    R = cfg.kv_lora_rank
    W = cache.shape[1]
    o = layers.decode_attention(
        q, cache[:, :, None], _values(cache, R)[:, :, None],
        q_position=pos, kv_positions=slot_pos,
        valid_len=jnp.minimum(pos + 1, W))
    return attn_out(p, o[..., :R])


def decode_paged(p: dict, h: Array, pool: Array, pos: Array, tables: Array,
                 cfg, use_pallas: bool = False):
    """One token a row against the latent page pool (N, bs, L).
    h: (B, 1, D) normed; pos (B,); tables (B, nb).  The new latent is
    written first.  Returns (out (B, 1, D), new pool)."""
    R = cfg.kv_lora_rank
    q, lat = project(p, h, pos[:, None], cfg)
    pool = paged_lib.scatter_token(pool, lat[:, 0], tables, pos)
    if use_pallas:
        # a table that starts at the trash page (the pool's last) is a
        # dead row: the kernel walks none of its pages
        dead = tables[:, 0] == pool.shape[0] - 1
        o = mla_decode_kernel.mla_decode_attention(
            q[:, 0], pool, tables, jnp.where(dead, 0, pos + 1), rank=R,
            interpret=kernel_ops._default_interpret())[:, None]
    else:
        seq = paged_lib.gather_tokens(pool, tables)      # (B, nb*bs, L)
        n = seq.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32),
                                  (h.shape[0], n))
        o = layers.decode_attention(
            q, seq[:, :, None], _values(seq, R)[:, :, None],
            q_position=pos, kv_positions=kv_pos, valid_len=pos + 1)[..., :R]
    return attn_out(p, o), pool


def chunks_paged(p: dict, h: Array, pool: Array, ctx: dict, cfg):
    """Every scheduled prefill chunk of one launch, packed (batch dim 1),
    against the latent page pool; ``ctx`` as in
    ``transformer._attn_chunks_paged``.  The chunks' latents are written
    first, then each chunk attends over its table: full over the prefix,
    causal within the chunk.  Returns (out (1, TT, D), new pool)."""
    R = cfg.kv_lora_rank
    positions, token_chunk = ctx["positions"], ctx["token_chunk"]
    meta, tables, Tp = ctx["meta"], ctx["table_rows"], ctx["chunk_pad"]
    C, TT = meta.shape[0], h.shape[1]
    q, lat = project(p, h, positions[None, :], cfg)
    pool = paged_lib.scatter_packed(pool, lat[0], tables, token_chunk,
                                    positions, ctx["valid"])
    qidx = jnp.clip(meta[:, 3][:, None]
                    + jnp.arange(Tp, dtype=jnp.int32)[None, :], 0, TT - 1)
    qv = jnp.take(q[0], qidx.reshape(-1), axis=0).reshape(
        (C, Tp) + q.shape[2:])                           # (C, Tp, H, L)
    if ctx.get("use_pallas", False):
        av = mla_prefill_kernel.mla_prefill_attention(
            qv, pool, tables, meta, rank=R,
            interpret=kernel_ops._default_interpret())
    else:
        seq = paged_lib.gather_tokens(pool, tables)      # (C, nb*bs, L)
        vals = _values(seq, R)
        n = seq.shape[1]
        outs = []
        for c in range(C):                               # static: C is a shape
            outs.append(layers.chunked_attention(
                qv[c:c + 1], seq[c:c + 1, :, None], vals[c:c + 1, :, None],
                q_positions=meta[c, 1] + jnp.arange(Tp, dtype=jnp.int32),
                kv_positions=jnp.arange(n, dtype=jnp.int32),
                causal=True)[0, ..., :R])
        av = jnp.stack(outs)                             # (C, Tp, H, R)
    o = av[token_chunk, jnp.clip(ctx["local"], 0, Tp - 1)][None]
    return attn_out(p, o), pool
