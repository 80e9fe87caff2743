"""Core neural building blocks, pure JAX.

Everything here is functional: params are pytrees of jnp arrays created by
``init_*`` helpers and consumed by the matching ``apply`` functions.  The
attention implementation is the *chunked* (flash-style, O(S*chunk) memory)
pure-jnp reference; the Pallas TPU kernels in ``repro.kernels`` implement the
same contract and are validated against ``repro.kernels.ref`` oracles.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.sharding import context as shctx

Array = jax.Array

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key: Array, shape, dtype, scale: float = 0.02) -> Array:
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rms_norm(x: Array, weight: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def gated_rms_norm(x: Array, z: Array, weight: Array, eps: float = 1e-6) -> Array:
    """Mamba-2 style norm: RMSNorm(x * silu(z))."""
    return rms_norm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                    weight, eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    """Yarn's attention-temperature factor (DeepSeek-V2's formula)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


YARN_BETA_FAST, YARN_BETA_SLOW = 32, 1


def yarn_freqs(dim: int, theta: float, factor: float,
               original_max: int) -> np.ndarray:
    """Yarn rope frequencies (arXiv:2309.00071, as DeepSeek-V2 computes
    them): dimensions that rotate more than 32 times (beta_fast) over
    the original context keep their frequency, those under once
    (beta_slow) are interpolated by ``factor``, and a linear ramp blends
    between."""
    def corr_dim(rot):
        return (dim * math.log(original_max / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr_dim(YARN_BETA_FAST)), 0)
    hi = min(math.ceil(corr_dim(YARN_BETA_SLOW)), dim - 1)
    if lo == hi:
        hi += 0.001
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - lo) / (hi - lo),
                   0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_for(cfg, dim: int):
    """(frequencies, cos/sin scale) of a config's rope over ``dim``
    dims: plain, or yarn where ``cfg.rope_factor`` is set."""
    if not cfg.rope_factor:
        return rope_freqs(dim, cfg.rope_theta), 1.0
    f = cfg.rope_factor
    return (jnp.asarray(yarn_freqs(dim, cfg.rope_theta, f,
                                   cfg.rope_original_max_pos)),
            yarn_get_mscale(f, cfg.rope_mscale)
            / yarn_get_mscale(f, cfg.rope_mscale_all_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10_000.0, *,
               freqs: Optional[Array] = None, mscale: float = 1.0) -> Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S).
    ``freqs`` (D/2,) replaces the plain frequencies of ``theta`` (yarn:
    ``rope_for``), and ``mscale`` scales cos and sin."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)                   # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                      # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — chunked flash-style reference (pure jnp)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def expand_kv(k: Array, num_heads: int) -> Array:
    """(B, S, KV, D) -> (B, S, H, D) by repeating each KV head G times.

    The H-expanded formulation keeps every attention einsum sharded purely
    on the H axis (logical "heads"), avoiding (KV, G) reshapes of a sharded
    dimension that GSPMD would have to re-layout with collectives.
    """
    KV = k.shape[2]
    if KV == num_heads:
        return k
    return jnp.repeat(k, num_heads // KV, axis=2)


def _attend_chunk(q, k, v, qpos, kpos, scale, causal, window):
    """One (q-chunk, kv-chunk) tile.  q: (B, Sq, H, D); k/v: (B, Sk, H, D)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones(s.shape[-2:], dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask, s, NEG_INF)
    return s


def chunked_attention(q: Array, k: Array, v: Array, *,
                      q_positions: Array, kv_positions: Array,
                      causal: bool = True, window: Optional[int] = None,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      kv_valid_len: Optional[Array] = None) -> Array:
    """Flash-style attention with O(chunk) score memory.

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D); GQA via H = KV * G.
    q_positions: (Sq,) absolute positions; kv_positions: (Sk,).
    kv_valid_len: optional scalar — keys at kv index >= valid_len are masked
      (ring-buffer caches).
    Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    scale = 1.0 / (D ** 0.5)
    k = shctx.constrain(expand_kv(k, H), ("batch", None, "heads", None))
    v = shctx.constrain(expand_kv(v, H), ("batch", None, "heads", None))

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    # pad to chunk multiples
    pq, pk = nq * q_chunk - Sq, nk * kv_chunk - Sk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pq), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pk),
                               constant_values=2**30)

    qs = q.reshape(B, nq, q_chunk, H, D).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(nq, q_chunk)
    ks = k.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 2, 3, 4)
    kp = kv_positions.reshape(nk, kv_chunk)
    kidx = jnp.arange(nk * kv_chunk).reshape(nk, kv_chunk)

    def q_body(_, qc):
        qi, qpi = qc

        def kv_body(carry, kc):
            m, l, acc = carry
            ki, vi, kpi, kii = kc
            s = _attend_chunk(qi, ki, vi, qpi, kpi, scale, causal, window)
            if kv_valid_len is not None:
                s = jnp.where(kii[None, None, None, :] < kv_valid_len,
                              s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vi.dtype), vi,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, D), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_body, (m0, l0, a0), (ks, vs, kp, kidx))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = lax.scan(q_body, None, (qs, qp))          # (nq,B,H,qc,D)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, nq * q_chunk, H, D)
    return out[:, :Sq]


def windowed_attention(q: Array, k: Array, v: Array, *,
                       q_positions: Array, kv_positions: Array,
                       window: int, q_chunk: int = 1024) -> Array:
    """Sliding-window causal attention with O(S*window) FLOPs.

    Each q chunk attends only to the kv slice [chunk_start - window,
    chunk_end), gathered with dynamic_slice — genuinely sub-quadratic.
    Requires q and kv aligned (Sq == Sk, same positions) — i.e. prefill.
    """
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    if window >= Sk:  # degenerate: full attention is cheaper
        return chunked_attention(q, k, v, q_positions=q_positions,
                                 kv_positions=kv_positions, causal=True,
                                 window=window, q_chunk=q_chunk)
    scale = 1.0 / (D ** 0.5)
    k = shctx.constrain(expand_kv(k, H), ("batch", None, "heads", None))
    v = shctx.constrain(expand_kv(v, H), ("batch", None, "heads", None))
    q_chunk = min(q_chunk, Sq)
    nq = -(-Sq // q_chunk)
    pq = nq * q_chunk - Sq
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pq), constant_values=-1)
    span = window + q_chunk
    # pad kv on the left by `window` so every chunk's slice is in range
    k = jnp.pad(k, ((0, 0), (window, pq), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (window, pq), (0, 0), (0, 0)))
    kv_positions = jnp.pad(kv_positions, (window, pq),
                           constant_values=2**30)
    kv_positions = kv_positions.at[:window].set(-(2**30))

    qr = q.reshape(B, nq, q_chunk, H, D).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(nq, q_chunk)
    starts = jnp.arange(nq) * q_chunk

    def body(_, xs):
        qi, qpi, start = xs
        ki = lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vi = lax.dynamic_slice_in_dim(v, start, span, axis=1)
        kpi = lax.dynamic_slice_in_dim(kv_positions, start, span, axis=0)
        s = _attend_chunk(qi, ki, vi, qpi, kpi, scale, True, window)
        out = jnp.einsum("bhqk,bkhd->bhqd",
                         jax.nn.softmax(s, axis=-1).astype(vi.dtype), vi,
                         preferred_element_type=jnp.float32)
        return None, out.astype(q.dtype)

    _, outs = lax.scan(body, None, (qr, qp, starts))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, nq * q_chunk, H, D)
    return out[:, :Sq]


def decode_attention(q: Array, k_cache: Array, v_cache: Array, *,
                     q_position: Array, kv_positions: Array,
                     valid_len: Array, window: Optional[int] = None) -> Array:
    """Single-step attention against a KV cache.

    q: (B, 1, H, D); caches: (B, Smax, KV, D); valid_len: scalar int —
    number of populated cache slots; kv_positions: (Smax,) absolute
    positions of cache entries (ring buffers make these non-monotonic).

    Per-slot (continuous-batching) form: q_position (B,), valid_len (B,)
    and kv_positions (B, Smax) — every batch row tracks an independent
    sequence, so the validity mask is computed per row.
    """
    B, _, H, D = q.shape
    _, Sm, KV, _ = k_cache.shape
    G = H // KV
    scale = 1.0 / (D ** 0.5)
    # grouped-GQA formulation: the cache is consumed at its stored (KV)
    # width — never materialize the H-expanded copy (the decode step is
    # cache-bandwidth-bound; an 8x expansion is an 8x memory-term hit.
    # The Pallas decode kernel achieves the same via its BlockSpec
    # index_map on TPU).
    qr = q.reshape(B, KV, G, D)
    policy = shctx.current()
    seq_sharded = (policy is not None
                   and policy.resolve(KV, "kv_heads") is None)
    if seq_sharded:
        # the cache is stored sequence-sharded (kv heads don't divide the
        # model axis).  Pin the score row to the same layout so GSPMD
        # reduces over the sharded seq dim with one small (B, H, D)
        # all-reduce instead of all-gathering the cache — for kimi
        # decode_32k this is ~110 GiB -> ~0.1 GiB of per-step collective
        # traffic (EXPERIMENTS.md §Perf).
        qr = policy.constrain(qr, ("batch", None, None, None))
    s = jnp.einsum("bhgd,bkhd->bhgk", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if seq_sharded:
        s = policy.constrain(s, ("batch", None, None, "kv_seq"))
    idx = jnp.arange(Sm)
    q_pos = jnp.asarray(q_position)
    if q_pos.ndim:                          # per-slot decode: (B,) state
        mask = ((idx[None, :] < jnp.asarray(valid_len)[:, None])
                & (kv_positions <= q_pos[:, None]))
        if window is not None:
            mask &= (q_pos[:, None] - kv_positions) < window
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    else:
        mask = (idx < valid_len) & (kv_positions <= q_position)
        if window is not None:
            mask &= (q_position - kv_positions) < window
        s = jnp.where(mask[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + norm)
# ---------------------------------------------------------------------------


def init_attention(key: Array, cfg, dtype) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (D, H, hd), dtype),
        "wk": dense_init(ks[1], (D, KV, hd), dtype),
        "wv": dense_init(ks[2], (D, KV, hd), dtype),
        "wo": dense_init(ks[3], (H, hd, D), dtype),
    }


def attention_qkv(params: dict, x: Array, positions: Array,
                  rope_theta: float) -> tuple[Array, Array, Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    policy = shctx.current()
    if policy is not None:
        q = policy.constrain(
            q, policy.attn_q_axes(q.shape[1], q.shape[2]))
    k = shctx.constrain(k, ("batch", None, "kv_heads", None))
    v = shctx.constrain(v, ("batch", None, "kv_heads", None))
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def attention_out(params: dict, attn: Array) -> Array:
    return jnp.einsum("bshk,hkd->bsd", attn, params["wo"])


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key: Array, d_model: int, d_ff: int, act: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d_model, d_ff), dtype),
         "w_down": dense_init(ks[1], (d_ff, d_model), dtype)}
    if act == "swiglu":
        p["w_gate"] = dense_init(ks[2], (d_model, d_ff), dtype)
    return p


def apply_mlp(params: dict, x: Array, act: str) -> Array:
    up = x @ params["w_up"]
    if act == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    else:
        h = jax.nn.relu(up)
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def init_embedding(key: Array, cfg, dtype) -> dict:
    V, D = cfg.padded_vocab, cfg.d_model
    ks = jax.random.split(key, 2)
    # unit-variance after the sqrt(D) input multiplier; keeps tied logits
    # at O(|x|) magnitude so the initial loss is ~log(V).
    p = {"embedding": dense_init(ks[0], (V, D), dtype,
                                 scale=1.0 / (D ** 0.5))}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], (D, V), dtype)
    return p


def embed(params: dict, tokens: Array, cfg) -> Array:
    x = params["embedding"][tokens]
    return x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)


def logits(params: dict, x: Array, cfg) -> Array:
    if cfg.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", x, params["embedding"])
    else:
        out = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    # mask vocab padding
    V = cfg.padded_vocab
    if V != cfg.vocab_size:
        mask = jnp.arange(V) < cfg.vocab_size
        out = jnp.where(mask, out, NEG_INF)
    return out
