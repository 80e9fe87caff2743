"""Decoder stacks for every assigned architecture family.

Design notes:
  * Layers are stacked along a leading axis and executed with ``lax.scan``
    so HLO size / compile time stay O(1 layer) even for the 61-layer 1T MoE
    at 512 devices.  Heterogeneous stacks (RecurrentGemma's rec/rec/attn
    pattern, MoE dense prefixes) scan over "superblocks" of one pattern
    repeat, with the non-multiple remainder unrolled.
  * KV caches are ring buffers of capacity ``min(window, max_len)`` so
    sliding-window / local-attention archs keep bounded decode state
    (long_500k eligibility).  ``slot_pos`` carries the absolute position of
    each slot; masking in the attention ops uses positions, so ring
    non-monotonicity is harmless.
  * All functions are functional; ``mode`` is one of train|prefill|decode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import chunked_prefill_attention as cpa_kernel
from repro.kernels import ops as kernel_ops
from repro.kernels import paged_decode_attention as pfd_kernel
from repro.kernels import ragged_chunked_prefill as rcp_kernel
from repro.kvcache import paged as paged_lib
from repro.sharding import context as shctx

from . import layers, mla, moe as moe_lib, rglru, ssm
from .layers import rms_norm

Array = jax.Array


# ---------------------------------------------------------------------------
# block init
# ---------------------------------------------------------------------------


def init_attn_mlp_block(key, cfg, dtype, *, use_moe=False, cross=False):
    ks = jax.random.split(key, 5)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": (mla.init_mla(ks[0], cfg, dtype) if cfg.mla
                 else layers.init_attention(ks[0], cfg, dtype)),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
    }
    if use_moe:
        p["moe"] = moe_lib.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff,
                                   cfg.mlp_act, dtype)
    if cross:
        p["ln_x"] = jnp.zeros((cfg.d_model,), dtype)
        p["xattn"] = layers.init_attention(ks[2], cfg, dtype)
    return p


def init_ssm_block(key, cfg, dtype):
    return {"ln": jnp.zeros((cfg.d_model,), dtype),
            "mixer": ssm.init_mamba2(key, cfg, dtype)}


def init_rec_block(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    return {"ln1": jnp.zeros((cfg.d_model,), dtype),
            "rec": rglru.init_rglru_block(ks[0], cfg, dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "mlp": layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff,
                                   cfg.mlp_act, dtype)}


# ---------------------------------------------------------------------------
# KV-cache ring buffer helpers
# ---------------------------------------------------------------------------


def kv_cache_capacity(cfg, max_len: int, window: Optional[int]) -> int:
    return min(window, max_len) if window else max_len


def empty_slot_pos(capacity: int) -> Array:
    return jnp.full((capacity,), 2**30, jnp.int32)


def prefill_write_kv(cache_k, cache_v, k, v, slot_pos_template=None):
    """Write a freshly prefilled sequence of length S into a ring cache.

    cache_k/v: (B, W, KV, D); k/v: (B, S, KV, D).  Prefill always starts at
    position 0, so slots are positions mod W.  Returns new caches + the
    slot->position map (W,).
    """
    Wc = cache_k.shape[1]
    S = k.shape[1]
    if S >= Wc:
        tail_k, tail_v = k[:, S - Wc:], v[:, S - Wc:]
        shift = S % Wc
        new_k = jnp.roll(tail_k, shift, axis=1).astype(cache_k.dtype)
        new_v = jnp.roll(tail_v, shift, axis=1).astype(cache_v.dtype)
        slot_pos = jnp.roll(jnp.arange(S - Wc, S, dtype=jnp.int32), shift)
    else:
        new_k = lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), 0, axis=1)
        new_v = lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), 0, axis=1)
        slot_pos = empty_slot_pos(Wc).at[:S].set(
            jnp.arange(S, dtype=jnp.int32))
    return new_k, new_v, slot_pos


def prefill_slot_pos(capacity: int, seq_len: int) -> Array:
    """Slot -> absolute-position map after prefilling ``seq_len`` tokens."""
    if seq_len >= capacity:
        shift = seq_len % capacity
        return jnp.roll(
            jnp.arange(seq_len - capacity, seq_len, dtype=jnp.int32), shift)
    return empty_slot_pos(capacity).at[:seq_len].set(
        jnp.arange(seq_len, dtype=jnp.int32))


def decode_write_kv(cache_k, cache_v, k, v, pos):
    """Write one token (B, 1, KV, D) at ring slot pos % W.

    ``pos`` is either a scalar (batch-mode decode: every row sits at the
    same position) or a (B,) vector (continuous batching: every slot
    tracks an independent sequence), in which case each row scatters at
    its own ring slot."""
    Wc = cache_k.shape[1]
    idx = (pos % Wc).astype(jnp.int32)
    if idx.ndim:
        rows = jnp.arange(cache_k.shape[0])
        new_k = cache_k.at[rows, idx].set(k[:, 0].astype(cache_k.dtype))
        new_v = cache_v.at[rows, idx].set(v[:, 0].astype(cache_v.dtype))
        return new_k, new_v
    new_k = lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), idx, axis=1)
    new_v = lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), idx, axis=1)
    return new_k, new_v


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def _attn_seq(p, x, positions, cfg, window, kv_len_hint=None):
    """Full-sequence self attention (train / prefill compute)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = layers.attention_qkv(p["attn"], h, positions, cfg.rope_theta)
    S = x.shape[1]
    policy = shctx.current()
    q_chunk = 1024
    if policy is not None and policy.use_seq_attention(S, cfg.num_heads):
        # sequence-sharded attention (heads don't divide the model axis):
        # q stays sharded on its seq dim — no q-chunk scan, so the
        # sharded dim is never scanned over; kv still streams in chunks.
        q_chunk = S
    if window is not None and window < S:
        # (windowed attention keeps its own chunking: its per-chunk kv
        # span is what makes it sub-quadratic; no assigned arch combines
        # SWA with a non-divisible head count)
        attn = layers.windowed_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            window=window)
    else:
        attn = layers.chunked_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            causal=True, window=window, q_chunk=q_chunk)
    return x + layers.attention_out(p["attn"], attn), k, v


def _attn_decode(p, x, cache_k, cache_v, pos, slot_pos, cfg, window):
    """One-token self attention against the ring cache.

    pos is a scalar with slot_pos (W,) in batch mode, or (B,) with
    slot_pos (B, W) in per-slot (continuous-batching) mode — every batch
    row then advances an independent sequence.
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = layers.attention_qkv(p["attn"], h, pos[..., None],
                                   cfg.rope_theta)
    new_k, new_v = decode_write_kv(cache_k, cache_v, k, v, pos)
    Wc = cache_k.shape[1]
    if pos.ndim:
        rows = jnp.arange(slot_pos.shape[0])
        new_slot_pos = slot_pos.at[rows, pos % Wc].set(pos)
    else:
        new_slot_pos = slot_pos.at[pos % Wc].set(pos)
    valid = jnp.minimum(pos + 1, Wc)
    attn = layers.decode_attention(
        q, new_k, new_v, q_position=pos, kv_positions=new_slot_pos,
        valid_len=valid, window=window)
    return (x + layers.attention_out(p["attn"], attn), new_k, new_v,
            new_slot_pos)


def _attn_decode_paged(p, x, pages_k, pages_v, pos, tables, cfg,
                       use_pallas: bool = False):
    """One-token self attention against a paged (block-table) KV cache.

    pos: (B,) per-slot logical positions; tables: (B, nb) i32 physical
    page ids; pages_k/v: (N, bs, KV, D).  The new token scatters into
    page ``tables[s, pos[s]//bs]`` and attention runs over the paged
    pool — positions 0..pos are bit-identical to the contiguous slot
    cache's layout (absolute-position order, masked tail), so the paged
    engine matches the contiguous engine token for token.

    ``use_pallas`` routes the attention through the Pallas
    ``paged_decode_attention`` kernel, which DMAs each row's live pages
    from the pool into VMEM and skips dead rows (the production TPU
    path); the default jnp path gathers a transient contiguous view —
    exact, but O(slots * max_len) scratch per layer.  Off TPU the
    kernel body runs in interpret mode (correct, slow;
    ``kernels.ops._default_interpret`` decides) — the engine
    auto-selects per backend (``generate.make_paged_decode_fn``).
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = layers.attention_qkv(p["attn"], h, pos[..., None],
                                   cfg.rope_theta)
    new_k = paged_lib.scatter_token(pages_k, k[:, 0], tables, pos)
    new_v = paged_lib.scatter_token(pages_v, v[:, 0], tables, pos)
    if use_pallas:
        # rows whose table starts at the trash page (the pool's last
        # page, ``PagedKVCache.trash_block == num_blocks``) are evicted
        # slots or slots still in prefill: the engine discards their
        # outputs, so the kernel walks none of their pages
        dead = tables[:, 0] == pages_k.shape[0] - 1
        attn = pfd_kernel.paged_flash_decode_attention(
            q[:, 0], new_k, new_v, tables, jnp.where(dead, 0, pos + 1),
            interpret=kernel_ops._default_interpret())[:, None]
    else:
        k_seq = paged_lib.gather_tokens(new_k, tables)  # (B, nb*bs, KV, D)
        v_seq = paged_lib.gather_tokens(new_v, tables)
        L = k_seq.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                  (x.shape[0], L))
        attn = layers.decode_attention(
            q, k_seq, v_seq, q_position=pos, kv_positions=kv_pos,
            valid_len=pos + 1, window=None)
    return x + layers.attention_out(p["attn"], attn), new_k, new_v


def _attn_chunk_paged(p, x, pages_k, pages_v, positions, table_row, cfg,
                      use_pallas: bool = False):
    """Chunked-prefill self attention for ONE sequence (batch dim 1).

    x: (1, T, D) the in-flight chunk; positions: (T,) its absolute
    positions ``ctx_len .. ctx_len + T - 1`` (traced); table_row: (nb,)
    i32 the sequence's block table.  The chunk's K/V scatter into the
    page pool at those positions FIRST, then the queries attend over
    the gathered logical view — full over the already-written prefix,
    causal within the chunk.  The jnp path runs the same
    ``layers.chunked_attention`` recipe as the stall prefill
    (``_attn_seq``), so per-position outputs — and therefore the KV the
    chunk writes and the final-chunk logits — match the stall-admission
    engine token for token; ``use_pallas`` routes through the
    ``chunked_prefill_attention`` kernel (block-table scalar-prefetch,
    no contiguous view materialized).
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = layers.attention_qkv(p["attn"], h, positions[None, :],
                                   cfg.rope_theta)
    new_k = paged_lib.scatter_chunk(pages_k, k[0], table_row, positions[0])
    new_v = paged_lib.scatter_chunk(pages_v, v[0], table_row, positions[0])
    if use_pallas:
        attn = cpa_kernel.chunked_prefill_attention(
            q, new_k, new_v, table_row[None, :], positions[:1],
            interpret=kernel_ops._default_interpret())
    else:
        k_seq = paged_lib.gather_tokens(new_k, table_row[None, :])
        v_seq = paged_lib.gather_tokens(new_v, table_row[None, :])
        L = k_seq.shape[1]
        attn = layers.chunked_attention(
            q, k_seq, v_seq, q_positions=positions,
            kv_positions=jnp.arange(L, dtype=jnp.int32), causal=True)
    return x + layers.attention_out(p["attn"], attn), new_k, new_v


def _attn_chunks_paged(p, x, pages_k, pages_v, ctx, cfg):
    """Fused ragged chunked-prefill attention: EVERY scheduled chunk of
    one engine iteration in one pass (batch dim 1, packed tokens).

    x: (1, TT, D) the PACKED token stream — chunk ``c`` owns rows
    ``q_off[c] .. q_off[c] + len[c] - 1``; ctx carries the per-chunk
    metadata (``meta`` rows ``[slot, ctx_len, chunk_len, q_offset]``,
    per-chunk block tables, per-token chunk ids / positions / validity
    and the static padded chunk length).  All chunks' K/V scatter into
    the page pools in one pass and each chunk attends full over its
    already-written prefix, causal within the chunk.

    The jnp path runs the exact per-chunk ``layers.chunked_attention``
    recipe over the gathered view (a static Python loop over the
    padded chunk count — ONE traced executable, so per-position
    numerics and therefore greedy output are bit-identical to the
    sequential per-chunk path and to stall admission); ``use_pallas``
    routes through the fused ``ragged_chunked_prefill`` kernel, whose
    in-kernel scatter (aliased page outputs) replaces the separate
    ``scatter_packed`` pass entirely.
    """
    positions = ctx["positions"]             # (TT,) absolute positions
    token_chunk = ctx["token_chunk"]         # (TT,) row -> chunk id
    local = ctx["local"]                     # (TT,) row within its chunk
    valid = ctx["valid"]                     # (TT,) False = padding row
    meta = ctx["meta"]                       # (C, 4) i32
    tables = ctx["table_rows"]               # (C, nb) i32
    Tp = ctx["chunk_pad"]                    # static padded chunk length
    C = meta.shape[0]
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = layers.attention_qkv(p["attn"], h, positions[None, :],
                                   cfg.rope_theta)
    TT = x.shape[1]
    # per-chunk padded views of the packed stream (row t of chunk c is
    # packed row q_off[c] + t; rows past chunk_len are padding)
    qidx = jnp.clip(meta[:, 3][:, None]
                    + jnp.arange(Tp, dtype=jnp.int32)[None, :], 0, TT - 1)
    if ctx.get("use_pallas", False):
        # chunk K/V are pre-cast to the page dtype so the kernel's
        # in-chunk phase matches the post-scatter page contents the
        # gathered jnp path reads
        qv = jnp.take(q[0], qidx.reshape(-1), axis=0).reshape(
            (C, Tp) + q.shape[2:])
        knv = jnp.take(k[0].astype(pages_k.dtype), qidx.reshape(-1),
                       axis=0).reshape((C, Tp) + k.shape[2:])
        vnv = jnp.take(v[0].astype(pages_v.dtype), qidx.reshape(-1),
                       axis=0).reshape((C, Tp) + v.shape[2:])
        av, new_k, new_v = rcp_kernel.ragged_chunked_prefill(
            qv, knv, vnv, pages_k, pages_v, tables, meta,
            interpret=kernel_ops._default_interpret())
    else:
        new_k = paged_lib.scatter_packed(pages_k, k[0], tables,
                                         token_chunk, positions, valid)
        new_v = paged_lib.scatter_packed(pages_v, v[0], tables,
                                         token_chunk, positions, valid)
        k_seq = paged_lib.gather_tokens(new_k, tables)  # (C, nb*bs, KV, D)
        v_seq = paged_lib.gather_tokens(new_v, tables)
        L = k_seq.shape[1]
        outs = []
        for c in range(C):                   # static: C is a shape
            qc = jnp.take(q, qidx[c], axis=1)           # (1, Tp, H, D)
            outs.append(layers.chunked_attention(
                qc, k_seq[c:c + 1], v_seq[c:c + 1],
                q_positions=meta[c, 1] + jnp.arange(Tp, dtype=jnp.int32),
                kv_positions=jnp.arange(L, dtype=jnp.int32),
                causal=True)[0])
        av = jnp.stack(outs)                 # (C, Tp, H, D)
    # repack: packed row j is row local[j] of chunk token_chunk[j]
    attn = av[token_chunk, jnp.clip(local, 0, Tp - 1)][None]
    return x + layers.attention_out(p["attn"], attn), new_k, new_v


def _project_enc_kv(p, enc_out):
    """Per-layer K/V projections of the shared encoder memory (no rope)."""
    enc_k = jnp.einsum("bsd,dhk->bshk", enc_out, p["xattn"]["wk"])
    enc_v = jnp.einsum("bsd,dhk->bshk", enc_out, p["xattn"]["wv"])
    return enc_k, enc_v


def _cross_attn(p, x, enc_k, enc_v, cfg):
    """Cross attention against the (already projected) encoder memory."""
    h = rms_norm(x, p["ln_x"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["xattn"]["wq"])
    Te = enc_k.shape[1]
    pos_q = jnp.full((x.shape[1],), Te, jnp.int32)  # attend to everything
    attn = layers.chunked_attention(
        q, enc_k, enc_v, q_positions=pos_q,
        kv_positions=jnp.arange(Te), causal=False)
    return x + jnp.einsum("bshk,hkd->bsd", attn, p["xattn"]["wo"])


def _mlp_part(p, x, cfg):
    return x + layers.apply_mlp(p["mlp"], rms_norm(x, p["ln2"],
                                                   cfg.norm_eps), cfg.mlp_act)


def _moe_part(p, x, cfg, *, serving=False, valid=None, use_pallas=False,
              layer=None):
    """The expert layer.  Serving (prefill and decode against a cache)
    drops no token (``moe.apply_moe_dropless``), on one device or
    sharded; training dispatches with a capacity (``moe.apply_moe``).
    ``layer``: the experts' weights are the whole scanned stack and this
    is the layer's index (``apply_stack``)."""
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if serving:
        y, aux = moe_lib.apply_moe_dropless(p["moe"], h, cfg, valid,
                                            use_pallas, layer)
    else:
        y, aux = moe_lib.apply_moe(p["moe"], h, cfg)
    return x + y, aux


def _ffn_part(kind, p, x, cfg, **serving):
    """The block's second half: the expert layer or the MLP."""
    if kind == "moe":
        return _moe_part(p, x, cfg, **serving)
    return _mlp_part(p, x, cfg), ZERO_AUX


ZERO_AUX = {"moe_aux_loss": jnp.float32(0.0), "moe_drop_frac": jnp.float32(0.0)}
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def apply_block_seq(kind, p, x, ctx, cfg, cache=None):
    """Full-sequence application of one block.

    ctx: dict(positions, enc_k, enc_v).  cache: per-layer cache pytree or
    None (train).  Returns (x, new_cache, aux).
    """
    positions = ctx["positions"]
    aux = ZERO_AUX
    # "seq" resolves to "model" only under the seq-parallel policy flag —
    # the residual stream (and thus every saved layer input under remat)
    # is then sequence-sharded between blocks (16x less live memory).
    x = shctx.constrain(x, ("batch", "seq", None))
    if cfg.mla and kind in ("dense", "moe"):
        y, lat = mla.attend_seq(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                                positions, cfg)
        new_cache = None
        if cache is not None:
            nl, _, _ = prefill_write_kv(cache["ckv"], cache["ckv"], lat, lat)
            new_cache = dict(cache, ckv=nl)
        x, aux = _ffn_part(kind, p, x + y, cfg, serving=cache is not None,
                           layer=ctx.get("layer"))
        return x, new_cache, aux
    if kind in ("dense", "moe", "cross"):
        window = cfg.window if kind != "attn_local" else cfg.local_window
        x, k, v = _attn_seq(p, x, positions, cfg, window)
        new_cache = None
        if cache is not None:
            nk, nv, _ = prefill_write_kv(cache["k"], cache["v"], k, v)
            new_cache = dict(cache, k=nk, v=nv)
        if kind == "cross":
            enc_k, enc_v = _project_enc_kv(p, ctx["enc_out"])
            x = _cross_attn(p, x, enc_k, enc_v, cfg)
            if new_cache is not None:
                new_cache["enc_k"] = enc_k.astype(new_cache["enc_k"].dtype)
                new_cache["enc_v"] = enc_v.astype(new_cache["enc_v"].dtype)
        if kind == "moe":
            x, aux = _moe_part(p, x, cfg, serving=cache is not None,
                               layer=ctx.get("layer"))
        else:
            x = _mlp_part(p, x, cfg)
        return x, new_cache, aux
    if kind == "attn_local":
        x, k, v = _attn_seq(p, x, positions, cfg, cfg.local_window)
        new_cache = None
        if cache is not None:
            nk, nv, _ = prefill_write_kv(cache["k"], cache["v"], k, v)
            new_cache = dict(cache, k=nk, v=nv)
        return _mlp_part(p, x, cfg), new_cache, aux
    if kind == "ssm":
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        y, new_state = ssm.apply_mamba2(p["mixer"], h, cfg,
                                        None if cache is None else cache)
        return x + y, new_state, aux
    if kind == "rec":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, new_state = rglru.apply_recurrent_block(
            p["rec"], h, cfg, None if cache is None else cache)
        return _mlp_part(p, x + y, cfg), new_state, aux
    raise ValueError(kind)


def apply_block_chunk(kind, p, x, ctx, cfg, cache):
    """Chunked-prefill application of one block against a paged cache.

    ctx: dict(positions (T,) traced absolute positions, table_row (nb,)
    i32, use_pallas bool).  Only the paged-eligible kinds apply
    (``paged_supported`` gates the engine to dense/moe stacks).
    """
    x = shctx.constrain(x, ("batch", None, None))
    if cfg.mla:
        raise NotImplementedError("latent attention prefills through the "
                                  "fused ragged path (mode='chunks')")
    if kind in ("dense", "moe"):
        x, nk, nv = _attn_chunk_paged(
            p, x, cache["k"], cache["v"], ctx["positions"],
            ctx["table_row"], cfg, ctx.get("use_pallas", False))
        x, aux = _ffn_part(kind, p, x, cfg, serving=True,
                           use_pallas=ctx.get("use_pallas", False),
                           layer=ctx.get("layer"))
        return x, dict(cache, k=nk, v=nv), aux
    raise NotImplementedError(
        f"chunked prefill requires a paged-eligible stack (got {kind!r})")


def apply_block_chunks(kind, p, x, ctx, cfg, cache):
    """Fused ragged chunked-prefill application of one block: the whole
    packed multi-chunk batch against the paged cache in one pass
    (``_attn_chunks_paged``).  Same kind gating as the per-chunk mode
    (``paged_supported`` restricts the engine to dense/moe stacks).
    """
    x = shctx.constrain(x, ("batch", None, None))
    serving = dict(serving=True, valid=ctx["valid"][None],
                   use_pallas=ctx.get("use_pallas", False),
                   layer=ctx.get("layer"))
    if cfg.mla and kind in ("dense", "moe"):
        y, pool = mla.chunks_paged(
            p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cache["ckv"],
            ctx, cfg)
        x, aux = _ffn_part(kind, p, x + y, cfg, **serving)
        return x, dict(cache, ckv=pool), aux
    if kind in ("dense", "moe"):
        x, nk, nv = _attn_chunks_paged(p, x, cache["k"], cache["v"],
                                       ctx, cfg)
        x, aux = _ffn_part(kind, p, x, cfg, **serving)
        return x, dict(cache, k=nk, v=nv), aux
    raise NotImplementedError(
        f"chunked prefill requires a paged-eligible stack (got {kind!r})")


def apply_block_decode(kind, p, x, ctx, cfg, cache):
    pos = ctx["pos"]
    tables = ctx.get("tables")         # paged decode: (B, nb) block table
    aux = ZERO_AUX
    x = shctx.constrain(x, ("batch", None, None))
    use_pallas = ctx.get("use_pallas", False)
    if cfg.mla and kind in ("dense", "moe"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        valid = None
        if tables is not None:
            y, ring = mla.decode_paged(p["attn"], h, cache["ckv"], pos,
                                       tables, cfg, use_pallas)
            # rows at the trash page are dead slots: routed nowhere
            valid = (tables[:, 0] != cache["ckv"].shape[0] - 1)[:, None]
        else:
            q, lat = mla.project(p["attn"], h, pos[..., None], cfg)
            ring, _ = decode_write_kv(cache["ckv"], cache["ckv"], lat, lat,
                                      pos)
            slot_pos = ctx["slot_pos"]
            Wc = ring.shape[1]
            if pos.ndim:
                rows = jnp.arange(slot_pos.shape[0])
                slot_pos = slot_pos.at[rows, pos % Wc].set(pos)
            else:
                slot_pos = slot_pos.at[pos % Wc].set(pos)
            y = mla.attend_ring(p["attn"], q, ring, pos, slot_pos, cfg)
        x, aux = _ffn_part(kind, p, x + y, cfg, serving=True, valid=valid,
                           use_pallas=use_pallas, layer=ctx.get("layer"))
        return x, dict(cache, ckv=ring), aux
    if kind in ("dense", "moe", "cross"):
        if tables is not None:
            x, nk, nv = _attn_decode_paged(p, x, cache["k"], cache["v"],
                                           pos, tables, cfg,
                                           ctx.get("use_pallas", False))
        else:
            x, nk, nv, _ = _attn_decode(p, x, cache["k"], cache["v"], pos,
                                        ctx["slot_pos"], cfg, cfg.window)
        if kind == "cross":
            x = _cross_attn(p, x, cache["enc_k"], cache["enc_v"], cfg)
        if kind == "moe":
            x, aux = _moe_part(p, x, cfg, serving=True,
                               use_pallas=use_pallas, layer=ctx.get("layer"))
        else:
            x = _mlp_part(p, x, cfg)
        return x, dict(cache, k=nk, v=nv), aux
    if kind == "attn_local":
        x, nk, nv, _ = _attn_decode(p, x, cache["k"], cache["v"], pos,
                                    ctx["slot_pos"], cfg, cfg.local_window)
        return _mlp_part(p, x, cfg), dict(cache, k=nk, v=nv), aux
    if kind == "ssm":
        h = rms_norm(x, p["ln"], cfg.norm_eps)
        y, new_state = ssm.decode_mamba2(p["mixer"], h, cfg, cache)
        return x + y, new_state, aux
    if kind == "rec":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, new_state = rglru.decode_recurrent_block(p["rec"], h, cfg, cache)
        return _mlp_part(x=x + y, p=p, cfg=cfg), new_state, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack structure: pattern of block kinds -> scanned superblocks + remainder
# ---------------------------------------------------------------------------


def stack_pattern(cfg) -> tuple[tuple[str, ...], int, tuple[str, ...],
                                tuple[str, ...]]:
    """Returns (pattern, n_repeats, prefix_kinds, tail_kinds)."""
    if cfg.family == "moe":
        prefix = ("dense",) * cfg.num_dense_layers
        n = cfg.num_layers - cfg.num_dense_layers
        return ("moe",), n, prefix, ()
    if cfg.family == "ssm":
        return ("ssm",), cfg.num_layers, (), ()
    if cfg.family == "hybrid":
        pat = cfg.block_pattern or ("rec", "rec", "attn_local")
        n = cfg.num_layers // len(pat)
        rem = cfg.num_layers - n * len(pat)
        return pat, n, (), pat[:rem]
    # dense / vlm / encdec decoder
    kind = "cross" if cfg.family == "encdec" else "dense"
    return (kind,), cfg.num_layers, (), ()


def _init_kind(kind, key, cfg, dtype):
    if kind == "dense":
        return init_attn_mlp_block(key, cfg, dtype)
    if kind == "moe":
        return init_attn_mlp_block(key, cfg, dtype, use_moe=True)
    if kind == "cross":
        return init_attn_mlp_block(key, cfg, dtype, cross=True)
    if kind == "attn_local":
        return init_attn_mlp_block(key, cfg, dtype)
    if kind == "ssm":
        return init_ssm_block(key, cfg, dtype)
    if kind == "rec":
        return init_rec_block(key, cfg, dtype)
    raise ValueError(kind)


def init_stack(key, cfg, dtype) -> dict:
    pat, n, prefix, tail = stack_pattern(cfg)
    out = {}
    kp, ks, kt = jax.random.split(key, 3)
    for i, kind in enumerate(prefix):
        out[f"prefix{i}"] = _init_kind(kind, jax.random.fold_in(kp, i),
                                       cfg, dtype)
    if n > 0:
        for s, kind in enumerate(pat):
            keys = jax.random.split(jax.random.fold_in(ks, s), n)
            out[f"scan{s}"] = jax.vmap(
                lambda k: _init_kind(kind, k, cfg, dtype))(keys)
    for i, kind in enumerate(tail):
        out[f"tail{i}"] = _init_kind(kind, jax.random.fold_in(kt, i),
                                     cfg, dtype)
    return out


def _sum_aux(a, b):
    return {k: a.get(k, 0) + b.get(k, 0) for k in {**a, **b}}


def apply_stack(params: dict, x: Array, ctx: dict, cfg, cache=None,
                mode: str = "train", remat: bool = False):
    """Run the whole block stack. Returns (x, new_cache, aux)."""
    pat, n, prefix, tail = stack_pattern(cfg)
    aux = dict(ZERO_AUX)
    # entries besides the layers' (pos, counters) pass through
    new_cache = dict(cache) if cache is not None else None
    apply_fn = {"decode": apply_block_decode,
                "chunk": apply_block_chunk,
                "chunks": apply_block_chunks}.get(mode, apply_block_seq)

    for i, kind in enumerate(prefix):
        c = None if cache is None else cache[f"prefix{i}"]
        x, nc, a = apply_fn(kind, params[f"prefix{i}"], x, ctx, cfg, c)
        aux = _sum_aux(aux, a)
        if new_cache is not None:
            new_cache[f"prefix{i}"] = nc

    if n > 0:
        p_stacked = tuple(params[f"scan{s}"] for s in range(len(pat)))
        # The serving expert layer reads its experts' weights from the
        # whole stack by layer index: a kernel operand sliced out of the
        # stack inside the scan would be copied whole every layer.
        whole = [{} for _ in pat]
        if cache is not None and shctx.current() is None:
            for s, kind in enumerate(pat):
                if kind == "moe":
                    m = p_stacked[s]["moe"]
                    whole[s] = {k: m[k] for k in EXPERT_WEIGHTS}
                    p_stacked = p_stacked[:s] + (dict(p_stacked[s], moe={
                        k: v for k, v in m.items()
                        if k not in EXPERT_WEIGHTS}),) + p_stacked[s + 1:]

        def superblock(x, inp):
            ps, cs, i = inp if any(whole) else inp + (None,)
            auxes = dict(ZERO_AUX)
            ncs = [None] * len(pat)
            for s, kind in enumerate(pat):
                c = None if cs is None else cs[s]
                p, cx = ps[s], ctx
                if whole[s]:
                    p = dict(p, moe=dict(p["moe"], **whole[s]))
                    cx = dict(ctx, layer=i)
                x, nc, a = apply_fn(kind, p, x, cx, cfg, c)
                auxes = _sum_aux(auxes, a)
                ncs[s] = nc
            if cs is None:
                return x, auxes
            return x, (tuple(ncs), auxes)

        body = jax.checkpoint(superblock) if (remat and mode == "train") \
            else superblock
        layer_idx = ((jnp.arange(n, dtype=jnp.int32),) if any(whole)
                     else ())
        if cache is None:
            x, auxes = lax.scan(body, x, (p_stacked, None) + layer_idx)
        else:
            c_stacked = tuple(cache[f"scan{s}"] for s in range(len(pat)))
            x, (nc_stacked, auxes) = lax.scan(
                body, x, (p_stacked, c_stacked) + layer_idx)
            for s in range(len(pat)):
                new_cache[f"scan{s}"] = nc_stacked[s]
        aux = _sum_aux(aux, jax.tree.map(jnp.sum, auxes))

    for i, kind in enumerate(tail):
        c = None if cache is None else cache[f"tail{i}"]
        x, nc, a = apply_fn(kind, params[f"tail{i}"], x, ctx, cfg, c)
        aux = _sum_aux(aux, a)
        if new_cache is not None:
            new_cache[f"tail{i}"] = nc

    return x, new_cache, aux


# ---------------------------------------------------------------------------
# cache construction (zeros for the real engine; specs for the dry-run)
# ---------------------------------------------------------------------------


def _layer_cache_zeros(kind, cfg, batch, max_len, dtype):
    if cfg.mla and kind in ("dense", "moe"):
        cap = kv_cache_capacity(cfg, max_len, cfg.window)
        return {"ckv": jnp.zeros((batch, cap, cfg.latent_lanes), dtype)}
    if kind in ("dense", "moe", "cross", "attn_local"):
        window = cfg.local_window if kind == "attn_local" else cfg.window
        cap = kv_cache_capacity(cfg, max_len, window)
        c = {"k": jnp.zeros((batch, cap, cfg.num_kv_heads, cfg.head_dim),
                            dtype),
             "v": jnp.zeros((batch, cap, cfg.num_kv_heads, cfg.head_dim),
                            dtype)}
        if kind == "cross":
            c["enc_k"] = jnp.zeros(
                (batch, cfg.encoder_seq_len, cfg.num_kv_heads, cfg.head_dim),
                dtype)
            c["enc_v"] = jnp.zeros_like(c["enc_k"])
        return c
    if kind == "ssm":
        conv_dim = cfg.ssm_d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
        return {"conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim),
                                  dtype),
                "ssd": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                                  cfg.ssm_state), jnp.float32)}
    if kind == "rec":
        lw = cfg.lru_width or cfg.d_model
        return {"conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, lw),
                                  dtype),
                "h": jnp.zeros((batch, lw), jnp.float32)}
    raise ValueError(kind)


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    pat, n, prefix, tail = stack_pattern(cfg)
    cache = {}
    for i, kind in enumerate(prefix):
        cache[f"prefix{i}"] = _layer_cache_zeros(kind, cfg, batch, max_len,
                                                 dtype)
    if n > 0:
        for s, kind in enumerate(pat):
            one = _layer_cache_zeros(kind, cfg, batch, max_len, dtype)
            cache[f"scan{s}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)
    for i, kind in enumerate(tail):
        cache[f"tail{i}"] = _layer_cache_zeros(kind, cfg, batch, max_len,
                                               dtype)
    # global scalars
    cap = kv_cache_capacity(cfg, max_len,
                            cfg.window or (cfg.local_window
                                           if cfg.family == "hybrid"
                                           else None))
    cache["pos"] = jnp.zeros((), jnp.int32)
    cache["slot_pos"] = empty_slot_pos(cap if cfg.family != "ssm" else 1)
    return cache


# ---------------------------------------------------------------------------
# continuous batching: per-slot cache (independent sequence per batch row)
# ---------------------------------------------------------------------------


def init_slot_cache(cfg, num_slots: int, max_len: int,
                    dtype=jnp.bfloat16) -> dict:
    """A decode cache whose ``pos``/``slot_pos`` are tracked PER SLOT:
    pos (C,) i32 and slot_pos (C, W) i32, so each batch row runs an
    independent sequence (admitted/evicted at any decode step)."""
    cache = init_cache(cfg, num_slots, max_len, dtype)
    cap = cache["slot_pos"].shape[0]
    cache["pos"] = jnp.zeros((num_slots,), jnp.int32)
    cache["slot_pos"] = jnp.broadcast_to(
        empty_slot_pos(cap), (num_slots, cap)).copy()
    return cache


# ---------------------------------------------------------------------------
# paged KV cache (block-table indirection; see repro.kvcache)
# ---------------------------------------------------------------------------


def paged_supported(cfg) -> tuple[bool, str]:
    """Whether the paged KV path applies to this config.

    Paging stores tokens by absolute position, so it requires full
    (non-windowed) attention layers and no recurrent/conv state; the
    sliding-window ring, SSM and RG-LRU states are O(window)/O(1)
    already — paging them buys nothing.
    """
    if cfg.family not in ("dense", "moe"):
        return False, (f"family {cfg.family!r} carries recurrent/cross "
                       "state the paged cache does not cover")
    if cfg.window is not None:
        return False, "sliding-window ring cache is already bounded"
    if cfg.frontend:
        return False, "multimodal prefix tokens not paged yet"
    return True, ""


def init_paged_cache(cfg, num_slots: int, num_blocks: int,
                     block_size: int, dtype=jnp.bfloat16) -> dict:
    """A paged decode cache: per-layer K/V page pools shared by ALL
    slots (``(num_blocks, block_size, KV, D)``; scanned layer groups
    carry a leading layer axis) plus per-slot ``pos`` (num_slots,) i32.
    Block tables ride as a separate operand of the decode step — they
    are host-managed by the engine's allocator.
    """
    ok, why = paged_supported(cfg)
    if not ok:
        raise NotImplementedError(f"paged KV cache: {why}")
    pat, n, prefix, tail = stack_pattern(cfg)

    def pages():
        if cfg.mla:
            # one latent a token shared by every head
            return {"ckv": jnp.zeros((num_blocks, block_size,
                                      cfg.latent_lanes), dtype)}
        return {"k": jnp.zeros((num_blocks, block_size, cfg.num_kv_heads,
                                cfg.head_dim), dtype),
                "v": jnp.zeros((num_blocks, block_size, cfg.num_kv_heads,
                                cfg.head_dim), dtype)}

    cache = {}
    for i, _ in enumerate(prefix):
        cache[f"prefix{i}"] = pages()
    if n > 0:
        for s, _ in enumerate(pat):
            one = pages()
            cache[f"scan{s}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)
    for i, _ in enumerate(tail):
        cache[f"tail{i}"] = pages()
    cache["pos"] = jnp.zeros((num_slots,), jnp.int32)
    if cfg.family == "moe":
        # decode launches' routed token-expert pairs and the experts
        # they reached, summed over MoE layers (``model.decode_step_paged``)
        cache["moe_count"] = jnp.zeros((2,), jnp.int32)
    return cache


def _is_layer(key: str) -> bool:
    return key.startswith(("prefix", "scan", "tail"))


def write_paged(cache: dict, one: dict, slot, table_row,
                seq_len: int) -> dict:
    """Scatter a freshly-prefilled single-sequence cache (batch dim 1,
    what ``model.prefill`` returns for a (1, S) batch with window=None:
    positions 0..S-1 at cache rows 0..S-1) into the page pool at the
    blocks named by ``table_row`` (nb,) i32, and set ``pos[slot]`` to
    ``seq_len``.  ``slot``/``table_row`` may be traced; ``seq_len`` is
    static (the admission prefill bucket), so one jitted executable
    serves every slot/table.
    """
    out = {}
    for key, big in cache.items():
        if key == "pos":
            out[key] = big.at[slot].set(jnp.asarray(seq_len, big.dtype))
        elif not _is_layer(key):
            out[key] = big
        else:
            if key.startswith("scan"):
                # leading layer axis: scatter each layer's pages with
                # the same (shared) table row
                out[key] = jax.tree.map(
                    lambda pages, o: jax.vmap(
                        lambda pg, sq: paged_lib.scatter_prefill(
                            pg, sq, table_row, seq_len)
                    )(pages, o[:, 0]),
                    big, one[key])
            else:
                out[key] = jax.tree.map(
                    lambda pages, o: paged_lib.scatter_prefill(
                        pages, o[0], table_row, seq_len),
                    big, one[key])
    return out


def prefill_chunk_paged(params: dict, x: Array, positions: Array,
                        table_row: Array, cfg, cache: dict,
                        use_pallas: bool = False):
    """Run ONE prompt chunk through the stack against the paged cache.

    x: (1, T, D) embedded chunk; positions: (T,) its absolute positions
    ``ctx_len .. ctx_len + T - 1`` (traced); table_row: (nb,) i32.
    Every attention layer scatters the chunk's K/V into its page pool
    at those positions and attends full-over-prefix / causal-in-chunk
    (``_attn_chunk_paged``).  Returns (x, new_cache, aux) — the caller
    (``model.prefill_chunk``) owns the final norm / logits / ``pos``
    bookkeeping.
    """
    ctx = {"positions": positions, "table_row": table_row,
           "use_pallas": use_pallas}
    return apply_stack(params, x, ctx, cfg, cache=cache, mode="chunk")


def prefill_chunks_paged_batched(params: dict, x: Array, ctx: dict, cfg,
                                 cache: dict):
    """Run one iteration's PACKED multi-chunk batch through the stack.

    x: (1, TT, D) embedded packed tokens (every scheduled chunk of the
    iteration back to back plus padding); ctx: the fused-chunk context
    (``positions``/``token_chunk``/``local``/``valid`` per packed row,
    ``meta`` rows ``[slot, ctx_len, chunk_len, q_offset]``,
    ``table_rows`` (C, nb), static ``chunk_pad`` and ``use_pallas``).
    Every attention layer scatters ALL chunks' K/V into its page pools
    and attends full-over-prefix / causal-in-chunk per chunk
    (``_attn_chunks_paged``) — one launch for the whole plan instead of
    one per chunk.  Returns (x, new_cache, aux); the caller
    (``model.prefill_chunks``) owns the final norm / per-chunk logits /
    ``pos`` bookkeeping.
    """
    return apply_stack(params, x, ctx, cfg, cache=cache, mode="chunks")


def copy_paged_block(cache: dict, src, dst) -> dict:
    """Copy-on-write page copy: duplicate physical block ``src`` into
    ``dst`` across every layer's K/V page pools (the prefix cache's
    full-match admission — see ``kvcache.prefix``).  ``src``/``dst``
    are traced scalars; scanned layer groups carry a leading layer
    axis, vmapped over so ``paged.copy_block`` is the single copy
    implementation.
    """
    copy = lambda pg: paged_lib.copy_block(pg, src, dst)  # noqa: E731
    out = {}
    for key, big in cache.items():
        if not _is_layer(key):
            out[key] = big
        elif key.startswith("scan"):
            out[key] = jax.tree.map(jax.vmap(copy), big)
        else:
            out[key] = jax.tree.map(copy, big)
    return out


def write_slot(cache: dict, one: dict, slot) -> dict:
    """Scatter a freshly-prefilled single-sequence cache (batch dim 1,
    scalar pos, (W,) slot_pos — exactly what ``model.prefill`` returns
    for a (1, S) batch) into row ``slot`` of a per-slot decode cache.

    Every per-layer KV/state row of the recycled slot is REPLACED and
    its slot_pos row reset, so no state from the evicted sequence can
    leak into the admitted one.  ``slot`` may be a traced index — the
    whole update jit-compiles to dynamic-update-slices.
    """
    out = {}
    for key, big in cache.items():
        if key == "pos":
            out[key] = big.at[slot].set(one["pos"].astype(big.dtype))
        elif key == "slot_pos":
            out[key] = big.at[slot].set(one["slot_pos"])
        else:
            # scanned layer caches carry a leading layer axis; batch is
            # axis 1 there and axis 0 for prefix/tail layer caches.
            ax = 1 if key.startswith("scan") else 0
            out[key] = jax.tree.map(
                lambda b, o: lax.dynamic_update_slice_in_dim(
                    b, o.astype(b.dtype), slot, axis=ax),
                big, one[key])
    return out
