"""Top-level model API: config -> init / train-loss / prefill / decode.

This is the single entry point the training loop, the serving engine and the
multi-pod dry-run all share:

  * ``init_params(key, cfg)``                 parameter pytree
  * ``lm_loss(params, cfg, batch)``           causal-LM loss for train_step
  * ``prefill(params, cfg, batch, max_len)``  build KV/recurrent cache
  * ``decode_step(params, cfg, cache, tok)``  one greedy decode step

Batch layout per family:
  dense / moe / ssm / hybrid :  {"tokens": (B, S) i32, "labels": (B, S) i32}
  vlm   : + {"patches": (B, num_patch_tokens, D) bf16} (stub ViT frontend);
          tokens/labels cover the S - num_patch_tokens text positions.
  encdec: + {"frames": (B, encoder_seq_len, D) bf16} (stub audio frontend);
          tokens/labels are the decoder sequence.

Labels < 0 are ignored in the loss.  Logit positions >= cfg.vocab_size
(vocab padding for shardability) are masked to -inf.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.sharding import context as shctx

from . import layers, multimodal, transformer

Array = jax.Array


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _param_dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def init_encoder(key: Array, cfg, dtype) -> dict:
    """Bidirectional encoder stack (seamless audio backbone)."""
    n = cfg.num_encoder_layers
    keys = jax.random.split(key, n)
    blocks = jax.vmap(
        lambda k: transformer.init_attn_mlp_block(k, cfg, dtype))(keys)
    return {"blocks": blocks, "final_ln": jnp.zeros((cfg.d_model,), dtype)}


def init_params(key: Array, cfg) -> dict:
    dtype = _param_dtype(cfg)
    k_embed, k_stack, k_enc, k_front = jax.random.split(key, 4)
    params = {
        "embed": layers.init_embedding(k_embed, cfg, dtype),
        "stack": transformer.init_stack(k_stack, cfg, dtype),
        "final_ln": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.family == "encdec":
        params["encoder"] = init_encoder(k_enc, cfg, dtype)
    if cfg.frontend or cfg.family == "encdec":
        params["frontend_proj"] = multimodal.init_projector(
            k_front, cfg, dtype)
    return params


# ---------------------------------------------------------------------------
# encoder forward (bidirectional, scanned)
# ---------------------------------------------------------------------------


def encode(params: dict, cfg, frames: Array, *, remat: bool = False) -> Array:
    """frames: (B, Te, D) stub frontend embeddings -> encoder memory."""
    x = multimodal.apply_projector(params["frontend_proj"], frames)
    x = shctx.constrain(x, ("batch", None, None))
    Te = x.shape[1]
    positions = jnp.arange(Te)

    def body(x, p):
        x = shctx.constrain(x, ("batch", "seq", None))
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = layers.attention_qkv(p["attn"], h, positions,
                                       cfg.rope_theta)
        attn = layers.chunked_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            causal=False)
        x = x + layers.attention_out(p["attn"], attn)
        x = x + layers.apply_mlp(
            p["mlp"], layers.rms_norm(x, p["ln2"], cfg.norm_eps),
            cfg.mlp_act)
        return x, None

    # without remat the encoder scan saves every per-layer attention
    # intermediate for the backward pass — tens of GiB at train_4k
    x, _ = lax.scan(jax.checkpoint(body) if remat else body, x,
                    params["encoder"]["blocks"])
    return layers.rms_norm(x, params["encoder"]["final_ln"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decoder input assembly
# ---------------------------------------------------------------------------


def _decoder_inputs(params: dict, cfg, batch: dict, *, remat: bool = False):
    """Returns (x, ctx, num_prefix) where num_prefix is the count of
    non-text positions (VLM patches) prepended before the tokens."""
    tokens = batch["tokens"]
    x = layers.embed(params["embed"], tokens, cfg)
    num_prefix = 0
    if cfg.frontend == "vision":
        patches = multimodal.apply_projector(
            params["frontend_proj"], batch["patches"]).astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        num_prefix = patches.shape[1]
    x = shctx.constrain(x, ("batch", None, None))
    S = x.shape[1]
    ctx = {"positions": jnp.arange(S), "enc_out": None}
    if cfg.family == "encdec":
        ctx["enc_out"] = encode(params, cfg, batch["frames"], remat=remat)
    return x, ctx, num_prefix


# ---------------------------------------------------------------------------
# loss (chunked over sequence so (B, S, V) logits never materialize)
# ---------------------------------------------------------------------------


def chunked_xent(params: dict, cfg, x: Array, labels: Array,
                 chunk: int = 1024):
    """x: (B, S, D) final hidden states; labels: (B, S) (<0 = ignore).

    Computes sum of per-token NLL and the token count, scanning over
    sequence chunks: peak logits memory is (B, chunk, V) instead of
    (B, S, V) — for the 1T MoE at train_4k that is the difference between
    ~343 MB/device and ~2.7 GB/device.
    """
    B, S, D = x.shape
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xs = x.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        nll_sum, count = carry
        xc, lc = inp
        logits = layers.logits(params["embed"], xc, cfg)     # (B, c, V) f32
        logits = shctx.constrain(logits, ("batch", None, "vocab"))
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        nll = (logz - ll) * mask
        return (nll_sum + nll.sum(), count + mask.sum()), None

    (nll_sum, count), _ = lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (xs, ls))
    return nll_sum, count


def lm_loss(params: dict, cfg, batch: dict, *, remat: bool = False):
    """Causal-LM loss. Returns (loss, metrics)."""
    x, ctx, num_prefix = _decoder_inputs(params, cfg, batch, remat=remat)
    x, _, aux = transformer.apply_stack(
        params["stack"], x, ctx, cfg, cache=None, mode="train", remat=remat)
    x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
    if num_prefix:
        x = x[:, num_prefix:]
    # gather the (possibly sequence-sharded) final hiddens: chunked_xent
    # scans over sequence chunks, and scanning over a sharded dim would
    # force GSPMD reshards inside the loop.
    x = shctx.constrain(x, ("batch", None, None))
    # next-token prediction: hidden state at position t predicts labels[t]
    nll_sum, count = chunked_xent(params, cfg, x, batch["labels"])
    xent = nll_sum / jnp.maximum(count, 1.0)
    loss = xent + aux["moe_aux_loss"]
    metrics = {
        "loss": loss,
        "xent": xent,
        "tokens": count,
        "moe_aux_loss": aux["moe_aux_loss"],
        "moe_drop_frac": aux["moe_drop_frac"],
    }
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: prefill + single-step decode
# ---------------------------------------------------------------------------


def prefill(params: dict, cfg, batch: dict, max_len: int,
            cache_dtype=jnp.bfloat16):
    """Run the prompt through the stack, building the decode cache.

    Returns (cache, last_logits) where last_logits: (B, V) are the logits
    at the final prompt position (the sampler consumes them).
    """
    x, ctx, _ = _decoder_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    cache = transformer.init_cache(cfg, B, max_len, cache_dtype)
    x, new_cache, _ = transformer.apply_stack(
        params["stack"], x, ctx, cfg, cache=cache, mode="prefill")
    x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
    last = x[:, -1]
    last_logits = layers.logits(params["embed"], last[:, None], cfg)[:, 0]
    # global decode bookkeeping
    cap = cache["slot_pos"].shape[0]
    new_cache["pos"] = jnp.asarray(S, jnp.int32)
    new_cache["slot_pos"] = transformer.prefill_slot_pos(cap, S)
    return new_cache, last_logits.astype(jnp.float32)


def decode_step(params: dict, cfg, cache: dict, token: Array):
    """One greedy decode step.

    token: (B, 1) i32 — the token sampled from the previous step's logits.
    Returns (next_token (B, 1) i32, logits (B, V) f32, new_cache).

    Works on both cache layouts: batch-mode (scalar ``pos``, (W,)
    ``slot_pos`` — every row at the same position) and per-slot
    continuous-batching caches from ``transformer.init_slot_cache``
    (``pos`` (B,), ``slot_pos`` (B, W) — independent sequences).
    """
    x = layers.embed(params["embed"], token, cfg)
    x = shctx.constrain(x, ("batch", None, None))
    ctx = {"pos": cache["pos"], "slot_pos": cache["slot_pos"]}
    x, new_cache, _ = transformer.apply_stack(
        params["stack"], x, ctx, cfg, cache=cache, mode="decode")
    x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = layers.logits(params["embed"], x, cfg)[:, 0]
    logits = logits.astype(jnp.float32)
    next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    # global bookkeeping (per-layer caches already updated in the stack)
    cap = cache["slot_pos"].shape[-1]
    pos = cache["pos"]
    new_cache["pos"] = pos + 1
    if pos.ndim:
        rows = jnp.arange(pos.shape[0])
        new_cache["slot_pos"] = cache["slot_pos"].at[rows, pos % cap].set(pos)
    else:
        new_cache["slot_pos"] = cache["slot_pos"].at[pos % cap].set(pos)
    return next_token, logits, new_cache


def decode_step_paged(params: dict, cfg, cache: dict, token: Array,
                      tables: Array, *, use_pallas: bool = False):
    """One greedy decode step against a paged KV cache.

    cache: from ``transformer.init_paged_cache`` (per-layer page pools
    + per-slot ``pos`` (B,)); tables: (B, nb) i32 block tables (host
    state of the engine's allocator, passed per step so boundary
    crossings need no cache rebuild).  Same contract as ``decode_step``:
    returns (next_token (B, 1) i32, logits (B, V) f32, new_cache).

    ``use_pallas`` (static) routes each layer's attention through the
    Pallas ``paged_decode_attention`` kernel instead of the transient
    contiguous gather — the production TPU path (interpret-mode
    emulation elsewhere); outputs match the gather path.
    """
    x = layers.embed(params["embed"], token, cfg)
    x = shctx.constrain(x, ("batch", None, None))
    ctx = {"pos": cache["pos"], "tables": tables, "use_pallas": use_pallas}
    x, new_cache, aux = transformer.apply_stack(
        params["stack"], x, ctx, cfg, cache=cache, mode="decode")
    x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = layers.logits(params["embed"], x, cfg)[:, 0]
    logits = logits.astype(jnp.float32)
    next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    new_cache["pos"] = cache["pos"] + 1
    if "moe_count" in cache:
        # the serve reads it once, at its end: no sync in the loop
        new_cache["moe_count"] = cache["moe_count"] + jnp.stack(
            [aux["moe_pairs"], aux["moe_experts"]]).astype(jnp.int32)
    return next_token, logits, new_cache


def decode_steps(params: dict, cfg, cache: dict, token: Array, *,
                 num_steps: int):
    """``num_steps`` greedy decode steps as ONE ``lax.scan`` launch (the
    async host pipeline's multi-step decode window).

    token: (B, 1) i32 — the previous step's sampled token for every row.
    Returns (tokens (B, num_steps) i32, new_cache): column j holds the
    token emitted by window step j; the last column is the next window's
    input.  The scan body is exactly ``decode_step``, so ``num_steps=1``
    is bit-identical to a single step — the engine's N=1 parity default.

    EOS/cap handling stays on the HOST at window end (in arrears): every
    row is stepped all ``num_steps`` times, and the caller discards the
    columns past a sequence's logical end.  The overhang writes are
    harmless by the eviction-lag invariant (``kvcache.allocator.
    window_target_tokens``) — the contiguous ring confines them to the
    dead row, the paged scatter clamps them onto the trash page.
    """
    def body(carry, _):
        tok, c = carry
        nt, _, c = decode_step(params, cfg, c, tok)
        return (nt, c), nt[:, 0]

    (_, new_cache), toks = lax.scan(
        body, (token, cache), None, length=num_steps)
    return toks.T, new_cache                          # (B, num_steps)


def decode_steps_paged(params: dict, cfg, cache: dict, token: Array,
                       tables: Array, *, num_steps: int,
                       use_pallas: bool = False):
    """Paged twin of ``decode_steps``: ``num_steps`` ``decode_step_paged``
    iterations in one ``lax.scan`` launch against the page pool.

    The block tables are fixed for the WHOLE window — the engine extends
    every active slot's table to ``window_target_tokens`` before the
    launch, so each step's scatter lands in a pre-backed (or trash)
    block and no host round-trip interrupts the scan.
    """
    def body(carry, _):
        tok, c = carry
        nt, _, c = decode_step_paged(params, cfg, c, tok, tables,
                                     use_pallas=use_pallas)
        return (nt, c), nt[:, 0]

    (_, new_cache), toks = lax.scan(
        body, (token, cache), None, length=num_steps)
    return toks.T, new_cache                          # (B, num_steps)


def prefill_into_paged(params: dict, cfg, cache: dict, batch: dict, slot,
                       table_row, max_len: int, cache_dtype=jnp.bfloat16):
    """Prefill ONE request (batch dim 1) and scatter its KV into the
    paged cache's blocks ``table_row`` (nb,) i32, marking ``slot``'s
    position (paged continuous-batching admission).  Returns
    (new_cache, last_logits (V,)).  Requires ``paged_supported(cfg)``
    (full attention, positions 0..S-1 land at prefill rows 0..S-1).
    """
    one, last_logits = prefill(params, cfg, batch, max_len, cache_dtype)
    S = batch["tokens"].shape[1]
    new_cache = transformer.write_paged(cache, one, slot, table_row, S)
    return new_cache, last_logits[0]


def prefill_chunk(params: dict, cfg, cache: dict, batch: dict, slot,
                  table_row, ctx_len, *, use_pallas: bool = False):
    """Run ONE chunk of a request's prompt against the paged cache.

    batch: {"tokens": (1, T)} — the chunk's token slice; ctx_len: traced
    i32 scalar, how many prompt tokens were already prefilled (the chunk
    occupies absolute positions ``ctx_len .. ctx_len + T - 1``);
    table_row: (nb,) i32 the sequence's block table (all of the prompt's
    blocks are allocated at admission, so every chunk position is
    backed).  Each attention layer scatters the chunk's K/V into the
    page pool at the correct position offset and attends full over the
    already-written prefix, causal within the chunk — per-position
    numerics match the stall-admission full prefill, so the final
    chunk's ``last_logits`` produce the identical first token.

    Returns (new_cache, last_logits (V,) f32) with ``pos[slot]`` set to
    ``ctx_len + T``; only the FINAL chunk's logits are meaningful to
    the sampler (they sit at the prompt's last position).  Requires
    ``transformer.paged_supported(cfg)``.
    """
    tokens = batch["tokens"]
    T = tokens.shape[1]
    x = layers.embed(params["embed"], tokens, cfg)
    x = shctx.constrain(x, ("batch", None, None))
    positions = (jnp.asarray(ctx_len, jnp.int32)
                 + jnp.arange(T, dtype=jnp.int32))
    x, new_cache, _ = transformer.prefill_chunk_paged(
        params["stack"], x, positions, table_row, cfg, cache,
        use_pallas=use_pallas)
    x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
    last = x[:, -1]
    last_logits = layers.logits(params["embed"], last[:, None], cfg)[:, 0]
    new_cache["pos"] = cache["pos"].at[slot].set(positions[-1] + 1)
    return new_cache, last_logits[0].astype(jnp.float32)


def prefill_chunks(params: dict, cfg, cache: dict, batch: dict,
                   token_chunk, meta, tables, *, chunk_pad: int,
                   use_pallas: bool = False):
    """Run EVERY scheduled prefill chunk of one engine iteration at
    once against the paged cache — the fused replacement for a loop of
    ``prefill_chunk`` calls (one launch per iteration, O(1) host
    dispatches instead of O(#chunks)).

    batch: {"tokens": (1, TT)} — the iteration's chunks PACKED back to
    back (chunk ``c`` owns columns ``q_off[c] .. q_off[c]+len[c]-1``)
    and padded to the executable's token bucket; token_chunk: (TT,)
    i32 mapping each packed column to its chunk row; meta: (C, 4) i32
    rows ``[slot, ctx_len, chunk_len, q_offset]`` (padding chunks:
    ``chunk_len == 0`` and ``slot`` out of range so their ``pos``
    update drops); tables: (C, nb) i32 per-chunk block tables;
    chunk_pad: STATIC padded max chunk length (the per-chunk view
    width).  Per-position numerics match sequential ``prefill_chunk``
    calls bit for bit (tests/test_chunked_prefill.py), so fusing never
    changes greedy output.

    Returns (new_cache, last_logits (C, V) f32): row ``c`` holds the
    logits at chunk ``c``'s LAST position — meaningful to the sampler
    only for chunks that finish their prompt.  ``pos[slot]`` is set to
    ``ctx_len + chunk_len`` for every real chunk.  Requires
    ``transformer.paged_supported(cfg)``.
    """
    tokens = batch["tokens"]
    TT = tokens.shape[1]
    token_chunk = jnp.asarray(token_chunk, jnp.int32)
    meta = jnp.asarray(meta, jnp.int32)
    slots, ctx_lens, lens, q_off = (meta[:, 0], meta[:, 1], meta[:, 2],
                                    meta[:, 3])
    local = jnp.arange(TT, dtype=jnp.int32) - q_off[token_chunk]
    positions = ctx_lens[token_chunk] + local
    valid = local < lens[token_chunk]
    x = layers.embed(params["embed"], tokens, cfg)
    x = shctx.constrain(x, ("batch", None, None))
    ctx = {"positions": positions, "token_chunk": token_chunk,
           "local": local, "valid": valid, "meta": meta,
           "table_rows": jnp.asarray(tables, jnp.int32),
           "chunk_pad": chunk_pad, "use_pallas": use_pallas}
    x, new_cache, _ = transformer.prefill_chunks_paged_batched(
        params["stack"], x, ctx, cfg, cache)
    x = layers.rms_norm(x, params["final_ln"], cfg.norm_eps)
    last_idx = jnp.clip(q_off + jnp.maximum(lens, 1) - 1, 0, TT - 1)
    last = jnp.take(x[0], last_idx, axis=0)            # (C, D)
    last_logits = layers.logits(params["embed"], last[None], cfg)[0]
    new_cache["pos"] = cache["pos"].at[slots].set(
        (ctx_lens + lens).astype(cache["pos"].dtype), mode="drop")
    return new_cache, last_logits.astype(jnp.float32)


def prefill_into_slot(params: dict, cfg, cache: dict, batch: dict, slot,
                      max_len: int, cache_dtype=jnp.bfloat16):
    """Prefill ONE request (batch dim 1) and write its state into row
    ``slot`` of a per-slot decode cache (continuous-batching admission).

    The evicted slot's KV/recurrent state is fully replaced.  Returns
    (new_cache, last_logits (V,)).  ``max_len`` must match the max_len
    the slot cache was built with so the ring capacities line up.
    """
    one, last_logits = prefill(params, cfg, batch, max_len, cache_dtype)
    return transformer.write_slot(cache, one, slot), last_logits[0]
