"""Batched generation on top of model.prefill / model.decode_step.

Two drivers:
  * ``generate()`` — host-loop greedy decoding with early exit when every
    sequence hit EOS (used by the serving engine; the host loop is what a
    real-time scheduler interleaves with queue management).
  * ``generate_scan()`` — fully-jitted lax.scan decode for a fixed number
    of steps (used by benchmarks; no host round-trips).
"""

from __future__ import annotations

import functools
import logging
import weakref
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import model as model_lib, transformer
from repro.obs import log as obslog

PAD_ID = 0

logger = logging.getLogger(__name__)

#: obs.log key of the use_pallas auto-detection degradation
FALLBACK_KEY = "jnp-fallback"


def reset_fallback_warning() -> None:
    """Re-arm the rate-limited jnp-fallback warning.

    The engine calls this at every ``serve()`` start so the warning is
    emitted at least once PER SERVE, not per process — otherwise the
    first engine constructed in a long-lived multi-config process (or
    the first test in a session) consumes the warning and every later
    serve's silent CPU fallback goes unreported.  Occurrence COUNTS
    are never cleared (``repro.obs.log.FALLBACKS``): ``_result``
    reports them as ``fallback_events`` so the degradation is
    countable, not only greppable in stderr."""
    obslog.FALLBACKS.reset(FALLBACK_KEY)


def resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    """Resolve the ``use_pallas=None`` auto-detection: the compiled
    Pallas kernels on TPU, the exact jnp fallbacks elsewhere (the
    kernels would run in slow interpret mode).  Routes the silent
    fallback through the shared rate-limited ledger
    (``repro.obs.log``) — warned once per re-arm window AND counted
    every time."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
        if not use_pallas:
            obslog.warn_once(
                logger, FALLBACK_KEY,
                "use_pallas auto-detection: backend %r is not TPU — "
                "falling back to the exact jnp kernel paths (pass "
                "use_pallas=True to force the Pallas kernels in "
                "interpret mode)", jax.default_backend())
    return use_pallas


class JitExecutable:
    """A jitted entry point plus its AOT-compiled per-shape executables.

    Transparent to existing callers — ``__call__`` forwards to the jit
    function (trace-on-first-call as before).  The serving engine's
    warmup path additionally pins ahead-of-time executables per shape
    key: ``jax.jit(...).lower(avals).compile()`` does NOT populate the
    jit call cache, so the ``Compiled`` objects are stored here and
    invoked directly via ``call_aot`` — first-request TTFT then pays
    neither trace nor compile time.  A ``call_aot`` at an unwarmed key
    falls back to the jit function (static kwargs included), so warmup
    is strictly an optimization, never a correctness dependency.

    Every dispatch runs inside a ``jax.profiler.TraceAnnotation`` named
    scope (``dispatch:<name>`` — the factory kind, e.g.
    ``dispatch:ragged``), so a ``jax.profiler.trace()`` capture of a
    serve shows which executable each device launch belongs to; the
    annotation is a no-op when no profiler is attached.  A ``call_aot``
    that misses the AOT store runs under ``dispatch:<name>:jit`` instead
    (a trace then shows which launch could have compiled) and counts in
    ``aot_misses``, over the executable's life.
    """

    def __init__(self, fn, name: str = "jit"):
        self.fn = fn
        self.name = f"dispatch:{name}"
        self.jit_name = f"{self.name}:jit"
        self.aot: dict = {}
        self.aot_misses = 0

    def __call__(self, *args, **kwargs):
        with jax.profiler.TraceAnnotation(self.name):
            return self.fn(*args, **kwargs)

    def warm(self, key, args, static_kwargs: Optional[dict] = None):
        """AOT-compile for the abstract ``args`` (ShapeDtypeStruct
        pytrees) under ``key``; idempotent per key."""
        if key not in self.aot:
            self.aot[key] = self.fn.lower(
                *args, **(static_kwargs or {})).compile()
        return self.aot[key]

    def call_aot(self, key, *args, **static_kwargs):
        """Dispatch through the warmed executable for ``key`` when one
        exists (array args only — statics were baked at lower time),
        else through the jit function."""
        compiled = self.aot.get(key)
        if compiled is not None:
            with jax.profiler.TraceAnnotation(self.name):
                return compiled(*args)
        self.aot_misses += 1
        with jax.profiler.TraceAnnotation(self.jit_name):
            return self.fn(*args, **static_kwargs)


# Factory memo: values are held WEAKLY, keyed by (kind, cfg, ...), so
# an executable's lifetime is bounded by the engines that hold it —
# dropping every engine for a config drops its traces and AOT
# executables with it (the unbounded-growth fix for long-lived
# multi-config processes).  A small strong LRU rides alongside so the
# common churn pattern (tests constructing engine after engine for ONE
# config) keeps its executables hot across instances; its capacity is
# the hard bound on what the module itself keeps alive.
_fn_memo: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_fn_lru: "OrderedDict" = OrderedDict()
_FN_LRU_CAP = 8


def _memoized(key, build) -> JitExecutable:
    """Bounded factory memo: engines sharing a (hashable) key reuse ONE
    ``JitExecutable`` — one trace cache AND one AOT store — for as long
    as any of them (or the strong LRU) keeps it alive.  An unhashable
    key skips the memo.  The key's leading element is the factory kind
    and becomes the executable's profiler-annotation name."""
    name = key[0] if isinstance(key, tuple) and key else "jit"
    try:
        cached = _fn_memo.get(key)
    except TypeError:                      # unhashable cfg: no memo
        return JitExecutable(build(), name)
    if cached is None:
        cached = JitExecutable(build(), name)
        _fn_memo[key] = cached
    _fn_lru[key] = cached
    _fn_lru.move_to_end(key)
    while len(_fn_lru) > _FN_LRU_CAP:
        _fn_lru.popitem(last=False)
    return cached


def make_prefill_fn(cfg, max_len: int):
    def build():
        @functools.partial(jax.jit, static_argnames=())
        def prefill_fn(params, batch):
            return model_lib.prefill(params, cfg, batch, max_len)

        return prefill_fn

    return _memoized(("prefill", cfg, max_len), build)


def make_decode_fn(cfg):
    def build():
        @jax.jit
        def decode_fn(params, cache, token):
            return model_lib.decode_step(params, cfg, cache, token)

        return decode_fn

    return _memoized(("decode", cfg), build)


def make_decode_steps_fn(cfg):
    """Jitted multi-step decode window over a per-slot contiguous cache
    (``model.decode_steps``): ``num_steps`` (static) scan iterations in
    ONE launch, returning the (B, num_steps) window tokens the engine
    reads back in arrears.  ``num_steps=1`` is bit-identical to
    ``make_decode_fn``'s single step."""
    def build():
        @functools.partial(jax.jit, static_argnames=("num_steps",))
        def decode_steps_fn(params, cache, token, *, num_steps):
            return model_lib.decode_steps(params, cfg, cache, token,
                                          num_steps=num_steps)

        return decode_steps_fn

    return _memoized(("decode_steps", cfg), build)


def make_slot_prefill_fn(cfg, max_len: int):
    """Jitted continuous-batching admission: prefill one (1, S) request
    into slot ``slot`` of a per-slot decode cache.  The slot index is a
    traced operand, so ONE executable serves every slot."""
    def build():
        @jax.jit
        def slot_prefill_fn(params, cache, batch, slot):
            return model_lib.prefill_into_slot(params, cfg, cache, batch,
                                               slot, max_len)

        return slot_prefill_fn

    return _memoized(("slot_prefill", cfg, max_len), build)


def make_paged_prefill_fn(cfg, max_len: int):
    """Jitted paged admission: prefill one (1, S) request into the page
    pool at the blocks named by ``table_row``.  Slot index and table
    are traced operands, so ONE executable serves every admission."""
    def build():
        @jax.jit
        def paged_prefill_fn(params, cache, batch, slot, table_row):
            return model_lib.prefill_into_paged(params, cfg, cache, batch,
                                                slot, table_row, max_len)

        return paged_prefill_fn

    return _memoized(("paged_prefill", cfg, max_len), build)


def make_paged_decode_fn(cfg, use_pallas: Optional[bool] = None):
    """Jitted paged decode step; block tables ride as a per-call operand
    (the engine extends them host-side on block-boundary crossings).

    use_pallas: route attention through the Pallas
    ``paged_decode_attention`` kernel (no transient contiguous gather).
    ``None`` auto-selects: on TPU the compiled kernel, elsewhere the
    exact jnp gather fallback (the kernel would run in slow interpret
    mode there)."""
    use_pallas = resolve_use_pallas(use_pallas)

    def build():
        @jax.jit
        def paged_decode_fn(params, cache, token, tables):
            return model_lib.decode_step_paged(params, cfg, cache, token,
                                               tables,
                                               use_pallas=use_pallas)

        return paged_decode_fn

    return _memoized(("paged_decode", cfg, use_pallas), build)


def make_paged_decode_steps_fn(cfg, use_pallas: Optional[bool] = None):
    """Jitted paged multi-step decode window (``model.decode_steps_paged``):
    ``num_steps`` (static) scan iterations against the page pool in ONE
    launch.  Block tables are fixed across the window — the engine
    pre-extends them to ``kvcache.window_target_tokens`` — so the scan
    needs no host round-trip.

    ``cache`` is donated: the launch writes the window's tokens into the
    page pools in place, and the caller must not read the cache it
    passed in, only the one returned."""
    use_pallas = resolve_use_pallas(use_pallas)

    def build():
        @functools.partial(jax.jit, static_argnames=("num_steps",),
                           donate_argnames=("cache",))
        def paged_decode_steps_fn(params, cache, token, tables, *,
                                  num_steps):
            return model_lib.decode_steps_paged(
                params, cfg, cache, token, tables, num_steps=num_steps,
                use_pallas=use_pallas)

        return paged_decode_steps_fn

    return _memoized(("paged_decode_steps", cfg, use_pallas), build)


def make_chunk_prefill_fn(cfg, use_pallas: Optional[bool] = None):
    """Jitted chunked-prefill step: run one (1, T) prompt chunk of slot
    ``slot`` against the paged cache at traced context offset
    ``ctx_len``, scattering its K/V through ``table_row``.  Slot, table
    and offset are traced operands, so ONE executable serves every
    chunk of every request (one retrace per distinct chunk length).
    Memoized (weakly) per ``(cfg, use_pallas)``."""
    use_pallas = resolve_use_pallas(use_pallas)

    def build():
        @jax.jit
        def chunk_prefill_fn(params, cache, batch, slot, table_row,
                             ctx_len):
            return model_lib.prefill_chunk(params, cfg, cache, batch,
                                           slot, table_row, ctx_len,
                                           use_pallas=use_pallas)

        return chunk_prefill_fn

    return _memoized(("chunk", cfg, use_pallas), build)


def make_ragged_prefill_fn(cfg, use_pallas: Optional[bool] = None):
    """Jitted FUSED chunked prefill: every scheduled chunk of one
    engine iteration in a single launch (``model.prefill_chunks``).

    The packed token stream, per-token chunk ids, metadata rows
    ``[slot, ctx_len, chunk_len, q_offset]`` and per-chunk block
    tables all ride as traced operands; ``chunk_pad`` (the padded
    per-chunk view width) is static.  jit therefore memoizes one
    executable per padded shape key ``(padded_tokens, padded_chunks,
    padded_chunk_len)`` — the ``ChunkBatch.shape_key`` buckets —
    instead of retracing per ``(chunk_len, offset)`` pair.  Memoized
    (weakly) per ``(cfg, use_pallas)`` like ``make_chunk_prefill_fn``."""
    use_pallas = resolve_use_pallas(use_pallas)

    def build():
        @functools.partial(jax.jit, static_argnames=("chunk_pad",))
        def ragged_prefill_fn(params, cache, batch, token_chunk, meta,
                              tables, *, chunk_pad):
            return model_lib.prefill_chunks(params, cfg, cache, batch,
                                            token_chunk, meta, tables,
                                            chunk_pad=chunk_pad,
                                            use_pallas=use_pallas)

        return ragged_prefill_fn

    return _memoized(("ragged", cfg, use_pallas), build)


def make_copy_block_fn(cfg):
    """Jitted copy-on-write page copy: duplicate physical block ``src``
    into ``dst`` across every layer's page pools (the prefix cache's
    full-match admission).  ``src``/``dst`` ride as traced operands, so
    ONE executable serves every CoW copy."""
    del cfg  # the cache pytree fixes every shape

    def build():
        @jax.jit
        def copy_block_fn(cache, src, dst):
            return transformer.copy_paged_block(cache, src, dst)

        return copy_block_fn

    return _memoized(("copy_block",), build)


def generate(params, cfg, batch: dict, *, max_new_tokens: int,
             eos_id: int = 1, prefill_fn=None, decode_fn=None,
             max_lens=None):
    """Greedy-decode a batch. Returns (tokens (B, T<=max_new), lengths).

    max_lens: optional (B,) per-sequence output-length caps — a sequence
    stops contributing once it has produced its cap, but the batch keeps
    stepping until its LONGEST member finishes (the head-of-line effect
    run-to-completion batching suffers from, and the baseline the
    continuous-batching engine is measured against).
    """
    max_len = batch["tokens"].shape[1] + max_new_tokens + 8
    if cfg.frontend == "vision":
        max_len += cfg.num_patch_tokens
    prefill_fn = prefill_fn or make_prefill_fn(cfg, max_len)
    decode_fn = decode_fn or make_decode_fn(cfg)

    cache, last_logits = prefill_fn(params, batch)
    B = batch["tokens"].shape[0]
    token = jnp.argmax(last_logits, -1).astype(jnp.int32)[:, None]
    done = (token[:, 0] == eos_id)
    lengths = jnp.ones((B,), jnp.int32)
    if max_lens is not None:
        max_lens = jnp.asarray(max_lens, jnp.int32)
        done = done | (lengths >= max_lens)
    out = [token]
    for _ in range(max_new_tokens - 1):
        if bool(done.all()):
            break
        token, _, cache = decode_fn(params, cache, token)
        token = jnp.where(done[:, None], PAD_ID, token)
        lengths = lengths + (~done).astype(jnp.int32)
        done = done | (token[:, 0] == eos_id)
        if max_lens is not None:
            done = done | (lengths >= max_lens)
        out.append(token)
    return jnp.concatenate(out, axis=1), lengths


def generate_scan(params, cfg, batch: dict, *, max_new_tokens: int):
    """Fixed-length jitted decode (benchmarks / dry-run style)."""
    max_len = batch["tokens"].shape[1] + max_new_tokens + 8
    if cfg.frontend == "vision":
        max_len += cfg.num_patch_tokens

    @jax.jit
    def run(params, batch):
        cache, last_logits = model_lib.prefill(params, cfg, batch, max_len)
        token = jnp.argmax(last_logits, -1).astype(jnp.int32)[:, None]

        def body(carry, _):
            token, cache = carry
            nt, _, cache = model_lib.decode_step(params, cfg, cache, token)
            return (nt, cache), token

        (_, _), tokens = lax.scan(
            body, (token, cache), None, length=max_new_tokens)
        return tokens[:, :, 0].T                       # (B, T)

    return run(params, batch)
