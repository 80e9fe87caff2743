"""Smoke run of the production serving path on a TPU.

    python chip_smoke.py              # one chip: StarCoder2-3B at published widths
    python chip_smoke.py --chips 4    # four chips: replicas on their own chips

With no option it serves a Poisson trace of requests through the normal
entry point (``repro.launch.serve``): continuous batching over a paged
KV pool, chunked prefill and the prefix cache, with random weights from
``--seed``.  It checks that every request got its full token count,
that no kernel fell back to jnp or to interpret mode, and that the two
Pallas-routed executables (paged decode, fused ragged prefill) agree
with their jnp paths on one identical input.

``--chips 4`` runs only the replicated path and what it is compared
with: four replicas each committed to its own chip, against the same
four replicas time-sharing chip 0.  Placements and tokens must agree.

Everything runs in this one process.  The script exits non-zero, and
prints no result, when JAX finds no TPU or the program is missing.
Its last line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "starcoder2-3b"
N_REQUESTS = 24
INPUT_BUCKET = 512
MAX_NEW_TOKENS = 32
CHUNK_SIZE = 256
NUM_SLOTS = 16
KV_NUM_BLOCKS = 2048          # 32768 tokens of KV
RAGGED_PAD = 512              # chunk pad of the ragged parity check
REPLICA_REQUESTS = 16
REPLICA_BUCKET = 128

# Pallas against jnp, on logits: bf16 keeps 8 significant bits, so each
# rounding of an activation moves it by up to 2^-8 of its size.  The two
# paths round attention outputs at different points (f32 online softmax
# in the kernel, bf16 operands in the jnp path) in each of the 30
# layers, and a few such roundings reaching the logits is expected:
# allow 2^-5 (eight bf16 steps) of the largest logit.  A wrong mask, a
# wrong page or a stale scatter moves logits by their own size.
LOGIT_TOL_REL = 2.0 ** -5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(need: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX runs on {devs[0].platform!r}")
    check(len(devs) >= need, f"--chips {need} needs {need} TPU chips, "
          f"JAX sees {len(devs)}")
    return devs


def n_params(params) -> int:
    import jax
    return sum(int(x.size) for x in jax.tree.leaves(params))


def pool_bytes(state) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(state))


# ---------------------------------------------------------------------------
# one chip: serve a trace at published widths
# ---------------------------------------------------------------------------


def serve_phase(setup):
    """Serve ``setup``'s trace through the production engine and check
    every request's output.  Returns the engine (its pool holds the
    serve's KV) and the result."""
    from repro.launch import serve

    engine = serve.make_engine(setup, input_bucket=INPUT_BUCKET,
                               chunk_size=CHUNK_SIZE, num_slots=NUM_SLOTS,
                               kv_num_blocks=KV_NUM_BLOCKS)
    reqs = setup.requests()
    t0 = time.perf_counter()
    res = engine.serve(reqs)
    wall = time.perf_counter() - t0
    kvc = engine.paged_cache
    log(f"kv_pool: {kvc.num_blocks} blocks x {kvc.block_size} tokens "
        f"(+1 trash), {pool_bytes(kvc.state) / 2**30:.3f} GiB")
    log(f"warmup_compile_s: {engine.warmup_s:.1f}")
    log(f"serve_wall_s (warm-up and first-call compiles included): "
        f"{wall:.1f}")
    done = res["tasks"]
    tokens = sum(len(t.task.out_tokens) for t in done)
    log(f"requests_completed: {len(done)} / {len(reqs)} sent")
    log(f"tokens_generated: {tokens}")
    log(f"fallback_events: {res['fallback_events']}")
    log(f"exec_cache_misses: {res['exec_cache_misses']} "
        f"(hits {res['exec_cache_hits']})")
    check(len(done) == len(reqs),
          f"{len(reqs) - len(done)} requests did not complete")
    short = [t.task.task_id for t in done
             if t.task.out_len != setup.max_new_tokens
             or len(t.task.out_tokens) != setup.max_new_tokens]
    check(not short, f"requests {short} did not produce "
          f"{setup.max_new_tokens} tokens")
    check(res["fallback_events"] == 0, "a kernel fell back to jnp")
    warmed = engine.warmed_executables()
    log(f"warmed_executables: {sorted(warmed)}")
    # the CoW page copy is a plain copy with no attention, the one
    # warmed executable that routes nothing through Pallas
    missing = [name for name, c in warmed.items()
               if "copy_block" not in name
               and "tpu_custom_call" not in c.as_text()]
    check(not missing, f"no Pallas kernel in {missing}")
    return engine, res


# ---------------------------------------------------------------------------
# one chip: Pallas-routed executables against their jnp paths
# ---------------------------------------------------------------------------


def _compare(name: str, got, want, rows) -> float:
    import numpy as np
    a = np.asarray(got, np.float32)[rows]
    b = np.asarray(want, np.float32)[rows]
    check(np.isfinite(a).all() and np.isfinite(b).all(),
          f"{name}: non-finite logits")
    diff = float(np.abs(a - b).max())
    scale = float(np.abs(b).max())
    log(f"{name}: max_abs_logit_diff {diff:.6g} (largest logit "
        f"{scale:.6g}, tolerance {LOGIT_TOL_REL * scale:.6g})")
    check(diff <= LOGIT_TOL_REL * scale,
          f"{name}: Pallas and jnp logits differ by {diff}")
    return diff


def parity_phase(setup, engine, *, seed: int = 0) -> None:
    """On the pool the serve left behind: sequence A's first half is
    prefilled through the jnp path, then one ragged launch at chunk pad
    ``RAGGED_PAD`` prefills A's second half (a prefix of 256 tokens on
    the pages) together with all of sequence B, once through Pallas and
    once through jnp; then one decode step of both sequences runs both
    ways on the jnp launch's pool."""
    import numpy as np
    import jax.numpy as jnp
    from repro.prefill import build_packed_arrays, suffix_shape_key
    from repro.serving import generate

    cfg, params = setup.cfg, setup.params
    kvc = engine.paged_cache
    C, nb, bs = kvc.num_slots, kvc.max_blocks_per_seq, kvc.block_size
    S = RAGGED_PAD
    rng = np.random.default_rng(seed)
    toks = rng.integers(2, cfg.vocab_size, size=(2, S), dtype=np.int32)
    rows = [np.arange(i * S // bs, (i + 1) * S // bs, dtype=np.int32)
            for i in range(2)]

    def launch(fn, cache, key, entries):
        tokens, token_chunk, meta, tables = build_packed_arrays(
            key, entries, pad_slot=C, table_width=nb,
            trash_block=kvc.trash_block)
        return fn(params, cache, {"tokens": jnp.asarray(tokens)},
                  jnp.asarray(token_chunk), jnp.asarray(meta),
                  jnp.asarray(tables), chunk_pad=key[2])

    ref_fn = generate.make_ragged_prefill_fn(cfg, use_pallas=False)
    pal_fn = generate.make_ragged_prefill_fn(cfg, use_pallas=True)
    half = S // 2
    cache, _ = launch(ref_fn, kvc.state, suffix_shape_key(half),
                      [(0, 0, toks[0, :half], rows[0])])
    key = (2 * S, 2, S)
    entries = [(0, half, toks[0, half:], rows[0]), (1, 0, toks[1], rows[1])]
    cache_ref, logits_ref = launch(ref_fn, cache, key, entries)
    _, logits_pal = launch(pal_fn, cache, key, entries)
    _compare(f"ragged_prefill[chunk_pad={S}]", logits_pal, logits_ref,
             slice(0, 2))

    tables = np.full((C, nb), kvc.trash_block, np.int32)
    tables[0, :len(rows[0])] = rows[0]
    tables[1, :len(rows[1])] = rows[1]
    token = np.zeros((C, 1), np.int32)
    token[:2, 0] = np.asarray(logits_ref).argmax(-1)
    args = (params, cache_ref, jnp.asarray(token), jnp.asarray(tables))
    _, dec_ref, _ = generate.make_paged_decode_fn(cfg, False)(*args)
    _, dec_pal, _ = generate.make_paged_decode_fn(cfg, True)(*args)
    _compare("paged_decode_step", dec_pal, dec_ref, slice(0, 2))


def one_chip(seed: int) -> None:
    from repro.launch import serve

    t0 = time.perf_counter()
    setup = serve.build(ARCH, n_requests=N_REQUESTS,
                        max_new_tokens=MAX_NEW_TOKENS, seed=seed)
    cfg = setup.cfg
    log(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"params={n_params(setup.params)} "
        f"(setup {time.perf_counter() - t0:.1f}s)")
    engine, _ = serve_phase(setup)
    parity_phase(setup, engine, seed=seed)


# ---------------------------------------------------------------------------
# four chips: replicas on their own chips against replicas sharing chip 0
# ---------------------------------------------------------------------------


def replica_phase(seed: int, devices) -> None:
    """Serve one trace twice through ``ReplicatedEngine(replicas=4)``:
    once spread over ``devices`` (one replica per device), once with all
    four replicas on ``devices[0]``.  Placements and every request's
    tokens must agree, and each replica's params and pool must sit on
    its own device."""
    import jax
    from repro.launch import serve

    R = len(devices)
    setup = serve.build(ARCH, n_requests=REPLICA_REQUESTS,
                        max_new_tokens=MAX_NEW_TOKENS, seed=seed)
    runs = {}
    for name, devs in (("spread", devices), ("shared", devices[:1])):
        eng = serve.make_engine(setup, input_bucket=REPLICA_BUCKET,
                                chunk_size=CHUNK_SIZE, num_slots=NUM_SLOTS,
                                replicas=R, devices=devs)
        t0 = time.perf_counter()
        res = eng.serve(setup.requests())
        wall = time.perf_counter() - t0
        tokens = {t.task.task_id: list(t.task.out_tokens)
                  for rr in res["per_replica"] if rr is not None
                  for t in rr["tasks"]}
        check(len(tokens) == len(setup.texts),
              f"{name}: {len(setup.texts) - len(tokens)} requests lost")
        for r, e in enumerate(eng.engines):
            want = {devs[r % len(devs)]}
            for what, tree in (("params", e.params),
                               ("kv pool", e.paged_cache.state)):
                got = {d for x in jax.tree.leaves(tree) for d in x.devices()}
                check(got == want, f"{name}: replica {r} {what} on {got}, "
                      f"expected {want}")
        log(f"replicas_{name}: devices "
            f"{[str(e.device) for e in eng.engines]} placements "
            f"{res['placement_counts']} fallback_events "
            f"{res['fallback_events']} wall {wall:.1f}s")
        check(res["fallback_events"] == 0, f"{name}: jnp fallback")
        runs[name] = (res["placements"], tokens)
    check(runs["spread"][0] == runs["shared"][0],
          "placements differ between spread and shared replicas")
    same = sum(runs["spread"][1][i] == runs["shared"][1][i]
               for i in runs["spread"][1])
    log(f"replica_tokens_identical: {same} / {len(runs['spread'][1])} "
        "requests")
    check(same == len(runs["spread"][1]),
          "tokens differ between spread and shared replicas")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        fail(f"the program is not next to chip_smoke.py: {e}")
    devs = tpu_devices(args.chips)
    log(f"device_kind: {devs[0].device_kind} (count {len(devs)})")
    log(f"compile_cache: {compile_cache.enable()}")
    if args.chips == 4:
        replica_phase(args.seed, devs[:4])
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
