"""One run of one cell: set-up, the measured window, the readings and the
check of correctness.

``run.py`` is the command; the tests call ``Run`` directly at a tiny size
on the CPU.  Everything that belongs to one cell, configuration, traffic
mix or metric is found by name under ``rtbench/``:

  * ``cells/<workload>.json``   rate, engine sizes, the check's sample
                                and the limits of ``correct``;
  * ``configs/<config>.json``   the model as it is run, and the name of
                                its plain reference (``reference/``);
  * ``traffic/<mix>.json``      the mix, read by ``gen.py``;
  * ``metrics/<metric>.py``     one reader per metric of BENCHMARK.json.

The window drives the program's serving entry point as
``repro.launch.serve.make_engine`` builds it (continuous batching, paged
KV, chunked prefill, prefix cache, EOS off) under the cell's policy.
Requests are due in the first ``seconds`` of the engine's clock; the
serve returns when every one of them has completed.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import random
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from rtbench import flops, gen

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
WINDOW_SPAN = "rtbench:window"
BLOCK = 16                       # the program's KV block (tokens)
WARM_CHUNKS = 8                  # most chunks one warm-up launch packs
WARM_GAP_S = 100.0               # engine-clock gap between warm-up groups


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict                  # the workload's entry in BENCHMARK.json
    config: Dict                 # configs/<config>.json
    mix: Dict                    # traffic/<mix>.json
    knobs: Dict                  # cells/<workload>.json


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_bench(root: Path = ROOT) -> Dict:
    return _json(root / "BENCHMARK.json")


def load_cell(name: str, bench: Dict, here: Path = HERE) -> Cell:
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    entry = entries[name]
    return Cell(name=name, entry=entry,
                config=_json(here / "configs" / f"{entry['config']}.json"),
                mix=_json(here / "traffic" / f"{entry['traffic']}.json"),
                knobs=_json(here / "cells" / f"{name}.json"))


def metric_entries(bench: Dict, workload: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``workload`` reports: end-to-end ones
    untraced, per-layer ones traced; an entry with ``workloads`` only in
    the cells it lists."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def _load(path: Path, modname: str):
    """Import the module at ``path`` (a name may hold dots, so not by
    the import system's dotted names)."""
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, here: Path = HERE):
    """The reader module ``metrics/<name>.py`` (its ``read(run)``)."""
    return _load(here / "metrics" / f"{name}.py",
                 f"rtbench_metric_{name.replace('.', '_')}")


def reference(name: str, here: Path = HERE):
    """The plain reference module ``reference/<name>.py``."""
    return _load(here / "reference" / f"{name}.py", f"rtbench_ref_{name}")


def model_config(c: Dict):
    """The program's ModelConfig for a configuration file.  What the
    program cannot express is refused here, not run as something else."""
    from repro.configs import ModelConfig
    acts = {"gelu_pytorch_tanh": "gelu", "relu": "relu"}
    if (c["hidden_act"] not in acts or c.get("mlp_gated")
            or c.get("use_bias") or c.get("norm_type") != "rms_norm_1p"
            or c.get("partial_rotary_factor", 1.0) != 1.0
            or c.get("sliding_window")):
        raise ValueError(f"{c['name']}: the program's paged path runs "
                         "ungated, biasless, full-attention stacks with "
                         "(1 + w) RMSNorm and full rotary")
    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], vocab_size=c["vocab_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], mlp_act=acts[c["hidden_act"]],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c.get("norm_epsilon", c.get("norm_eps"))),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        source=c["source"])


# ---------------------------------------------------------------------------
# warm-up: every ragged prefill shape the window's traffic can reach
# ---------------------------------------------------------------------------


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def chunk_lengths(cell: Cell) -> List[int]:
    """The lengths one job's chunk can have in one launch: suffixes that
    the prefix cache leaves (whole KV blocks past a cached prefix, or one
    token after a whole-prompt hit), cut to ``chunk_size`` pieces and
    merged back up to the launch's token budget."""
    k = cell.knobs["engine"]
    S, chunk = k["input_bucket"], k["chunk_size"]
    budget = k["num_slots"] + chunk
    p = cell.mix["prompt"]
    if p["kind"] == "words":        # random words: no prompt repeats
        step = -(-p["length"].get("multiple", 1) // BLOCK) * BLOCK
        suffixes = set(range(step, S + 1, step)) | {S}
    else:
        suffixes = set(range(BLOCK, S + 1, BLOCK)) | {S, 1}
    out = set()
    for n in suffixes:
        full, tail = divmod(n, chunk)
        pieces = [chunk] * full + ([tail] if tail else [])
        # consecutive pieces of one job merge while they fit the budget
        for i in range(len(pieces)):
            tot = 0
            for q in pieces[i:]:
                if tot + q > budget:
                    break
                tot += q
                out.add(tot)
    return sorted(out)


def warm_groups(cell: Cell) -> List[List[int]]:
    """One list of chunk lengths for every ragged shape key (padded
    tokens, padded chunks, padded chunk length) that a launch of up to
    ``WARM_CHUNKS`` chunks within the token budget can have."""
    k = cell.knobs["engine"]
    budget = k["num_slots"] + k["chunk_size"]
    lengths = chunk_lengths(cell)
    best: Dict[tuple, List[int]] = {}

    def walk(i: int, chosen: List[int], tot: int) -> None:
        if chosen:
            key = (_pow2(tot), _pow2(len(chosen)), _pow2(max(chosen)))
            if key not in best or len(chosen) < len(best[key]):
                best[key] = list(chosen)
        if len(chosen) == WARM_CHUNKS:
            return
        for j in range(i, len(lengths)):
            if tot + lengths[j] > budget:
                break
            chosen.append(lengths[j])
            walk(j, chosen, tot + lengths[j])
            chosen.pop()

    walk(0, [], 0)
    return [best[key] for key in sorted(best)]


def warm_requests(cell: Cell, seed: int):
    """Requests that make the serve launch every key of ``warm_groups``,
    one group at a time: a group's prompts arrive together on an idle
    engine, each leaves a suffix of one of the group's lengths (fresh
    words after the cached all-pad blocks, or a repeat of an earlier
    prompt for a one-token suffix), and each asks for one token, so the
    group is one prefill launch."""
    from repro.serving.engine import Request
    S = cell.knobs["engine"]["input_bucket"]
    rng = random.Random(seed)

    def words(n):
        return " ".join(f"x{rng.randrange(10**9)}" for _ in range(n))

    # the first group fills the cache with the all-pad blocks and leaves
    # a prompt to repeat
    primer = words(1)
    texts = [[primer]]
    for group in warm_groups(cell):
        texts.append([primer if n == 1 else words(n) for n in group])
    reqs = []
    for g, group in enumerate(texts):
        for t in group:
            reqs.append(Request(text=t, arrival=g * WARM_GAP_S,
                                task_id=len(reqs), max_new_tokens=1))
    return reqs


# ---------------------------------------------------------------------------
# compiles inside the window
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts JAX's trace and compile events while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.events: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, duration: float, **_) -> None:
        if self.on and ("compile" in event or "trace" in event):
            self.events.append(event)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) \
        else None


def cached_upper(ids: List[np.ndarray]) -> List[int]:
    """For each prompt, the most positions the prefix cache could have
    spared it: whole leading blocks it shares with another prompt of the
    run (one position short of the whole prompt when all of it is
    shared).  An upper bound: the prompt that came first shared nothing."""
    counts: Dict[bytes, int] = {}
    for a in ids:
        for k in range(1, len(a) // BLOCK + 1):
            key = a[:k * BLOCK].tobytes()
            counts[key] = counts.get(key, 0) + 1
    out = []
    for a in ids:
        k = 0
        while (k < len(a) // BLOCK
               and counts[a[:(k + 1) * BLOCK].tobytes()] > 1):
            k += 1
        out.append(len(a) - 1 if k * BLOCK == len(a) else k * BLOCK)
    return out


class Run:
    """One run of a cell.  ``require_tpu=False`` skips the look for a
    chip (the tests drive the rest of a run on the CPU)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 *, t_start: float, out_dir: Path, log=print,
                 require_tpu: bool = True):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.t_start, self.out_dir = trace, t_start, out_dir
        self.log, self.require_tpu = log, require_tpu
        self.setup_parts: Dict[str, float] = {}

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.core import personas, scheduler as sched_lib
        from repro.launch import serve
        from rtbench import weights

        k = self.cell.knobs
        eng = k["engine"]
        t = time.perf_counter()
        self.requests = gen.generate(self.cell.mix, k["rate_per_s"],
                                     self.seconds, self.seed)
        too_long = max(r.out_len for r in self.requests)
        if too_long > eng["max_new_tokens"]:
            raise ValueError(f"a request asks for {too_long} tokens, the "
                             f"cell's engine caps {eng['max_new_tokens']}")
        self.cfg = model_config(self.cell.config)
        self.params = weights.make(self.cfg, self.seed)
        jax.block_until_ready(self.params)
        self.setup_parts["weights_s"] = time.perf_counter() - t

        t = time.perf_counter()
        pm = PROFILE
        persona = personas.get_persona(pm["persona"]["name"])
        corpus = gen.profile_corpus(pm["mix"], pm["persona"], pm["tasks"],
                                    pm["seed"])
        profile = sched_lib.offline_profile(corpus, persona,
                                            epochs=pm["epochs"],
                                            seed=pm["seed"])
        policy = sched_lib.POLICIES[k["policy"]](persona,
                                                 profile.policy_config())
        if getattr(policy, "offload", False):
            raise ValueError(f"policy {k['policy']!r} offloads to the "
                             "emulated CPU lane; no cell may use it")
        self.setup_parts["profile_s"] = time.perf_counter() - t

        t = time.perf_counter()
        setup = serve.Setup(cfg=self.cfg, params=self.params,
                            profile=profile, policy=policy, texts=[],
                            arrivals=[], max_new_tokens=eng["max_new_tokens"])
        self.engine = serve.make_engine(
            setup, input_bucket=eng["input_bucket"],
            chunk_size=eng["chunk_size"], num_slots=eng["num_slots"],
            kv_num_blocks=eng["kv_num_blocks"])
        warm = warm_requests(self.cell, self.seed)
        res = self.engine.serve(warm)
        self.warmup_compile_s = self.engine.warmup_s
        self.warm_fallbacks = res["fallback_events"]
        self.warm_keys = res["exec_cache_misses"]
        if self.require_tpu and self.warm_fallbacks:
            raise RuntimeError(f"{self.warm_fallbacks} kernel fallback "
                               "events in the warm-up serve")
        # the warm-up's page pool goes before the window builds its own
        self.engine.paged_cache = None
        self.engine.prefix_cache = None
        del res
        gc.collect()
        self.setup_parts["engine_warm_s"] = time.perf_counter() - t
        self.setup_s = time.perf_counter() - self.t_start

    # -- the window -------------------------------------------------------
    def window(self) -> None:
        import jax
        from repro.serving.engine import Request
        reqs = [Request(text=r.text, arrival=r.arrival, task_id=i,
                        max_new_tokens=r.out_len)
                for i, r in enumerate(self.requests)]
        counter = CompileCounter()
        self.engine.paged_cache = None       # a pool from an earlier serve
        gc.collect()
        tdir = str(self.out_dir / "trace")
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tdir, profiler_options=opts)
        # the engine adds up its admission time over its life
        sched0 = self.engine.scheduler_overhead_s
        counter.on = True
        t0, cpu0 = time.perf_counter(), time.process_time()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            res = self.engine.serve(reqs)
        self.wall_s = time.perf_counter() - t0
        self.cpu_s = time.process_time() - cpu0
        counter.on = False
        self.window_compiles = list(counter.events)
        if self.trace:
            jax.profiler.stop_trace()
        if self.require_tpu and res["fallback_events"]:
            raise RuntimeError(f"{res['fallback_events']} kernel fallback "
                               "events in the window")
        self.res = {k: v for k, v in res.items() if k != "tasks"}
        self.sched_overhead_s = res["scheduler_overhead_s"] - sched0
        self.served = reqs
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        self.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count(),
                       "memory_peak_bytes": self.memory_peak_bytes}
        self.trace_summary = None
        if self.trace:
            from rtbench import trace_reduce
            tr = trace_reduce.load(trace_reduce.find(tdir))
            self.trace_summary = trace_reduce.reduce(
                tr, trace_reduce.host_window(tr, WINDOW_SPAN))
            self.device["busy_s"] = self.trace_summary["busy_s"]
            self.device["window_s"] = self.trace_summary["window_s"]

    # -- what the readers read ---------------------------------------------
    def derive(self) -> None:
        S = self.cell.knobs["engine"]["input_bucket"]
        V = self.cell.config["vocab_size"]
        done = [r for r in self.served if r.token_times]
        self.ttft = [r.token_times[0] - r.arrival for r in done]
        self.itl = [float(d) for r in done for d in np.diff(r.token_times)]
        self.response = [r.finish - r.arrival for r in done]
        self.queue_wait = [r.queue_wait_s for r in done
                           if r.queue_wait_s >= 0]
        self.tokens = sum(len(r.out_tokens) for r in self.served)
        self.ids = [gen.hash_ids(r.text, V, S) for r in self.served]
        shape = flops.Shape.from_config(self.cell.config)
        cached = cached_upper(self.ids)
        self.cached_total = int(self.res.get("cached_tokens_reused", 0))
        self.cached_bound = int(sum(cached))
        self.work = {
            "prefill": flops.prefill_work(shape, [(S, c) for c in cached]),
            "decode": flops.decode_work(
                shape, [(S, len(r.out_tokens)) for r in self.served]),
        }

    # -- correctness --------------------------------------------------------
    def check(self) -> Dict[str, Dict]:
        """Every number compared, with its limit; ``correct`` is that each
        is within its limit."""
        import jax
        k = self.cell.knobs
        short = [i for i, r in enumerate(self.served)
                 if len(r.out_tokens) != self.requests[i].out_len]
        checks = {"requests_short": {"value": len(short), "limit": 0}}
        sample = self.sample()
        # the program's state goes before the reference runs
        self.engine = None
        gc.collect()
        ref = reference(self.cell.config["reference"])
        spec = ref.Spec.from_config(self.cell.config)
        gaps, nan = [], 0
        for i in sample:
            seq, tgt, n = self._rows(i)
            mx, at, _ = ref.score(
                self.params, jax.numpy.asarray(seq), jax.numpy.asarray(tgt),
                spec=spec, first=k["engine"]["input_bucket"] - 1,
                rows=k["engine"]["max_new_tokens"])
            g = np.asarray(mx - at)[:n]
            nan += int(np.isnan(g).sum())
            gaps.append(float(np.nanmax(g)))
        self.sampled = sample
        self.sample_tokens = sum(len(self.served[i].out_tokens)
                                 for i in sample)
        self.repeats = _repeat_share([self._rows(i) for i in sample])
        checks["served_logit_gap"] = {
            "value": max(gaps) if gaps else float("inf"),
            "limit": self.cell.knobs["limits"]["served_logit_gap"]}
        checks["nan_logits"] = {"value": nan, "limit": 0}
        return checks

    def _rows(self, i: int):
        """Request ``i``'s prompt and served tokens as the reference's
        input, and its served tokens as targets (padded to the cell's
        largest answer, so one compile serves every request)."""
        k = self.cell.knobs["engine"]
        S, rows = k["input_bucket"], k["max_new_tokens"]
        toks = np.asarray(self.served[i].out_tokens, np.int32)
        seq = np.zeros((S + rows - 1,), np.int32)
        seq[:S] = self.ids[i]
        seq[S:S + len(toks) - 1] = toks[:-1]
        tgt = np.zeros((rows,), np.int32)
        tgt[:len(toks)] = toks
        return seq, tgt, len(toks)

    def control_gap(self) -> float:
        """The control's reading on the check's sample: at each position
        the token that the reference computed in float8 puts first, and
        how far its float32 logit lies below the float32 best."""
        import jax.numpy as jnp
        ref = reference(self.cell.config["reference"])
        spec = ref.Spec.from_config(self.cell.config)
        k = self.cell.knobs["engine"]
        kw = dict(spec=spec, first=k["input_bucket"] - 1,
                  rows=k["max_new_tokens"])
        worst = 0.0
        for i in self.sampled:
            seq, tgt, n = self._rows(i)
            seq = jnp.asarray(seq)
            _, _, pick = ref.score(self.params, seq, jnp.asarray(tgt),
                                   quant="fp8", **kw)
            mx, at, _ = ref.score(self.params, seq, pick, **kw)
            worst = max(worst, float(np.max(np.asarray(mx - at)[:n])))
        return worst

    def sample(self) -> List[int]:
        """Requests to compare: the longest served, then others drawn from
        the seed until ``check_tokens`` served tokens are covered."""
        done = [i for i, r in enumerate(self.served) if r.out_tokens]
        if not done:
            return []
        want = self.cell.knobs["check_tokens"]
        longest = max(done, key=lambda i: len(self.served[i].out_tokens))
        rng = np.random.default_rng(self.seed)
        order = [i for i in rng.permutation(done) if i != longest]
        out, n = [longest], len(self.served[longest].out_tokens)
        for i in order:
            if n >= want:
                break
            out.append(int(i))
            n += len(self.served[i].out_tokens)
        return out


def _repeat_share(rows) -> float:
    """Share of served tokens equal to the token before them (a random
    model stuck on one token compares nothing near a tie)."""
    same = total = 0
    for seq, tgt, n in rows:
        k = len(seq) - len(tgt) + 1          # position of the first answer
        prev = seq[k - 1:k - 1 + n]
        same += int((prev == tgt[:n]).sum())
        total += n
    return same / total if total else 0.0


# the program's offline profile: its predictor is trained on chat
# utterances of the BST-like "normal" mix labelled with the dialogpt
# persona's output lengths, one fixed set for every run
PROFILE = {"persona": {"name": "dialogpt", "base_output": 8.0,
                       "uncertainty_gain": 2.6, "noise_std": 2.5,
                       "max_output": 128},
           "tasks": 256, "epochs": 40, "seed": 1234,
           "mix": {"plain": 0.30, "structural": 0.08, "syntactic": 0.07,
                   "semantic": 0.15, "vague": 0.15, "open_ended": 0.15,
                   "multi_part": 0.10}}


def execute(run: Run, bench: Dict, log=print) -> Dict:
    """Set up, serve the window, read the metrics and check the output;
    returns the result line.  The numbers compared go last on standard
    error, each beside its limit, and last in the result under
    ``checks``."""
    run.setup()
    log(f"setup: {json.dumps(run.setup_parts)} setup_s {run.setup_s:.3f} "
        f"warm-up keys {run.warm_keys} warmup_compile_s "
        f"{run.warmup_compile_s:.3f}")
    run.window()
    run.derive()
    res = run.res
    log(f"window: {len(run.requests)} requests, {run.tokens} tokens, serve "
        f"wall {run.wall_s:.3f} s (process CPU {run.cpu_s:.3f} s), compiles "
        f"inside the window "
        f"{len(run.window_compiles)}, ragged keys {res['exec_cache_misses']}"
        f", prefill launches {res['prefill_dispatches']}, decode launches "
        f"{res['decode_dispatches']} ({res['decode_steps_executed']} steps), "
        f"peak concurrency "
        f"{res['peak_concurrency']}, rejected for memory "
        f"{res['rejected_for_memory']}, cached prompt tokens "
        f"{run.cached_total} (bound used for the prefill work "
        f"{run.cached_bound})")
    if run.trace_summary:
        t = run.trace_summary
        log(f"trace: window {t['window_s']:.3f} s busy {t['busy_s']:.3f} s "
            f"executables {json.dumps(t['module_s'])} kernels "
            f"{json.dumps(t['kernel_s'])} launches {json.dumps(t['launches'])}")
    metrics = {}
    for m in metric_entries(bench, run.cell.name, run.trace):
        value = reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    t = time.perf_counter()
    checks = run.check()
    log(f"reference: {len(run.sampled)} requests, {run.sample_tokens} "
        f"served tokens compared in {time.perf_counter() - t:.1f} s, "
        f"{run.repeats:.3f} of them repeat the token before")
    failed = checks["requests_short"]["value"]
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(run.requests), "failed": failed,
              "metrics": metrics, "device": run.device}
    if run.trace_summary:
        result["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result
