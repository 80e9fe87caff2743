"""Real serving engine: RT-LM scheduling over the actual JAX model.

This is the end-to-end integration of the paper's ecosystem with the
model substrate: requests (text + arrival time) flow through RULEGEN ->
m_theta -> the UASCHED policy, and execution happens on the REAL batched
prefill/greedy-decode JAX engine (tiny configs on CPU; the same code
path jit-lowers for the production mesh).

Two execution modes:

  * ``mode="batch"`` — the paper's run-to-completion model: the policy
    forms whole batches, each batch decodes until its LONGEST member
    finishes (head-of-line blocking on output-length variance — exactly
    the pathology RT-LM quantifies).
  * ``mode="continuous"`` — iteration-level batching: a persistent
    decode loop over C slots backed by one preallocated per-slot KV
    cache (transformer.init_slot_cache).  Finished sequences are evicted
    PER DECODE STEP and the policy's ``admit`` is consulted to fill each
    freed slot (uncertainty-aware admission instead of batch formation).
    Admission prefills the request into its slot through one jitted
    executable (bucketed (1, input_bucket) shape, traced slot index);
    the decode step reuses one jitted (C, 1) executable throughout.

Continuous mode takes a KV-cache layout, ``kv="contiguous"`` (default)
or ``kv="paged"``:

  * contiguous — each slot owns a private (max_len,) KV ring; memory is
    pinned to ``num_slots * max_len`` regardless of live tokens.
  * paged — one pool of ``kv_num_blocks`` fixed-size blocks shared by
    all slots (repro.kvcache): a sequence holds a block table, admission
    reserves its worst case ``ceil((S + cap - 1)/block_size)`` blocks
    (deadlock-free: a boundary crossing can never find the pool empty),
    physical blocks are allocated lazily when decode crosses a block
    boundary, and eviction returns every block to the free list.  A
    request whose reservation does not fit is REJECTED for memory
    (left queued; counted in the results) — the admission gate the
    simulator's block-budget model mirrors exactly.  Decode runs the
    same (C, 1) executable against gathered block-table views, so paged
    output is token-for-token identical to contiguous; with
    ``num_slots`` raised above the persona batch size at the same KV
    budget, paging admits strictly more concurrent sequences.

With ``prefix_cache=True`` (requires ``kv="paged"``), admission first
looks up the longest CACHED prefix of the padded prompt bucket in a
content-hash index over previously written blocks
(``repro.kvcache.prefix``): matched blocks are shared read-only into
the new sequence's table (per-block refcounts), prefill runs only from
the first uncached position (through the traced-offset chunk
executable), a full-prompt match copy-on-writes its last block so the
final position's logits can be recomputed, and cached blocks nobody
references are LRU-evicted only under pool pressure.  Output stays
token-for-token identical with the cache on or off; the simulator
drives the same ``PrefixCache`` host-side, so hit/CoW/eviction counts
and completion order agree bit-for-bit (tests/test_prefix_cache.py).

Adaptation note (DESIGN.md §2): a CPU-only container has no heterogeneous
co-processor, so the "CPU lane" is a *bulk lane* — a second execution
queue drained only when the main lane is idle, emulating resource
isolation of high-uncertainty tasks.  On a TPU pod the same lane maps to
a dedicated low-priority replica slice.

Batches are padded to (policy.max_batch(), input_bucket) — b * C for the
consolidating UASCHED policies, C otherwise — so a dynamically
consolidated batch executes as ONE batch (as the simulator models it)
and the jitted prefill/decode executables are reused across batches.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import priority as prio
from repro.core import scheduler as sched_lib
from repro.core.simulator import _pct as pct  # noqa: F401 - re-exported
from repro.core.personas import Persona
from repro.kvcache import (BlockAllocator, blocks_for_tokens,
                           decode_pages_walked, window_target_tokens)
from repro.kvcache.paged import PagedKVCache
from repro.kvcache.prefix import PrefixCache
from repro.models import transformer
from repro.obs import Observability
from repro.obs import log as obslog
from repro.obs.metrics import Histogram
from repro.prefill import (ChunkScheduler, build_packed_arrays, pack_plans,
                           suffix_shape_key)

from . import generate
from .faults import shed_pass
from .pipeline import CompletionWorker, host_phase

EOS_ID = 1
# max_len headroom past input_bucket + max_new_tokens.  It doubles as
# the multi-step decode window's OVERHANG budget: with readback in
# arrears a slot may be stepped up to decode_steps - 1 times past its
# logical end, and those dead-row writes must stay inside the slot's
# own ring (contiguous) / its table's clamp range (paged) — hence the
# constructor's ``decode_steps - 1 <= _MAX_LEN_SLACK`` validation.
_MAX_LEN_SLACK = 8


def params_device(params):
    """The one device an engine's parameters live on.  The engine puts
    its KV pool, block tables and compiled executables there too, so R
    replicas in one process each stay on the device their params were
    committed to.  Uncommitted params resolve to the default device."""
    devs = {d for leaf in jax.tree.leaves(params) for d in leaf.devices()}
    if len(devs) > 1:
        raise ValueError(f"engine params span several devices: {devs}")
    return devs.pop() if devs else jax.devices()[0]


def hash_tokenize(text: str, vocab_size: int, max_len: int) -> List[int]:
    """Toy deterministic tokenizer: word -> stable hash id (2..V-1)."""
    toks = []
    for w in text.lower().split()[:max_len]:
        h = 2166136261
        for c in w.encode():
            h = ((h ^ c) * 16777619) & 0xFFFFFFFF
        toks.append(2 + (h % (vocab_size - 2)))
    return toks or [2]


def tokenize_padded(text: str, vocab_size: int, bucket: int) -> np.ndarray:
    """The engine's admission bucket: ``hash_tokenize`` then LEFT-pad
    to ``bucket``.  Module-level because the simulator's prefix-cache
    model and the benchmarks must hash the exact same token buckets
    the engine prefills (``simulate_continuous(prompt_tokens=...)``)."""
    arr = np.zeros((bucket,), np.int32)
    seq = hash_tokenize(text, vocab_size, bucket)
    arr[bucket - len(seq):] = seq                   # left-pad
    return arr


@dataclasses.dataclass
class Request:
    text: str
    arrival: float
    task_id: int
    # optional per-request decode budget (None -> engine default); with
    # EOS disabled this IS the output length — how the benchmarks build
    # deterministic heterogeneous-output-length workloads.
    max_new_tokens: Optional[int] = None
    # traffic class (repro.core.workload.TrafficClass name) the SLO
    # monitor attributes this request to; "" = unclassed (resolves to
    # the monitor's default class, and the enqueue event stays
    # bit-identical to pre-class traces)
    traffic_class: str = ""
    # filled at completion:
    start: float = -1.0
    finish: float = -1.0
    # admission instant minus arrival (engine clock): how long the
    # request sat queued before the scheduler committed resources to it
    # — bulk/batch requests are stamped at batch start
    queue_wait_s: float = -1.0
    lane: str = ""
    out_len: int = 0
    slot: int = -1               # decode slot served in (continuous mode)
    # generated token ids (greedy); the paged-vs-contiguous parity test
    # asserts these match token for token
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    # per-token emission times (engine clock): token_times[0] is the
    # first-token instant (TTFT = token_times[0] - arrival), successive
    # diffs are the inter-token latencies the percentile metrics
    # summarize.  Continuous modes record exact step times; batch mode
    # models streaming linearly across the batch's decode horizon.
    token_times: List[float] = dataclasses.field(default_factory=list)

    @property
    def response_time(self) -> float:
        return self.finish - self.arrival


class ServingEngine:
    """Single-node engine with a pluggable scheduling policy.

    mode="batch": policy.select forms run-to-completion batches.
    mode="continuous": policy.admit fills decode slots per step.
    """

    def __init__(self, params, cfg, policy: sched_lib.Policy,
                 profile: sched_lib.OfflineProfile, *,
                 input_bucket: int = 32, max_new_tokens: int = 32,
                 xi: float = 2.0, mode: str = "batch",
                 eos_id: int = EOS_ID, kv: str = "contiguous",
                 num_slots: Optional[int] = None,
                 kv_block_size: int = 16,
                 kv_num_blocks: Optional[int] = None,
                 prefill: str = "stall",
                 chunk_size: int = 16,
                 token_budget: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 prefix_cache: bool = False,
                 decode_steps: int = 1,
                 aot_warmup: bool = True,
                 persist_prefix_cache: bool = False,
                 faults=None,
                 obs: Optional[Observability] = None):
        # per-engine fallback ledger FIRST: the kernel factories below
        # may fire the jnp-fallback warning while they build.  Scoping
        # the ledger to this instance (obslog.scope around the factory
        # build and serve()) keeps fallback_events replica-accurate
        # when R engines share the process — a process-global delta
        # would attribute every replica's events to one engine and
        # rate-suppress later replicas' first warnings.
        self.fallback_ledger = obslog.RateLimitedLogger()
        if mode not in ("batch", "continuous"):
            raise ValueError(f"unknown mode {mode!r}")
        if kv not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv layout {kv!r}")
        if kv == "paged" and mode != "continuous":
            raise ValueError('kv="paged" requires mode="continuous"')
        if prefill not in ("stall", "chunked"):
            raise ValueError(f"unknown prefill mode {prefill!r}")
        if prefill == "chunked" and kv != "paged":
            raise ValueError('prefill="chunked" requires mode="continuous"'
                             ', kv="paged"')
        if prefix_cache and kv != "paged":
            raise ValueError('prefix_cache=True requires mode="continuous"'
                             ', kv="paged"')
        if decode_steps < 1:
            raise ValueError(f"decode_steps must be >= 1, got "
                             f"{decode_steps}")
        if decode_steps > 1 and mode != "continuous":
            raise ValueError('decode_steps > 1 requires mode="continuous" '
                             "(batch mode has no persistent decode loop)")
        if decode_steps - 1 > _MAX_LEN_SLACK:
            raise ValueError(
                f"decode_steps={decode_steps}: the eviction lag "
                f"(decode_steps - 1 overhang writes past a sequence's "
                f"end) exceeds the max_len slack ({_MAX_LEN_SLACK}) that "
                "keeps dead-row writes inside the slot's own KV range")
        if persist_prefix_cache and not prefix_cache:
            raise ValueError("persist_prefix_cache=True requires "
                             "prefix_cache=True")
        if faults is not None and (mode != "continuous"
                                   or prefill != "stall"):
            raise ValueError('faults (serving.faults.ReplicaFaults) '
                             'require mode="continuous", '
                             'prefill="stall"')
        self.params = params
        self.cfg = cfg
        self.policy = policy
        self.profile = profile
        self.persona = policy.persona
        self.input_bucket = input_bucket
        self.max_new_tokens = max_new_tokens
        self.xi = xi
        self.mode = mode
        self.eos_id = eos_id
        self.kv = kv
        self.max_len = input_bucket + max_new_tokens + _MAX_LEN_SLACK
        # async host pipeline knobs: N decode steps per launch (N=1 is
        # the bit-parity synchronous default) and AOT executable warmup
        # at serve() start
        self.decode_steps = decode_steps
        self.aot_warmup = aot_warmup
        self.persist_prefix_cache = persist_prefix_cache
        # observability bundle (repro.obs): OFF by default — every
        # emission site below is guarded, and with obs=None the serve
        # path is bit-identical to the unobserved engine
        self.obs = obs
        # continuous-mode decode width; paged engines raise it above the
        # persona batch size so the BLOCK BUDGET (not worst-case slot
        # length) bounds concurrency
        self.num_slots = (num_slots if num_slots is not None
                          else self.persona.batch_size)
        self.kv_block_size = kv_block_size
        # chunked-prefill knobs (repro.prefill): the per-iteration token
        # budget covers one decode token per active slot FIRST, then as
        # many prefill-chunk tokens as fit; the default budget leaves
        # one chunk of headroom above a fully busy decode loop.
        self.prefill = prefill
        self.chunk_size = chunk_size
        self.token_budget = (token_budget if token_budget is not None
                             else self.num_slots + chunk_size)
        if prefill == "chunked":
            # constructor-time validation (ChunkScheduler re-checks)
            ChunkScheduler(chunk_size, self.token_budget)
        self.use_pallas = use_pallas
        # default budget: the worst-case reservation fits in every slot
        # (no rejections) — benchmarks pass an explicit tighter budget
        self.kv_num_blocks = (
            kv_num_blocks if kv_num_blocks is not None
            else self.num_slots * blocks_for_tokens(self.max_len,
                                                    kv_block_size))
        if kv == "paged":
            ok, why = transformer.paged_supported(cfg)
            if not ok:
                raise NotImplementedError(f"paged KV cache: {why}")
            worst = blocks_for_tokens(input_bucket + max_new_tokens - 1,
                                      kv_block_size)
            if worst > self.kv_num_blocks:
                raise ValueError(
                    f"kv_num_blocks={self.kv_num_blocks} cannot hold one "
                    f"worst-case sequence ({worst} blocks) — admission "
                    "would deadlock")
        # batch-mode executables are preallocated at the policy's max
        # consolidated batch (b * C for UASCHED, C otherwise) so a
        # consolidated batch runs as ONE batch, matching the simulator;
        # padded rows are capped at a single token (see _run_batch).
        self.batch_capacity = policy.max_batch()
        self.prefix_cache_enabled = prefix_cache
        with obslog.scope(self.fallback_ledger):
            self._prefill = generate.make_prefill_fn(cfg, self.max_len)
            self._decode = generate.make_decode_fn(cfg)
            self._slot_prefill = generate.make_slot_prefill_fn(
                cfg, self.max_len)
            self._decode_steps_fn = generate.make_decode_steps_fn(cfg)
            if kv == "paged":
                self._paged_prefill = generate.make_paged_prefill_fn(
                    cfg, self.max_len)
                self._paged_decode = generate.make_paged_decode_fn(
                    cfg, use_pallas)
                self._paged_decode_steps = \
                    generate.make_paged_decode_steps_fn(cfg, use_pallas)
                if prefill == "chunked" or prefix_cache:
                    # the FUSED executable: every scheduled chunk of an
                    # iteration in one launch (padded-shape-keyed memo).
                    # Prefix-cached STALL admission routes its uncached
                    # suffix through the same executable as a
                    # single-chunk launch, so a prefix hit pays one
                    # fused dispatch.
                    self._ragged_prefill = \
                        generate.make_ragged_prefill_fn(cfg, use_pallas)
                if prefix_cache:
                    self._copy_block = generate.make_copy_block_fn(cfg)
        # the device the params live on: the pool, the tables and every
        # executable follow it (serve runs under jax.default_device)
        self.device = params_device(params)
        # AOT warm keys: the factory memo shares JitExecutables across
        # same-cfg engines, so every key carries the dims that fix this
        # engine's array shapes and the device it was compiled for — two
        # engines with identical dims on one device share warmed
        # executables; differing dims or devices never collide.
        self._aot_dims = (self.num_slots, self.input_bucket, self.max_len,
                          self.kv, self.kv_num_blocks, self.kv_block_size,
                          self.device)
        self._window_key = ("window", self._aot_dims, self.decode_steps)
        self._admit_key = ("admit", self._aot_dims)
        self._cow_key = ("cow", self._aot_dims)
        self.scheduler_overhead_s = 0.0
        # host wall seconds of the serve in flight by loop phase
        # (pipeline.host_phase: predict, setup, admit, pack, launch,
        # tables, wait, advance), reset per serve
        self.host_phase_s: Dict[str, float] = {}
        self._aot_misses0 = 0
        # wall seconds the last serve spent AOT-compiling in _aot_warm
        self.warmup_s = 0.0
        # exposed for the slot-recycling tests: per-slot cache after the
        # last continuous serve, and the admission audit trail
        self.slot_cache = None
        self.admission_log: List[Dict] = []
        # paged-KV state (populated by a paged continuous serve)
        self.paged_cache: Optional[PagedKVCache] = None
        self.allocator: Optional[BlockAllocator] = None
        # live PrefixCache of the last serve (when prefix_cache=True);
        # rebuilt per serve — cached block ids index that serve's pool
        self.prefix_cache: Optional[PrefixCache] = None
        # memory-efficiency accounting (reset per serve)
        self.kv_util_samples: List[float] = []
        self._rejected_ids: set = set()
        self.peak_concurrency = 0
        # tail-latency accounting (reset per serve): wall-clock spent on
        # prefill work while decode slots were live (the decode-stall
        # time chunked prefill bounds), and the chunked engine's
        # per-iteration (decode_tokens, prefill_tokens) budget trace —
        # the simulator's chunked mode reproduces it entry for entry.
        self.prefill_stall_s = 0.0
        self.prefill_stall_max_s = 0.0   # worst single-iteration stall
        self.budget_trace: List = []
        # dispatch accounting (reset per serve): prefill launches in
        # total and per iteration — the chunked engine issues exactly
        # ONE fused launch per iteration with scheduled chunks, versus
        # one per admission (stall) / one per chunk (the pre-fused
        # path); exec_cache_* count the fused executable's padded-shape
        # keys (miss = first launch at a new ChunkBatch.shape_key this
        # serve).  The simulator mirrors all four from the same plans.
        self.prefill_dispatches = 0
        self.prefill_dispatch_trace: List[int] = []
        self.exec_cache_hits = 0
        self.exec_cache_misses = 0
        self._exec_keys: set = set()
        # decode-dispatch accounting (reset per serve): launches and
        # steps of the multi-step decode window — steps/dispatches ==
        # decode_steps exactly (every window launches the full N; dead
        # rows ride along and are discarded at readback).  The trace
        # records steps per window (chunked mode aligns entries with
        # budget_trace, 0 = no decode that iteration).  The simulator
        # mirrors all three.
        self.decode_dispatches = 0
        self.decode_steps_total = 0
        self.decode_page_share_sum = 0.0
        self.decode_dispatch_trace: List[int] = []
        # completion worker (serving.pipeline) of the serve in flight
        self._worker: Optional[CompletionWorker] = None
        # failure-aware serving (serving.faults.ReplicaFaults): the
        # pre-admission shed pass, straggler slowdowns and the crash
        # point of the continuous stall loop.  The crash latch and the
        # final step coordinate persist across serve calls — failover
        # rounds (replica.ReplicatedEngine) continue a replica's step
        # stream via serve(step_offset=...), and a crash fires once.
        self.faults = faults
        self._crashed = False
        self.last_step = 0
        self.timed_out_tasks: List[prio.SimTask] = []
        self.shed_tasks: List[prio.SimTask] = []
        self.survivors: List[Request] = []

    # ------------------------------------------------------------------
    def _to_sim_task(self, req: Request) -> prio.SimTask:
        t0 = time.perf_counter()
        u = self.profile.predictor.score(req.text)
        d = prio.priority_point(req.arrival, len(req.text.split()),
                                self.persona.phi, None, xi=self.xi)
        self.scheduler_overhead_s += time.perf_counter() - t0
        st = prio.SimTask(task=req, u=float(max(u, 0.0)), r=req.arrival,
                          d=d, input_len=float(len(req.text.split())),
                          true_out_len=0)
        return st

    def _sim_tasks(self, requests: Sequence[Request]) -> List[prio.SimTask]:
        """The requests in arrival order as SimTasks — the uncertainty
        predictor and the priority point, host phase ``predict``."""
        with host_phase(self.host_phase_s, "predict"):
            return [self._to_sim_task(r)
                    for r in sorted(requests, key=lambda r: r.arrival)]

    def _tokenize_padded(self, text: str) -> np.ndarray:
        return tokenize_padded(text, self.cfg.vocab_size,
                               self.input_bucket)

    def _cap(self, req: Request) -> int:
        cap = (req.max_new_tokens if req.max_new_tokens is not None
               else self.max_new_tokens)
        return max(1, min(cap, self.max_new_tokens))

    def _run_batch(self, batch: Sequence[prio.SimTask], lane: str,
                   now: float) -> float:
        """Execute a run-to-completion batch; returns finish time."""
        Cb = self.batch_capacity
        S = self.input_bucket
        arr = np.zeros((Cb, S), np.int32)
        for i, t in enumerate(batch):
            arr[i] = self._tokenize_padded(t.task.text)
        tokens = jnp.asarray(arr)
        # padded rows stop after one token so they never extend the
        # batch's decode horizon (the run-to-completion cost is set by
        # the longest REAL member, as in the simulator's latency model)
        caps = np.ones((Cb,), np.int32)
        caps[:len(batch)] = [self._cap(t.task) for t in batch]
        t0 = time.perf_counter()
        out_tokens, lengths = generate.generate(
            self.params, self.cfg, {"tokens": tokens},
            max_new_tokens=self.max_new_tokens, eos_id=self.eos_id,
            prefill_fn=self._prefill, decode_fn=self._decode,
            max_lens=caps)
        jax.block_until_ready(out_tokens)
        dur = time.perf_counter() - t0
        # one prefill launch per executed batch; the per-iteration trace
        # only covers batch mode — in continuous modes the trace is the
        # DECODE-LOOP launch profile (chunked: aligned with
        # budget_trace), so bulk-lane batches count in the total only
        self.prefill_dispatches += 1
        if self.mode == "batch":
            self.prefill_dispatch_trace.append(1)
        if lane == "cpu":
            dur *= self.persona.cpu_slowdown   # bulk-lane emulation
        finish = now + dur
        if self.mode == "batch":
            # batch-mode memory metric: rows used of the preallocated
            # executable; the continuous bulk lane must NOT sample here,
            # its KV metrics track the decode slots / block pool only
            self.kv_util_samples.append(len(batch) / Cb)
            self.peak_concurrency = max(self.peak_concurrency, len(batch))
        toks = np.asarray(out_tokens)
        # run-to-completion streaming model for the tail-latency
        # metrics: the batch decodes max(realized lengths) steps in
        # ``dur``, so member token j is emitted at a linear fraction of
        # the horizon (uniform ITL = dur / horizon).
        horizon = max(max((int(lengths[i]) for i in range(len(batch))),
                          default=1), 1)
        ob = self.obs
        for i, t in enumerate(batch):
            t.start, t.finish, t.lane = now, finish, lane
            t.task.start, t.task.finish, t.task.lane = now, finish, lane
            t.task.queue_wait_s = now - t.r
            t.task.out_len = int(lengths[i]) if i < len(lengths) else 0
            t.task.out_tokens = toks[i, :t.task.out_len].tolist()
            t.task.token_times = [now + dur * (j + 1) / horizon
                                  for j in range(t.task.out_len)]
        if ob is not None:
            ob.inc("prefill.dispatches")
            ob.span("bulk_batch", now, finish - now, lane=lane,
                    size=len(batch))
            for t in batch:
                tid = t.task.task_id
                cls = t.task.traffic_class
                ob.slo_observe("queue_wait", cls, now,
                               t.task.queue_wait_s)
                if t.task.token_times:
                    ob.event("first_token", t.task.token_times[0], tid,
                             lane=lane)
                    ob.slo_observe("ttft", cls, t.task.token_times[0],
                                   t.task.token_times[0] - t.r)
                    if t.task.out_len > 1:
                        # run-to-completion streaming model: uniform
                        # ITL across the batch's decode horizon
                        ob.slo_observe("itl", cls, finish,
                                       dur / horizon,
                                       n=t.task.out_len - 1)
                ob.event("complete", finish, tid, lane=lane,
                         out_len=t.task.out_len)
                ob.inc("sched.completions")
                ob.complete_request(cls, finish, u=t.u,
                                    out_len=t.task.out_len,
                                    latency_s=finish - t.r)
        return finish

    # ------------------------------------------------------------------
    def serve(self, requests: Sequence[Request], *,
              step_offset: int = 0) -> Dict:
        """Run a full trace (virtual-time arrivals, real execution).

        ``step_offset`` starts the step coordinate above zero — the
        failover rounds of ``replica.ReplicatedEngine`` use it so a
        replica's event stream keeps counting steps where its previous
        serve stopped (the simulator's per-replica step counter never
        resets, so parity needs the continuation)."""
        if step_offset and (self.mode != "continuous"
                            or self.prefill != "stall"):
            raise ValueError("step_offset requires the continuous "
                             "stall serve loop")
        self.timed_out_tasks = []
        self.shed_tasks = []
        self.survivors = []
        self.kv_util_samples = []
        self._rejected_ids = set()
        self.peak_concurrency = 0
        self.prefill_stall_s = 0.0
        self.prefill_stall_max_s = 0.0
        self.budget_trace = []
        self.prefill_dispatches = 0
        self.prefill_dispatch_trace = []
        self.exec_cache_hits = 0
        self.exec_cache_misses = 0
        self._exec_keys = set()
        self.decode_dispatches = 0
        self.decode_steps_total = 0
        self.decode_page_share_sum = 0.0
        self.decode_dispatch_trace = []
        self.host_phase_s = {}
        self._aot_misses0 = self._aot_misses()
        # the jnp-fallback warning is one-time PER SERVE (a process
        # running many engines must not mask later serves' fallbacks);
        # re-arm this engine's scoped ledger the same way
        generate.reset_fallback_warning()
        self.fallback_ledger.reset(generate.FALLBACK_KEY)
        if not self.persist_prefix_cache:
            # default: the device page pool is rebuilt per serve, so
            # cached block ids must not outlive it.  With persistence
            # the pool, allocator and index survive (the continuous
            # setup reuses them and resets the per-serve counters).
            self.prefix_cache = None
        # serve-time fallbacks (late kernel fallbacks) land in this
        # engine's own ledger; arrays the serve creates (pool, tables,
        # token operands) land on the params' device
        with obslog.scope(self.fallback_ledger), \
                jax.default_device(self.device):
            # the worker is constructed BEFORE the try: if it raises,
            # there is no half-built worker for the finally to trip
            # over, and any engine exception mid-window always reaches
            # a close() that joins the daemon thread (close() is
            # idempotent, so double-teardown is safe too)
            self._worker = CompletionWorker()
            try:
                if self.mode == "continuous":
                    if self.prefill == "chunked":
                        return self._serve_continuous_chunked(requests)
                    return self._serve_continuous(
                        requests, step_offset=step_offset)
                return self._serve_batch(requests)
            finally:
                self._worker.close()
                self._worker = None

    def _result(self, done: List[prio.SimTask], n: int) -> Dict:
        ps = (self.prefix_cache.stats()
              if self.prefix_cache is not None else {})
        # a crashed or fully-shed serve can complete nothing — guard
        # the aggregates (zeros, not nan) instead of assuming done
        rts = (np.array([t.response_time for t in done]) if done
               else np.zeros(1))
        span = (max(t.finish for t in done) - min(t.r for t in done)
                if done else 0.0)
        util = (np.array(self.kv_util_samples)
                if self.kv_util_samples else np.zeros(1))
        # tail-latency metrics: TTFT per request (first token emission
        # minus arrival), the pooled inter-token latencies of every
        # request, and the per-request queue wait — all folded into the
        # shared log-bucketed streaming histograms (repro.obs.metrics),
        # the same quantile substrate SimResult uses, so engine and sim
        # tail metrics stay comparable and state stays O(buckets)
        # regardless of trace length.
        ttft_h, itl_h, qw_h = Histogram(), Histogram(), Histogram()
        for t in done:
            times = getattr(t.task, "token_times", None) or []
            if times:
                ttft_h.record(times[0] - t.r)
                for d in np.diff(times):
                    itl_h.record(float(d))
            qw = getattr(t.task, "queue_wait_s", -1.0)
            if qw >= 0.0:
                qw_h.record(qw)
        out = {
            "mean_response_s": float(rts.mean()),
            "max_response_s": float(rts.max()),
            "throughput_per_min": 60.0 * n / max(span, 1e-9),
            "scheduler_overhead_s": self.scheduler_overhead_s,
            # this serve's host wall seconds by loop phase, and how many
            # of its call_aot dispatches missed the AOT store and went
            # through the jit function (compiling at an unseen shape)
            "host_phase_s": dict(self.host_phase_s),
            "aot_misses": self._aot_misses() - self._aot_misses0,
            "n_tasks": n,
            "tasks": done,
            "completion_order": [t.task.task_id for t in done],
            "mode": self.mode,
            # memory-efficiency metrics: KV utilization is the fraction
            # of the reserved KV memory in use, sampled per decode step
            # (paged: allocated/total blocks; contiguous continuous:
            # occupied/total slots — a slot pins max_len KV whether its
            # sequence is short or long; batch: rows used / capacity).
            # rejected_for_memory counts DISTINCT requests deferred at
            # least once by the block-budget gate (a blocked request is
            # retried every step; counting events would scale with
            # decode-step count, not workload)
            "kv_util_peak": float(util.max()),
            "kv_util_mean": float(util.mean()),
            "rejected_for_memory": len(self._rejected_ids),
            "peak_concurrency": self.peak_concurrency,
            "ttft_p50": ttft_h.quantile(0.50),
            "ttft_p90": ttft_h.quantile(0.90),
            "ttft_p99": ttft_h.quantile(0.99),
            "itl_p50": itl_h.quantile(0.50),
            "itl_p90": itl_h.quantile(0.90),
            "itl_p99": itl_h.quantile(0.99),
            "queue_wait_p50": qw_h.quantile(0.50),
            "queue_wait_p90": qw_h.quantile(0.90),
            "queue_wait_p99": qw_h.quantile(0.99),
            # countable silent degradations (repro.obs.log): jnp-kernel
            # fallback at factory build — counted by THIS engine's
            # scoped ledger, so R replicas in one process each report
            # only their own events
            "fallback_events": self.fallback_ledger.count(),
            # wall-clock the obs emitters spent recording (0.0 with
            # obs=None) — the measured-overhead guard: recording happens
            # outside the timed device regions, so it never perturbs the
            # virtual clock, and its host cost is reported, not guessed
            "obs_overhead_s": (self.obs.overhead_s
                               if self.obs is not None else 0.0),
            # wall-clock spent prefilling while decode slots were live
            # (the head-of-line stall chunked prefill bounds); _max_s is
            # the worst stall injected between two consecutive decode
            # steps — the jitter spike the token budget caps
            "prefill_stall_s": self.prefill_stall_s,
            "prefill_stall_max_s": self.prefill_stall_max_s,
            "budget_trace": list(self.budget_trace),
            # dispatch accounting: total prefill launches (bulk-lane
            # batches included), and the DECODE-LOOP per-iteration
            # launch counts (chunked mode aligns entries with
            # budget_trace and every entry is <= 1 — ONE fused launch
            # per iteration; stall mode records admission-burst sizes;
            # batch mode one entry per executed batch), plus the fused
            # executable's padded-shape-key cache hits / misses this
            # serve (0/0 outside chunked mode).  All four parity-match
            # the simulator's SimResult fields.
            "prefill_dispatches": self.prefill_dispatches,
            "prefill_dispatch_trace": list(self.prefill_dispatch_trace),
            "exec_cache_hits": self.exec_cache_hits,
            "exec_cache_misses": self.exec_cache_misses,
            # decode-dispatch accounting (async host pipeline): one
            # launch per N-step window, so steps/dispatches ==
            # decode_steps exactly; the trace holds steps per window.
            # All three parity-match SimResult.
            "decode_dispatches": self.decode_dispatches,
            "decode_steps_executed": self.decode_steps_total,
            "decode_dispatch_trace": list(self.decode_dispatch_trace),
            # paged decode: pages the kernel walked over the pages it
            # could walk (every slot's whole table), meaned over decode
            # launches; dead rows walk none (0.0 without a paged decode)
            "decode_page_share": (self.decode_page_share_sum
                                  / max(self.decode_dispatches, 1)),
            # prefix-cache metrics (kvcache.prefix counters; the
            # simulator's cache model reports the identical fields —
            # the engine-vs-sim parity tests compare them directly).
            # hit_rate is hit / probed FULL prompt blocks across all
            # admissions; cached_tokens_reused counts prompt tokens NOT
            # recomputed; cow_copies counts full-match page copies.
            "prefix_hit_rate": ps.get("prefix_hit_rate", 0.0),
            "cached_tokens_reused": ps.get("cached_tokens_reused", 0),
            "cow_copies": ps.get("cow_copies", 0),
            "prefix_evictions": ps.get("prefix_evictions", 0),
            "kv": {"kind": self.kv, "num_slots": self.num_slots,
                   "block_size": self.kv_block_size,
                   "num_blocks": self.kv_num_blocks,
                   "prefix_cache": self.prefix_cache_enabled},
            "prefill": {"kind": self.prefill,
                        "chunk_size": self.chunk_size,
                        "token_budget": self.token_budget},
            "pipeline": {"decode_steps": self.decode_steps,
                         "aot_warmup": self.aot_warmup,
                         "persist_prefix_cache":
                             self.persist_prefix_cache},
            # SLO monitoring / predictor calibration / health snapshots
            # (PR 8): {} / [] with the features off, so the obs=None
            # result stays field-identical to pre-PR serves.
            # SimResult carries the same three fields.
            "slo_attainment": (self.obs.slo.attainment()
                               if self.obs is not None
                               and self.obs.slo is not None else {}),
            "calibration": (self.obs.calibration.summary()
                            if self.obs is not None
                            and self.obs.calibration is not None
                            else {}),
            "health_trace": (list(self.obs.health_trace)
                             if self.obs is not None else []),
        }
        state = (self.paged_cache.state if self.paged_cache is not None
                 else None)
        if state is not None and "moe_count" in state:
            # expert-layer keys, only for a paged MoE serve: the decode
            # launches' routed token-expert pairs and the experts they
            # reached, summed over steps and MoE layers on the device
            # and read here once, after the serve
            pairs, reached = (int(v) for v in np.asarray(state["moe_count"]))
            out["moe_decode_pairs"] = pairs
            out["moe_decode_experts"] = reached
        if self.faults is not None:
            # fault-gated keys: present ONLY when a fault plan is
            # threaded, so unfaulted result dicts stay byte-identical
            # to pre-fault serves (SimResult mirrors the counts)
            out["timed_out"] = len(self.timed_out_tasks)
            out["shed"] = len(self.shed_tasks)
            out["timed_out_ids"] = [t.task.task_id
                                    for t in self.timed_out_tasks]
            out["shed_ids"] = [t.task.task_id for t in self.shed_tasks]
            out["crashed"] = self._crashed
            out["final_step"] = self.last_step
            out["survivor_ids"] = [q.task_id for q in self.survivors]
        return out

    def health(self) -> Dict:
        """Latest health snapshot of the current/last serve — the
        observation vector a future auto-tuner/router polls ({} with
        obs off or before the first snapshot fires)."""
        return self.obs.health() if self.obs is not None else {}

    def _serve_batch(self, requests: Sequence[Request]) -> Dict:
        sim_tasks = self._sim_tasks(requests)
        queue: List[prio.SimTask] = []
        bulk: List[prio.SimTask] = []
        done: List[prio.SimTask] = []
        now = 0.0
        i = 0
        n = len(sim_tasks)
        C = self.persona.batch_size
        while len(done) < n:
            while i < n and sim_tasks[i].r <= now + 1e-9:
                if self.obs is not None:
                    cls = sim_tasks[i].task.traffic_class
                    self.obs.event("enqueue", sim_tasks[i].r,
                                   sim_tasks[i].task.task_id,
                                   **({"cls": cls} if cls else {}))
                queue.append(sim_tasks[i])
                i += 1
            if queue and (len(queue) >= C
                          or now - min(t.r for t in queue) >= self.xi
                          or i >= n):
                t0 = time.perf_counter()
                gpu_b, cpu_b, rest = self.policy.select(list(queue), now)
                self.scheduler_overhead_s += time.perf_counter() - t0
                queue = list(rest)
                bulk.extend(cpu_b)
                if gpu_b:
                    Cb = self.batch_capacity
                    now = self._run_batch(gpu_b[:Cb], "gpu", now)
                    done.extend(gpu_b[:Cb])
                    queue.extend(gpu_b[Cb:])
                    continue
            if bulk and not queue:
                batch, bulk = bulk[:C], bulk[C:]
                now = self._run_batch(batch, "cpu", now)
                done.extend(batch)
                continue
            # idle: advance to next arrival / window expiry
            cand = []
            if i < n:
                cand.append(sim_tasks[i].r)
            if queue:
                cand.append(min(t.r for t in queue) + self.xi)
            future = [c for c in cand if c > now]
            if future:
                now = min(future)
            else:
                now += self.xi
        return self._result(done, n)

    # ------------------------------------------------------------------
    # continuous batching: persistent decode loop with slot recycling
    # ------------------------------------------------------------------

    def _extend_block_tables(self, active, slot_task, slot_gen, slot_cap,
                             alloc, kvc, steps: int) -> None:
        """Boundary crossings before a paged decode WINDOW: extend each
        active slot's table to cover every useful write of the next
        ``steps`` launches-in-one (``kvcache.window_target_tokens`` —
        clamped at the admission reservation, so the pool can never run
        dry and rejection decisions are independent of ``steps``).
        Overhang writes past the clamp land on the trash page via the
        scatter primitives' table-width clamp.  Shared by the stall and
        chunked serve loops; ``steps=1`` is the original synchronous
        per-step rule.  Also counts the pages the decode kernel walks
        in the window (``decode_page_share``, ``decode.pages``)."""
        S = self.input_bucket
        for s in active:
            tid = slot_task[s].task.task_id
            target = alloc.blocks_for(window_target_tokens(
                S, slot_gen[s], slot_cap[s], steps))
            have = len(alloc.table(tid))
            while target > have:
                kvc.extend_table(s, have, alloc.allocate(tid))
                have += 1
        # the pages the decode kernel will walk: the live slots' live
        # table entries (every other row's table is the trash page's)
        nb = kvc.max_blocks_per_seq
        walked = sum(decode_pages_walked(S, slot_gen[s], steps,
                                         self.kv_block_size)
                     for s in active)
        self.decode_page_share_sum += walked / (self.num_slots * nb * steps)
        if self.obs is not None:
            self.obs.inc("decode.pages", walked)

    def _advance_decode_window(self, active, window_host, now, dt,
                               slot_task, slot_gen, slot_cap, tokens,
                               done, *, alloc=None, kvc=None,
                               reserved=None, step: int = 0) -> None:
        """Window-END (in-arrears) bookkeeping shared by the stall and
        chunked serve loops: consume the (C, n) window tokens STEP-MAJOR
        (step j, slots in slot order — for n=1 this is exactly the old
        per-step loop, including completion order), record each token
        with its interpolated emission time, mark sequences finished at
        their EOS/cap step and discard their remaining window columns.
        Eviction happens only after the whole window is consumed: a
        finished sequence's blocks stayed held while the device stepped
        past its end (the eviction-lag invariant — overhang writes hit
        the slot's own blocks or the trash page, never a freed or
        foreign block), and are returned here, before any admission
        decision that could reuse them."""
        ob = self.obs
        n = window_host.shape[1]
        finished: List[int] = []
        for j in range(n):
            t_j = now - dt + dt * (j + 1) / n
            for s in active:
                if slot_task[s] is None or s in finished:
                    continue
                tok = int(window_host[s, j])
                slot_gen[s] += 1
                task = slot_task[s]
                prev_t = task.task.token_times[-1]
                task.task.out_tokens.append(tok)
                task.task.token_times.append(t_j)
                if ob is not None:
                    ob.event("token", t_j, task.task.task_id, step,
                             slot=s, idx=slot_gen[s])
                    ob.slo_observe("itl", task.task.traffic_class,
                                   t_j, t_j - prev_t)
                if tok == self.eos_id or slot_gen[s] >= slot_cap[s]:
                    task.finish = t_j
                    task.task.finish = t_j
                    task.task.out_len = slot_gen[s]
                    done.append(task)
                    finished.append(s)
                    if ob is not None:
                        ob.event("complete", t_j, task.task.task_id,
                                 step, lane="gpu", out_len=slot_gen[s])
                        ob.inc("sched.completions")
                        ob.complete_request(task.task.traffic_class,
                                            t_j, u=task.u,
                                            out_len=slot_gen[s],
                                            latency_s=t_j - task.r)
                        # eviction lag: window steps this slot's blocks
                        # stay held past its logical end (in arrears)
                        ob.observe("decode.eviction_lag_steps",
                                   n - 1 - j)
                else:
                    tokens[s, 0] = tok
        # eviction in arrears: frees happen at window end, in slot
        # order (the simulator frees in the same order, so allocator
        # free-list state stays bit-identical)
        for s in active:
            if s not in finished:
                continue
            tid = slot_task[s].task.task_id
            slot_task[s] = None
            tokens[s, 0] = generate.PAD_ID
            if ob is not None:
                ob.event("evict", now, tid, step, slot=s)
            if alloc is not None:
                alloc.free_sequence(tid)
                kvc.clear_table(s)
                reserved[s] = 0

    # ------------------------------------------------------------------
    def _paged_setup(self):
        """Build — or, with ``persist_prefix_cache=True``, revive — the
        paged serve state (page pool, allocator, prefix cache).  On the
        persistent path the device pool's cached blocks carry their KV
        content across serves (all decode slots were evicted at the
        previous serve's end, so only cache-pinned blocks are live) and
        the prefix index keeps its entries while its per-serve counters
        reset, the expert layer's ``moe_count`` among them."""
        C = self.num_slots
        mreg = self.obs.metrics if self.obs is not None else None
        if (self.persist_prefix_cache and self.paged_cache is not None
                and self.prefix_cache is not None):
            kvc, alloc = self.paged_cache, self.allocator
            pc = self.prefix_cache
            pc.reset_stats()
            pc.metrics = mreg
            if "moe_count" in kvc.state:
                kvc.state = dict(kvc.state, moe_count=jnp.zeros_like(
                    kvc.state["moe_count"]))
            return kvc, alloc, pc, kvc.state
        kvc = PagedKVCache(self.cfg, C, self.kv_num_blocks,
                           self.kv_block_size, self.max_len)
        alloc = BlockAllocator(self.kv_num_blocks, self.kv_block_size)
        self.paged_cache, self.allocator = kvc, alloc
        pc = None
        if self.prefix_cache_enabled:
            pc = PrefixCache(alloc, self.kv_block_size)
            pc.metrics = mreg
            self.prefix_cache = pc
        return kvc, alloc, pc, kvc.state

    def _ragged_aot_key(self, shape_key: tuple) -> tuple:
        return ("ragged", self._aot_dims, shape_key)

    def _executables(self) -> List[generate.JitExecutable]:
        """The executables the continuous serve loops dispatch through
        ``call_aot``."""
        return [exe for exe in (getattr(self, attr, None) for attr in (
            "_paged_decode_steps", "_paged_prefill", "_ragged_prefill",
            "_copy_block", "_decode_steps_fn", "_slot_prefill"))
            if exe is not None]

    def _aot_misses(self) -> int:
        return sum(exe.aot_misses for exe in self._executables())

    def warmed_executables(self) -> Dict[str, object]:
        """This engine's AOT-compiled executables by ``dispatch:<kind>``
        name plus the key's suffix (decode steps, ragged shape key) —
        the ones ``_aot_warm`` compiled for this engine's dims and
        device, not those of other engines sharing the factory memo."""
        out = {}
        for exe in self._executables():
            for key, compiled in exe.aot.items():
                if key[1] == self._aot_dims:
                    out[f"{exe.name}{list(key[2:])}"] = compiled
        return out

    def _aot_warm(self, cache, kvc=None) -> None:
        """AOT-compile the continuous serve loop's executables at
        ``serve()`` start (``jit.lower(avals).compile()`` per shape
        key), so the first request pays neither trace nor compile time.
        ``lower().compile()`` does NOT populate the jit call cache —
        the ``Compiled`` objects live in each ``JitExecutable``'s AOT
        store (shared across same-shape engines via the factory memo)
        and the loops dispatch through ``call_aot``.

        Warmed: the N-step decode window, the admission prefill (stall
        mode), the CoW page copy and the block-quantized
        prefix-suffix ragged keys (prefix cache), and the single-chunk
        ragged keys a chunked serve typically opens with.  Ragged keys
        outside the warmed set (workload-dependent ChunkBatch shapes)
        fall back to jit-on-first-call, counted by exec_cache_misses as
        before.  Every executable is compiled for the engine's device;
        a compile the backend refuses raises here."""
        if not self.aot_warmup:
            return
        t_warm = time.perf_counter()
        C, S, n = self.num_slots, self.input_bucket, self.decode_steps
        on_dev = jax.sharding.SingleDeviceSharding(self.device)

        def sds(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), tree)

        def arg(shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=on_dev)

        p_s, c_s = sds(self.params), sds(cache)
        tok_s, i32 = arg((C, 1)), arg(())
        batch_s = {"tokens": arg((1, S))}
        if self.kv == "paged":
            nb = kvc.max_blocks_per_seq
            self._paged_decode_steps.warm(
                self._window_key, (p_s, c_s, tok_s, arg((C, nb))),
                {"num_steps": n})
            if self.prefill == "stall":
                self._paged_prefill.warm(
                    self._admit_key, (p_s, c_s, batch_s, i32, arg((nb,))))
            ragged_lens: set = set()
            if self.prefix_cache_enabled:
                self._copy_block.warm(self._cow_key, (c_s, i32, i32))
                if self.prefill == "stall":
                    # every reachable uncached-suffix length: prefix
                    # matches are block-quantized, plus the L=1
                    # full-match recompute
                    bs = self.kv_block_size
                    ragged_lens |= {S - k * bs
                                    for k in range(1, S // bs + 1)
                                    if S - k * bs > 0} | {1}
            if self.prefill == "chunked":
                ragged_lens |= {min(self.chunk_size, S), S}
            for L in sorted(ragged_lens):
                key = suffix_shape_key(L)
                TTp, Cp, Tp = key
                self._ragged_prefill.warm(
                    self._ragged_aot_key(key),
                    (p_s, c_s, {"tokens": arg((1, TTp))}, arg((TTp,)),
                     arg((Cp, 4)), arg((Cp, nb))),
                    {"chunk_pad": Tp})
        else:
            self._decode_steps_fn.warm(
                self._window_key, (p_s, c_s, tok_s), {"num_steps": n})
            self._slot_prefill.warm(
                self._admit_key, (p_s, c_s, batch_s, i32))
        self.warmup_s = time.perf_counter() - t_warm

    def _serve_continuous(self, requests: Sequence[Request], *,
                          step_offset: int = 0) -> Dict:
        persona = self.persona
        ob = self.obs
        rf = self.faults
        C = self.num_slots
        S = self.input_bucket
        paged = self.kv == "paged"
        sim_tasks = self._sim_tasks(requests)
        n = len(sim_tasks)
        queue: List[prio.SimTask] = []
        bulk: List[prio.SimTask] = []
        done: List[prio.SimTask] = []
        pc = None
        kvc = alloc = None
        phase = functools.partial(host_phase, self.host_phase_s)
        with phase("setup"):
            if paged:
                kvc, alloc, pc, cache = self._paged_setup()
                reserved = [0] * C   # per-slot worst-case block holdback
            else:
                cache = transformer.init_slot_cache(self.cfg, C,
                                                    self.max_len)
            self._aot_warm(cache, kvc)
        slot_task: List[Optional[prio.SimTask]] = [None] * C
        slot_gen = [0] * C
        slot_cap = [0] * C
        tokens = np.zeros((C, 1), np.int32)     # host copy of next tokens
        self.admission_log = []
        now = 0.0
        i = 0
        step = step_offset
        while (len(done) + len(self.timed_out_tasks)
               + len(self.shed_tasks)) < n:
            if (rf is not None and rf.crash_at_step is not None
                    and not self._crashed and step >= rf.crash_at_step):
                # replica death (serving.faults.CrashFault): evict the
                # active slots in slot order (freeing their KV blocks),
                # then every unfinished request — active, queued,
                # bulk-lane, not-yet-arrived — survives for the fault
                # coordinator to re-dispatch.  The simulator's
                # _ReplicaSim.crash() mirrors this sequence exactly.
                crash_surv: List[prio.SimTask] = []
                for slot in range(C):
                    t = slot_task[slot]
                    if t is None:
                        continue
                    if ob is not None:
                        ob.event("evict", now, t.task.task_id, step,
                                 slot=slot)
                    if paged:
                        alloc.free_sequence(t.task.task_id)
                        kvc.clear_table(slot)
                        reserved[slot] = 0
                    slot_task[slot] = None
                    crash_surv.append(t)
                crash_surv += list(queue) + list(bulk) + sim_tasks[i:]
                queue, bulk = [], []
                self._crashed = True
                self.survivors = [t.task for t in crash_surv]
                if ob is not None:
                    ob.event("replica_down", now, None, step,
                             reason="crash", survivors=len(crash_surv))
                    ob.inc("faults.replica_down")
                break
            while i < n and sim_tasks[i].r <= now + 1e-9:
                if ob is not None:
                    cls = sim_tasks[i].task.traffic_class
                    ob.event("enqueue", sim_tasks[i].r,
                             sim_tasks[i].task.task_id, step,
                             **({"cls": cls} if cls else {}))
                queue.append(sim_tasks[i])
                i += 1
            if rf is not None and queue:
                # failure-aware pre-admission pass (serving.faults):
                # doomed-request timeouts + pressure shedding — the
                # same shed_pass call the simulator's iterate() makes
                # at the same point, so events/counters parity-match
                queue, timed, dropped = shed_pass(
                    queue, now=now, step=step, rf=rf,
                    slo=ob.slo if ob is not None else None, obs=ob)
                self.timed_out_tasks += timed
                self.shed_tasks += dropped
            iter_stall = 0.0
            iter_launches = 0

            # --- admissions: fill freed slots, one policy call per slot
            while queue and None in slot_task:
                running = [t for t in slot_task if t is not None]
                prev_queue = list(queue)
                t0 = time.perf_counter()
                task, lane, rest = self.policy.admit(list(queue), now,
                                                     running)
                self.scheduler_overhead_s += time.perf_counter() - t0
                if task is None:
                    break
                queue = list(rest)
                if lane == "cpu":
                    if ob is not None:
                        ob.event("offload", now, task.task.task_id, step)
                        ob.inc("sched.offloads")
                    bulk.append(task)
                    continue
                cap = self._cap(task.task)
                need = 0
                if paged:
                    # admission gate: reserve the sequence's worst case
                    # (prompt + cap - 1 written positions) so boundary
                    # crossings can never exhaust the pool.  The
                    # simulator's block-budget model mirrors this check
                    # bit for bit (simulate_continuous).
                    need = blocks_for_tokens(S + cap - 1,
                                             self.kv_block_size)
                    if need > self.kv_num_blocks - sum(reserved):
                        queue = prev_queue       # leave it queued
                        self._rejected_ids.add(task.task.task_id)
                        if ob is not None:
                            ob.event("reject", now, task.task.task_id,
                                     step, kv_blocks=need)
                            ob.inc("sched.rejections")
                        break
                slot = slot_task.index(None)
                tid = task.task.task_id
                task.task.queue_wait_s = now - task.r
                if ob is not None:
                    ob.event("admit", now, tid, step, slot=slot,
                             u=task.u, kv_blocks=need)
                    ob.inc("sched.admissions")
                    ob.observe("queue_wait_s", task.task.queue_wait_s)
                    ob.slo_observe("queue_wait",
                                   task.task.traffic_class, now,
                                   task.task.queue_wait_s)
                stalled = any(t is not None for t in slot_task)
                toks = self._tokenize_padded(task.task.text)
                batch = {"tokens": jnp.asarray(toks[None, :])}
                pf_start = 0
                pf_key = "admit"
                t0 = time.perf_counter()
                if paged and pc is not None:
                    # longest-cached-prefix admission: matched blocks
                    # are SHARED into the table (refcounted), the CoW
                    # page copy covers a full-prompt match, and prefill
                    # runs only from the first uncached position —
                    # through the SAME fused ragged executable as
                    # chunked mode, as a single-chunk launch
                    reserved[slot] = need
                    tid = task.task.task_id
                    plan = pc.admit(tid, toks)
                    kvc.set_table(slot, alloc.table(tid))
                    for src, dst in plan.cow:
                        cache = self._copy_block.call_aot(
                            self._cow_key, cache, jnp.int32(src),
                            jnp.int32(dst))
                    if plan.start == 0:
                        cache, last_logits = self._paged_prefill.call_aot(
                            self._admit_key, self.params, cache, batch,
                            jnp.int32(slot), kvc.table_row(slot))
                    else:
                        key = suffix_shape_key(S - plan.start)
                        pf_start, pf_key = plan.start, str(key)
                        pf_hit = key in self._exec_keys
                        if pf_hit:
                            self.exec_cache_hits += 1
                        else:
                            self._exec_keys.add(key)
                            self.exec_cache_misses += 1
                        tokens_arr, token_chunk, meta, tabs = \
                            build_packed_arrays(
                                key,
                                [(slot, plan.start, toks[plan.start:],
                                  alloc.table(tid))],
                                pad_slot=C,
                                table_width=kvc.max_blocks_per_seq,
                                trash_block=kvc.trash_block)
                        cache, last_logits = self._ragged_prefill.call_aot(
                            self._ragged_aot_key(key), self.params, cache,
                            {"tokens": jnp.asarray(tokens_arr)},
                            jnp.asarray(token_chunk), jnp.asarray(meta),
                            jnp.asarray(tabs), chunk_pad=key[2])
                        last_logits = last_logits[0]   # chunk row 0
                    pc.commit(tid, toks)
                elif paged:
                    reserved[slot] = need
                    kvc.set_table(slot, alloc.allocate_n(
                        task.task.task_id, alloc.blocks_for(S)))
                    cache, last_logits = self._paged_prefill.call_aot(
                        self._admit_key, self.params, cache, batch,
                        jnp.int32(slot), kvc.table_row(slot))
                else:
                    cache, last_logits = self._slot_prefill.call_aot(
                        self._admit_key, self.params, cache, batch,
                        jnp.int32(slot))
                first = int(jnp.argmax(last_logits))
                dt = time.perf_counter() - t0
                now += dt
                self.prefill_dispatches += 1   # one launch per admission
                iter_launches += 1
                if stalled:       # live slots waited out this prefill
                    self.prefill_stall_s += dt
                    iter_stall += dt
                if ob is not None:
                    # emitted AFTER the timed launch region so recording
                    # cost never lands on the virtual clock; the order
                    # (prefix_hit -> exec_cache -> prefill_chunk ->
                    # first_token) is what the simulator mirrors
                    if paged and pc is not None and plan.matched_blocks:
                        ob.event("prefix_hit", now, tid, step,
                                 cached_tokens=plan.start,
                                 matched_blocks=plan.matched_blocks,
                                 cow=len(plan.cow))
                    if pf_key != "admit":
                        ob.event("exec_cache", now, tid, step, hit=pf_hit,
                                 shape_key=pf_key)
                        ob.inc("exec_cache.hits" if pf_hit
                               else "exec_cache.misses")
                    ob.inc("prefill.dispatches")
                    ob.span("prefill.admit", now - dt, dt, task=tid,
                            slot=slot)
                    ob.event("prefill_chunk", now, tid, step, slot=slot,
                             start=pf_start, length=S - pf_start,
                             finishes=True, shape_key=pf_key)
                    ob.event("first_token", now, tid, step, slot=slot)
                    ob.slo_observe("ttft", task.task.traffic_class,
                                   now, now - task.r)
                task.start, task.lane = now, "gpu"
                task.task.start, task.task.lane = now, "gpu"
                task.task.slot = slot
                task.task.out_tokens = [first]
                task.task.token_times = [now]
                self.admission_log.append(
                    {"task_id": task.task.task_id, "slot": slot,
                     "step": step, "now": now})
                if first == self.eos_id or cap <= 1:
                    task.finish = now
                    task.task.finish, task.task.out_len = now, 1
                    done.append(task)
                    if ob is not None:
                        ob.event("complete", now, tid, step, lane="gpu",
                                 out_len=1)
                        ob.event("evict", now, tid, step, slot=slot)
                        ob.inc("sched.completions")
                        ob.complete_request(task.task.traffic_class,
                                            now, u=task.u, out_len=1,
                                            latency_s=now - task.r)
                    if paged:
                        alloc.free_sequence(task.task.task_id)
                        kvc.clear_table(slot)
                        reserved[slot] = 0
                else:
                    slot_task[slot] = task
                    slot_gen[slot], slot_cap[slot] = 1, cap
                    tokens[slot, 0] = first

            self.prefill_stall_max_s = max(self.prefill_stall_max_s,
                                           iter_stall)
            if iter_launches:
                self.prefill_dispatch_trace.append(iter_launches)
            active = [s for s in range(C) if slot_task[s] is not None]
            if active:
                self.peak_concurrency = max(self.peak_concurrency,
                                            len(active))
                # --- one N-step decode WINDOW over ALL slots: a single
                # scanned launch; the completion worker handles the
                # blocking readback off the scheduler thread, and all
                # bookkeeping (token recording, eviction) happens at
                # window end, in arrears
                nsteps = self.decode_steps
                t0 = time.perf_counter()
                if paged:
                    with phase("tables"):
                        self._extend_block_tables(active, slot_task,
                                                  slot_gen, slot_cap,
                                                  alloc, kvc, nsteps)
                        tables = kvc.tables_device()
                    with phase("launch"):
                        window_tok, cache = \
                            self._paged_decode_steps.call_aot(
                                self._window_key, self.params, cache,
                                jnp.asarray(tokens), tables,
                                num_steps=nsteps)
                        self._worker.submit(window_tok, t0, kind="decode")
                else:
                    with phase("launch"):
                        window_tok, cache = self._decode_steps_fn.call_aot(
                            self._window_key, self.params, cache,
                            jnp.asarray(tokens), num_steps=nsteps)
                        self._worker.submit(window_tok, t0, kind="decode")
                with phase("wait"):
                    window_host, dt = self._worker.collect()
                with phase("advance"):
                    if rf is not None:
                        # straggler fault (SlowFault): stretch the
                        # window's charge to the virtual clock.
                        # Wall-only — parity streams strip time fields
                        # by construction.
                        dt *= rf.slow_factor(step)
                    now += dt
                    step += nsteps
                    self.decode_dispatches += 1
                    self.decode_steps_total += nsteps
                    self.decode_dispatch_trace.append(nsteps)
                    if paged:
                        self.kv_util_samples.append(alloc.utilization())
                    else:
                        self.kv_util_samples.append(len(active) / C)
                    if ob is not None:
                        ob.inc("decode.dispatches")
                        ob.inc("decode.steps", nsteps)
                        ob.gauge("kv.util", self.kv_util_samples[-1])
                        ob.counter_sample("kv.util", now,
                                          self.kv_util_samples[-1])
                        ob.span("decode.window", now - dt, dt,
                                steps=nsteps, active=len(active))
                        ob.event("decode_window", now, None, step,
                                 steps=nsteps, active=len(active), dur=dt)
                    self._advance_decode_window(
                        active, window_host, now, dt, slot_task, slot_gen,
                        slot_cap, tokens, done,
                        alloc=alloc if paged else None,
                        kvc=kvc if paged else None,
                        reserved=reserved if paged else None, step=step)
                    if ob is not None:
                        # snapshot cadence keys off ``step`` (the shared
                        # iteration coordinate), AFTER window bookkeeping
                        # — the simulator snapshots at the identical point
                        ob.maybe_snapshot(
                            now, step, queue_depth=len(queue),
                            active=sum(t is not None for t in slot_task),
                            kv_util=self.kv_util_samples[-1],
                            wall=self.host_phase_s)
                continue

            if bulk and not queue:
                batch, bulk = bulk[:C], bulk[C:]
                now = self._run_batch(batch, "cpu", now)
                done.extend(batch)
                continue

            # idle: advance to the next arrival
            if i < n:
                now = max(now, sim_tasks[i].r)
            else:
                now += self.xi
        if paged:
            kvc.state = cache
        else:
            self.slot_cache = cache
        self.last_step = step
        return self._result(done, n)

    # ------------------------------------------------------------------
    # chunked prefill: token-budgeted prefill/decode interleaving
    # ------------------------------------------------------------------

    def _serve_continuous_chunked(self, requests: Sequence[Request]) -> Dict:
        """Continuous serve with ``prefill="chunked"`` (kv="paged").

        Admission allocates a slot plus the prompt's blocks and enqueues
        a ChunkJob instead of stalling the loop for a full prefill; each
        iteration then packs the token budget — decode tokens first,
        prefill chunks in the policy's uncertainty-priority order — so
        per-iteration prefill work (and therefore every live request's
        ITL) is bounded by ``token_budget``, not by the admission burst.

        Execution is FUSED: the whole iteration's plan becomes one
        ``ChunkBatch`` (``repro.prefill.pack_plans``) and runs through
        a single ragged-prefill launch (``generate.make_ragged_prefill_fn``
        → ``model.prefill_chunks``), with the chunk K/V scatter inside
        — exactly ONE prefill dispatch per iteration instead of one
        scatter + one kernel per chunk (asserted via
        ``prefill_dispatches`` / ``prefill_dispatch_trace``).  Chunk
        writes land at exact position offsets, so output is
        token-for-token identical to the stall-admission paged engine;
        ``simulate_continuous(prefill="chunked")`` drives the same
        ChunkScheduler + pack_plans and reproduces the completion
        order, the per-iteration budget trace AND the dispatch /
        executable-cache counters.
        """
        C = self.num_slots
        S = self.input_bucket
        ob = self.obs
        sim_tasks = self._sim_tasks(requests)
        n = len(sim_tasks)
        queue: List[prio.SimTask] = []
        bulk: List[prio.SimTask] = []
        done: List[prio.SimTask] = []
        phase = functools.partial(host_phase, self.host_phase_s)
        with phase("setup"):
            kvc, alloc, pc, cache = self._paged_setup()
            self._aot_warm(cache, kvc)
        reserved = [0] * C           # per-slot worst-case block holdback
        sched = ChunkScheduler(self.chunk_size, self.token_budget,
                               metrics=ob.metrics if ob is not None
                               else None)
        slot_task: List[Optional[prio.SimTask]] = [None] * C  # decoding
        slot_gen = [0] * C
        slot_cap = [0] * C
        job_cap: Dict[int, int] = {}      # slot -> decode cap
        job_tokens: Dict[int, np.ndarray] = {}  # slot -> padded prompt
        job_row: Dict[int, np.ndarray] = {}     # slot -> host table row
        job_start: Dict[int, int] = {}    # slot -> cached-prefix offset
        tokens = np.zeros((C, 1), np.int32)
        self.admission_log = []
        now = 0.0
        i = 0
        step = 0
        while len(done) < n:
            with phase("admit"):
                while i < n and sim_tasks[i].r <= now + 1e-9:
                    if ob is not None:
                        cls = sim_tasks[i].task.traffic_class
                        ob.event("enqueue", sim_tasks[i].r,
                                 sim_tasks[i].task.task_id, step,
                                 **({"cls": cls} if cls else {}))
                    queue.append(sim_tasks[i])
                    i += 1

                # --- admissions: allocate slot + blocks, enqueue chunk job
                free = [s for s in range(C) if slot_task[s] is None
                        and s not in job_cap]
                while queue and free:
                    running = ([t for t in slot_task if t is not None]
                               + [j.task for j in sorted(sched.jobs,
                                                         key=lambda j: j.seq)])
                    prev_queue = list(queue)
                    t0 = time.perf_counter()
                    task, lane, rest = self.policy.admit(list(queue), now,
                                                         running)
                    self.scheduler_overhead_s += time.perf_counter() - t0
                    if task is None:
                        break
                    queue = list(rest)
                    if lane == "cpu":
                        if ob is not None:
                            ob.event("offload", now, task.task.task_id, step)
                            ob.inc("sched.offloads")
                        bulk.append(task)
                        continue
                    cap = self._cap(task.task)
                    # identical reservation gate to the stall path — the
                    # chunked simulator mirrors it bit for bit
                    need = blocks_for_tokens(S + cap - 1, self.kv_block_size)
                    if need > self.kv_num_blocks - sum(reserved):
                        queue = prev_queue           # leave it queued
                        self._rejected_ids.add(task.task.task_id)
                        if ob is not None:
                            ob.event("reject", now, task.task.task_id, step,
                                     kv_blocks=need)
                            ob.inc("sched.rejections")
                        break
                    slot = free.pop(0)
                    reserved[slot] = need
                    task.task.queue_wait_s = now - task.r
                    if ob is not None:
                        ob.event("admit", now, task.task.task_id, step,
                                 slot=slot, u=task.u, kv_blocks=need)
                        ob.inc("sched.admissions")
                        ob.observe("queue_wait_s", task.task.queue_wait_s)
                        ob.slo_observe("queue_wait",
                                       task.task.traffic_class, now,
                                       task.task.queue_wait_s)
                    # all of the prompt's blocks up front: every chunk
                    # position is backed, but kvc's DECODE table row stays
                    # on the trash page until prefill completes (the decode
                    # step writes a KV entry for every row, and a
                    # mid-prefill slot must not scribble real blocks)
                    toks = self._tokenize_padded(task.task.text)
                    start = 0
                    if pc is not None:
                        # matched prefix blocks are shared into the table;
                        # the chunk job covers only the uncached suffix
                        plan = pc.admit(task.task.task_id, toks)
                        start = plan.start
                        if ob is not None and plan.matched_blocks:
                            ob.event("prefix_hit", now, task.task.task_id,
                                     step, cached_tokens=plan.start,
                                     matched_blocks=plan.matched_blocks,
                                     cow=len(plan.cow))
                        for src, dst in plan.cow:
                            cache = self._copy_block.call_aot(
                                self._cow_key, cache, jnp.int32(src),
                                jnp.int32(dst))
                    else:
                        alloc.allocate_n(task.task.task_id,
                                         alloc.blocks_for(S))
                    row = np.full((kvc.max_blocks_per_seq,), kvc.trash_block,
                                  np.int32)
                    tbl = alloc.table(task.task.task_id)
                    row[:len(tbl)] = tbl
                    job_row[slot] = row
                    job_tokens[slot] = toks
                    job_start[slot] = start
                    job_cap[slot] = cap
                    sched.add(task, slot, S - start,
                              self.policy.assign_priority(task))
                    self.admission_log.append(
                        {"task_id": task.task.task_id, "slot": slot,
                         "step": step, "now": now})

            # --- chunk phase: pack the budget, decode tokens first;
            # the WHOLE plan executes as one fused ragged launch
            with phase("pack"):
                iter_stall = 0.0
                active0 = [s for s in range(C) if slot_task[s] is not None]
                plans = sched.schedule(len(active0)) if sched.has_jobs else []
                batch_plan = pack_plans(plans)
                if batch_plan is not None:
                    key = batch_plan.shape_key
                    hit = key in self._exec_keys
                    if hit:
                        self.exec_cache_hits += 1
                    else:
                        self._exec_keys.add(key)
                        self.exec_cache_misses += 1
                    if ob is not None:
                        ob.event("exec_cache", now, None, step, hit=hit,
                                 shape_key=str(key))
                        ob.inc("exec_cache.hits" if hit
                               else "exec_cache.misses")
                    Tp = batch_plan.padded_chunk_len
                    # chunk offsets are relative to the job (the uncached
                    # suffix); job_start shifts them to absolute prompt
                    # positions when a cached prefix was skipped.  The
                    # packed layout itself (metadata rows, padding rules)
                    # is encoded once in prefill.build_packed_arrays.
                    entries = []
                    for ch in batch_plan.chunks:
                        s = ch.slot
                        base = job_start[s] + ch.start
                        entries.append((s, base,
                                        job_tokens[s][base:base + ch.length],
                                        job_row[s]))
                    tokens_arr, token_chunk, meta, tabs = build_packed_arrays(
                        key, entries, pad_slot=C,
                        table_width=kvc.max_blocks_per_seq,
                        trash_block=kvc.trash_block)
            if batch_plan is not None:
                stalled = any(t is not None for t in slot_task)
                t0 = time.perf_counter()
                with phase("launch"):
                    cache, last_logits = self._ragged_prefill.call_aot(
                        self._ragged_aot_key(key), self.params, cache,
                        {"tokens": jnp.asarray(tokens_arr)},
                        jnp.asarray(token_chunk), jnp.asarray(meta),
                        jnp.asarray(tabs), chunk_pad=Tp)
                    # greedy-pick on device: only (Cp,) token ids cross the
                    # host link, not the (Cp, V) logits; the completion
                    # worker does the blocking readback off this thread
                    self._worker.submit(jnp.argmax(last_logits, axis=-1),
                                        t0, kind="prefill")
                with phase("wait"):
                    next_ids, dt = self._worker.collect()
                with phase("advance"):
                    now += dt
                    self.prefill_dispatches += 1     # ONE launch, all chunks
                    if stalled:      # live slots waited out this launch
                        self.prefill_stall_s += dt
                        iter_stall += dt
                    if ob is not None:
                        ob.inc("prefill.dispatches")
                        ob.span("prefill.ragged", now - dt, dt,
                                chunks=len(batch_plan.chunks),
                                tokens=batch_plan.total_tokens)
                        for ch in batch_plan.chunks:
                            ob.event("prefill_chunk", now,
                                     ch.job.task.task.task_id, step,
                                     slot=ch.slot, start=ch.start,
                                     length=ch.length, finishes=ch.finishes,
                                     shape_key=str(key))
                    for ci, ch in enumerate(batch_plan.chunks):
                        if not ch.finishes:
                            continue
                        s = ch.slot
                        task = ch.job.task
                        first = int(next_ids[ci])
                        if pc is not None:
                            pc.commit(task.task.task_id, job_tokens[s])
                        cap = job_cap.pop(s)
                        del job_tokens[s], job_row[s], job_start[s]
                        task.start, task.lane = now, "gpu"
                        task.task.start, task.task.lane = now, "gpu"
                        task.task.slot = s
                        task.task.out_tokens = [first]
                        task.task.token_times = [now]
                        if ob is not None:
                            ob.event("first_token", now, task.task.task_id,
                                     step, slot=s)
                            ob.slo_observe("ttft", task.task.traffic_class,
                                           now, now - task.r)
                        if first == self.eos_id or cap <= 1:
                            task.finish = now
                            task.task.finish, task.task.out_len = now, 1
                            done.append(task)
                            if ob is not None:
                                ob.event("complete", now, task.task.task_id,
                                         step, lane="gpu", out_len=1)
                                ob.event("evict", now, task.task.task_id,
                                         step, slot=s)
                                ob.inc("sched.completions")
                                ob.complete_request(
                                    task.task.traffic_class, now,
                                    u=task.u, out_len=1,
                                    latency_s=now - task.r)
                            alloc.free_sequence(task.task.task_id)
                            reserved[s] = 0
                        else:
                            # install the real table: the slot joins THIS
                            # iteration's decode step (as a stall admission
                            # would), writing token 1's KV at position S
                            kvc.set_table(s, alloc.table(task.task.task_id))
                            slot_task[s] = task
                            slot_gen[s], slot_cap[s] = 1, cap
                            tokens[s, 0] = first
            prefill_toks = sum(p.length for p in plans)
            self.prefill_stall_max_s = max(self.prefill_stall_max_s,
                                           iter_stall)

            active = [s for s in range(C) if slot_task[s] is not None]
            nsteps = self.decode_steps
            if plans or active:
                self.budget_trace.append((len(active0), prefill_toks))
                self.prefill_dispatch_trace.append(1 if plans else 0)
                # aligned with budget_trace: steps launched this
                # iteration (0 = prefill-only iteration, no decode)
                self.decode_dispatch_trace.append(nsteps if active else 0)
            if active:
                self.peak_concurrency = max(self.peak_concurrency,
                                            len(active))
                # --- one N-step decode WINDOW over ALL slots (see
                # _serve_continuous; identical launch/readback recipe)
                t0 = time.perf_counter()
                with phase("tables"):
                    self._extend_block_tables(active, slot_task, slot_gen,
                                              slot_cap, alloc, kvc, nsteps)
                    tables = kvc.tables_device()
                with phase("launch"):
                    window_tok, cache = self._paged_decode_steps.call_aot(
                        self._window_key, self.params, cache,
                        jnp.asarray(tokens), tables, num_steps=nsteps)
                    self._worker.submit(window_tok, t0, kind="decode")
                with phase("wait"):
                    window_host, dt = self._worker.collect()
                with phase("advance"):
                    now += dt
                    step += nsteps
                    self.decode_dispatches += 1
                    self.decode_steps_total += nsteps
                    self.kv_util_samples.append(alloc.utilization())
                    if ob is not None:
                        ob.inc("decode.dispatches")
                        ob.inc("decode.steps", nsteps)
                        ob.gauge("kv.util", self.kv_util_samples[-1])
                        ob.counter_sample("kv.util", now,
                                          self.kv_util_samples[-1])
                        ob.span("decode.window", now - dt, dt,
                                steps=nsteps, active=len(active))
                        ob.event("decode_window", now, None, step,
                                 steps=nsteps, active=len(active), dur=dt)
                    self._advance_decode_window(
                        active, window_host, now, dt, slot_task, slot_gen,
                        slot_cap, tokens, done, alloc=alloc, kvc=kvc,
                        reserved=reserved, step=step)
                    if ob is not None:
                        # same post-window snapshot point as the stall
                        # loop
                        ob.maybe_snapshot(
                            now, step, queue_depth=len(queue),
                            active=sum(t is not None for t in slot_task),
                            kv_util=self.kv_util_samples[-1],
                            wall=self.host_phase_s)
                continue
            if plans:
                continue

            if bulk and not queue:
                batch, bulk = bulk[:C], bulk[C:]
                now = self._run_batch(batch, "cpu", now)
                done.extend(batch)
                continue

            # idle: advance to the next arrival
            if i < n:
                now = max(now, sim_tasks[i].r)
            else:
                now += self.xi
        kvc.state = cache
        return self._result(done, n)
