"""Serving launcher: RT-LM scheduling over the production JAX engine.

    # published widths, on a TPU
    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b
    # the 2-layer smoke variant, on the CPU
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
        --arch starcoder2-3b --smoke --n-requests 8

Runs the full RT-LM ecosystem end to end: offline profiling (predictor
training, tau), then a Poisson request trace served by the production
engine — continuous batching over a paged KV pool, chunked prefill and
the prefix cache.  ``build`` and ``make_engine`` are the setup that
``chip_smoke.py`` drives too.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
from typing import List, Optional, Sequence

import jax

from repro import configs
from repro.core import datagen, personas, scheduler as sched_lib, workload
from repro.launch import compile_cache
from repro.models import model as model_lib
from repro.serving.engine import Request, ServingEngine
from repro.serving.replica import ReplicatedEngine
from repro.serving.router import Router


@dataclasses.dataclass
class Setup:
    """Everything a serve needs besides the engine."""

    cfg: configs.ModelConfig
    params: dict
    profile: sched_lib.OfflineProfile
    policy: sched_lib.Policy
    texts: List[str]
    arrivals: List[float]
    max_new_tokens: int

    def requests(self) -> List[Request]:
        """A fresh request list (a serve fills its requests in)."""
        return [Request(text=t, arrival=a, task_id=i,
                        max_new_tokens=self.max_new_tokens)
                for i, (t, a) in enumerate(zip(self.texts, self.arrivals))]


def init_params(cfg, seed: int) -> dict:
    """Random parameters from ``seed``, made inside ``jax.jit`` so that
    no float32 draw of a whole layer stack is materialised eagerly."""
    return jax.jit(functools.partial(model_lib.init_params, cfg=cfg))(
        jax.random.PRNGKey(seed))


def build(arch: str, *, smoke: bool = False, policy: str = "rt-lm",
          persona: str = "dialogpt", n_requests: int = 200,
          betas: Sequence[int] = (120, 240), max_new_tokens: int = 16,
          seed: int = 0) -> Setup:
    """Model, offline profile, Poisson trace and policy for one serve.

    ``smoke`` picks the architecture's reduced CPU variant; the default
    is the published configuration."""
    cfg = (configs.get_smoke_config(arch) if smoke
           else configs.get_config(arch))
    params = init_params(cfg, seed)
    pers = personas.get_persona(persona)
    corpus = datagen.generate_corpus(
        datagen.VARIANCE_MIXES["normal"], n_requests * 2, seed=seed)
    train, test = datagen.train_test_split(corpus, train_frac=0.5)
    test = test[:n_requests]
    profile = sched_lib.offline_profile(train, pers, epochs=40, seed=seed)
    arrivals = workload.poisson_trace(len(test), betas=list(betas),
                                      seed=seed + 1)
    pol = sched_lib.POLICIES[policy](pers, profile.policy_config())
    return Setup(cfg=cfg, params=params, profile=profile, policy=pol,
                 texts=[t.text for t in test], arrivals=list(arrivals),
                 max_new_tokens=max_new_tokens)


def make_engine(setup: Setup, *, input_bucket: int = 512,
                chunk_size: int = 256, num_slots: Optional[int] = None,
                kv_num_blocks: Optional[int] = None, replicas: int = 1,
                devices: Optional[Sequence] = None):
    """The production engine: continuous batching, paged KV, chunked
    prefill and the prefix cache, with EOS off (``eos_id=-1``) so that
    every request yields exactly ``max_new_tokens`` tokens.
    ``replicas > 1`` (or explicit ``devices``) serves R such engines
    behind the rtlm router."""
    kw = dict(mode="continuous", kv="paged", prefill="chunked",
              prefix_cache=True, input_bucket=input_bucket,
              max_new_tokens=setup.max_new_tokens, eos_id=-1,
              chunk_size=chunk_size, num_slots=num_slots,
              kv_num_blocks=kv_num_blocks)
    if replicas == 1 and devices is None:
        return ServingEngine(setup.params, setup.cfg, setup.policy,
                             setup.profile, **kw)
    return ReplicatedEngine(setup.params, setup.cfg, setup.policy,
                            setup.profile, replicas=replicas,
                            router=Router(replicas, "rtlm"),
                            devices=devices, **kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced CPU variant of --arch")
    ap.add_argument("--policy", default="rt-lm",
                    choices=tuple(sched_lib.POLICIES))
    ap.add_argument("--persona", default="dialogpt",
                    choices=personas.PERSONA_NAMES)
    ap.add_argument("--n-requests", type=int, default=200)
    ap.add_argument("--beta", default="120,240",
                    help="comma-separated per-minute arrival rates")
    ap.add_argument("--input-bucket", type=int, default=512)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    setup = build(args.arch, smoke=args.smoke, policy=args.policy,
                  persona=args.persona, n_requests=args.n_requests,
                  betas=[int(b) for b in args.beta.split(",")],
                  max_new_tokens=args.max_new_tokens, seed=args.seed)
    engine = make_engine(setup, input_bucket=args.input_bucket)
    reqs = setup.requests()
    print(f"[serve] serving {len(reqs)} requests under {args.policy} "
          f"(arch={setup.cfg.name})...")
    res = engine.serve(reqs)
    out = {k: v for k, v in res.items() if k != "tasks"}
    out["scheduler_overhead_ms_per_task"] = (
        1000.0 * res["scheduler_overhead_s"] / res["n_tasks"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
