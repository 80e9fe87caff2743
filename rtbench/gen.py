"""The one traffic generator: a mix's data file in, a request list out.

A mix (``traffic/<name>.json``) names a prompt model, an output-length
model and the shape of its arrivals; the cell (``cells/<name>.json``)
gives the rate.  Every seed gets the same work in another order: the
prompt and output lengths are fixed quantiles (or a pool drawn from the
mix's own ``corpus_seed``), the inter-arrival gaps are fixed quantiles
of the exponential distribution, and ``--seed`` shuffles them and draws
the words.  So runs with different seeds differ in order and content,
not in the amount of work.

Prompt models (``prompt.kind``):
  * ``utterance`` -- chat utterances of the six uncertainty types
    (``corpus.py``) in the proportions of ``prompt.mix``;
  * ``words``     -- random words, lognormal length (``prompt.length``).
Output models (``output.kind``):
  * ``persona``   -- the utterance's persona output length times
    ``output.scale`` (keeps the correlation with uncertainty);
  * ``lognormal`` -- lognormal length, independent of the prompt.
Arrivals (``arrival.segments``): ``[start_frac, end_frac, multiplier]``
pieces of the window, each an open-loop Poisson stream at ``multiplier``
times the cell's rate.
"""

from __future__ import annotations

import dataclasses
import json
import math
import random
import statistics
from typing import Dict, List, Optional

import numpy as np

from rtbench import corpus


@dataclasses.dataclass
class Req:
    """One offered request: its text, due time (s) and output length."""

    text: str
    arrival: float
    out_len: int
    u: Optional[float] = None          # true uncertainty (utterances only)


def load_mix(path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _quantile_lengths(spec: Dict, n: int) -> List[int]:
    """``n`` lognormal quantiles, clipped to ``[min, max]`` and rounded
    to ``multiple``: the same multiset for every seed."""
    mult = spec.get("multiple", 1)
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"]
                                      * nd.inv_cdf((i + 0.5) / n))
        x = min(max(x, spec["min"]), spec["max"])
        out.append(int(max(mult, round(x / mult) * mult)))
    return out


def _arrivals(segments, rate: float, seconds: float,
              rng: np.random.Generator) -> List[float]:
    """Open-loop arrivals: per segment, the quantiles of the exponential
    gap at the segment's rate, shuffled, scaled to fill the segment."""
    out: List[float] = []
    for lo, hi, mult in segments:
        span = (hi - lo) * seconds
        n = int(round(rate * mult * span))
        if n == 0:
            continue
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
        rng.shuffle(gaps)
        gaps *= span / gaps.sum()
        out.extend((lo * seconds + np.cumsum(gaps)).tolist())
    return out


def generate(mix: Dict, rate: float, seconds: float, seed: int) -> List[Req]:
    """The requests due in ``seconds`` at ``rate`` requests/s."""
    rng = np.random.default_rng(seed)
    arrivals = _arrivals(mix["arrival"]["segments"], rate, seconds, rng)
    n = len(arrivals)
    p, o = mix["prompt"], mix["output"]
    us: List[Optional[float]] = [None] * n
    if p["kind"] == "utterance":
        pool = random.Random(p["corpus_seed"])
        types = list(p["mix"])
        weights = [p["mix"][t] for t in types]
        items = []
        for _ in range(n):
            text, u = corpus.utterance(pool.choices(types, weights)[0], pool)
            ln = (corpus.output_length(u, o["persona"], pool) * o["scale"]
                  if o["kind"] == "persona" else None)
            items.append((text, u, ln))
        order = rng.permutation(n)
        texts = [items[i][0] for i in order]
        us = [items[i][1] for i in order]
        outs = [items[i][2] for i in order]
    elif p["kind"] == "words":
        lens = _quantile_lengths(p["length"], n)
        rng.shuffle(lens)
        texts = [" ".join(f"w{w}" for w in rng.integers(0, 10**9, ln))
                 for ln in lens]
        outs = [None] * n
    else:
        raise ValueError(f"unknown prompt kind {p['kind']!r}")
    if o["kind"] == "lognormal":
        outs = _quantile_lengths(o, n)
        rng.shuffle(outs)
    elif o["kind"] != "persona" or p["kind"] != "utterance":
        raise ValueError(f"output kind {o['kind']!r} does not fit prompt "
                         f"kind {p['kind']!r}")
    return [Req(text=t, arrival=float(a), out_len=int(ln), u=u)
            for t, a, ln, u in zip(texts, arrivals, outs, us)]


def profile_corpus(mix: Dict, persona: Dict, n: int, seed: int):
    """Training utterances for the program's offline profile: objects
    with ``text`` and ``out_lens[persona name]`` (the persona's own,
    unscaled lengths), drawn from ``mix`` with ``seed``."""
    rng = random.Random(seed)
    types = list(mix)
    weights = [mix[t] for t in types]
    out = []
    for _ in range(n):
        text, u = corpus.utterance(rng.choices(types, weights)[0], rng)
        out.append(_Utterance(text, {persona["name"]:
                                     corpus.output_length(u, persona, rng)}))
    return out


@dataclasses.dataclass
class _Utterance:
    text: str
    out_lens: Dict[str, int]


def hash_ids(text: str, vocab_size: int, bucket: int) -> np.ndarray:
    """The served prompt as token ids: one id per word (FNV-1a hash into
    ``2 .. vocab_size - 1``), first ``bucket`` words, left-padded with 0
    to ``bucket``.  The program tokenizes text by this rule; the copy
    here lets the reference see the same ids without the program."""
    ids = []
    for w in text.lower().split()[:bucket]:
        h = 2166136261
        for c in w.encode():
            h = ((h ^ c) * 16777619) & 0xFFFFFFFF
        ids.append(2 + (h % (vocab_size - 2)))
    ids = ids or [2]
    out = np.zeros((bucket,), np.int32)
    out[bucket - len(ids):] = ids
    return out
