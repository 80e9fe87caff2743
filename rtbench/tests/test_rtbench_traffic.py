"""The traffic generator: seeds, length ranges and medians, and the
correlation of chat answers with uncertainty."""

import json
from pathlib import Path

import numpy as np
import pytest

from rtbench import gen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
# mixes that no cell runs yet, kept to test the generator's other prompt,
# output and arrival models
DATA = Path(__file__).resolve().parent / "data"
EXTRA = ["chat_uncertain_burst", "code_complete"]
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json")) + EXTRA


def _mix(name):
    return gen.load_mix((DATA if name in EXTRA else TRAFFIC) / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_other_order(name):
    mix = _mix(name)
    a = gen.generate(mix, 6.0, 20.0, 2**31 + 7)
    b = gen.generate(mix, 6.0, 20.0, 2**31 + 7)
    c = gen.generate(mix, 6.0, 20.0, 2**31 + 8)
    assert a == b
    assert a != c
    # another seed changes the order and the words, not the work
    assert sorted(r.out_len for r in a) == sorted(r.out_len for r in c)
    assert sorted(len(r.text.split()) for r in a) == sorted(
        len(r.text.split()) for r in c)
    assert len(a) == len(c)


@pytest.mark.parametrize("name", MIXES)
def test_arrivals_fill_the_window_at_the_rate(name):
    mix = _mix(name)
    reqs = gen.generate(mix, 8.0, 30.0, 3)
    arr = np.array([r.arrival for r in reqs])
    assert arr.min() > 0 and arr.max() <= 30.0 + 1e-6
    mean_mult = sum((hi - lo) * m for lo, hi, m in
                    mix["arrival"]["segments"])
    assert len(reqs) == pytest.approx(8.0 * 30.0 * mean_mult, abs=len(
        mix["arrival"]["segments"]))


def test_code_lengths_as_stated():
    mix = _mix("code_complete")
    reqs = gen.generate(mix, 20.0, 30.0, 11)
    p, o = mix["prompt"]["length"], mix["output"]
    plen = np.array([len(r.text.split()) for r in reqs])
    olen = np.array([r.out_len for r in reqs])
    assert plen.min() >= p["min"] and plen.max() <= p["max"]
    assert np.all(plen % p["multiple"] == 0)
    assert np.median(plen) == pytest.approx(p["median"], rel=0.07)
    assert olen.min() >= o["min"] and olen.max() <= o["max"]
    assert np.median(olen) == pytest.approx(o["median"], rel=0.07)


@pytest.mark.parametrize("name", ["chat_uncertain", "chat_uncertain_burst"])
def test_chat_lengths_and_uncertainty(name):
    mix = _mix(name)
    reqs = gen.generate(mix, 20.0, 30.0, 5)
    words = np.array([len(r.text.split()) for r in reqs])
    out = np.array([r.out_len for r in reqs])
    u = np.array([r.u for r in reqs])
    assert words.max() <= 23
    scale = mix["output"]["scale"]
    assert np.all(out % scale == 0)
    assert out.min() >= scale
    assert out.max() <= scale * mix["output"]["persona"]["max_output"]
    # answers about 25 tokens long per unit of scale (the persona's mean)
    assert 20 * scale <= out.mean() <= 30 * scale
    assert np.corrcoef(u, out)[0, 1] > 0.9


def test_burst_mix_is_the_steady_mix_in_bursts():
    steady, burst = _mix("chat_uncertain"), _mix("chat_uncertain_burst")
    assert steady["prompt"] == burst["prompt"]
    assert steady["output"] == burst["output"]
    mults = [m for _, _, m in burst["arrival"]["segments"]]
    assert max(mults) == 2.5 and min(mults) == 0.5
    assert sum((hi - lo) * m for lo, hi, m in
               burst["arrival"]["segments"]) == pytest.approx(1.25)


def test_hash_ids_left_pads_and_truncates():
    ids = gen.hash_ids("a b c", 100, 8)
    assert list(ids[:5]) == [0] * 5 and all(2 <= i < 100 for i in ids[5:])
    assert gen.hash_ids("a b c", 100, 8).tolist() == ids.tolist()
    long = " ".join(f"w{i}" for i in range(20))
    assert gen.hash_ids(long, 100, 8).tolist() == gen.hash_ids(
        " ".join(f"w{i}" for i in range(8)), 100, 8).tolist()


def test_profile_corpus_is_fixed():
    mix = {"plain": 0.5, "vague": 0.5}
    persona = {"name": "p", "base_output": 8.0, "uncertainty_gain": 2.6,
               "noise_std": 2.5, "max_output": 128}
    a = gen.profile_corpus(mix, persona, 50, 1)
    b = gen.profile_corpus(mix, persona, 50, 1)
    assert [(t.text, t.out_lens) for t in a] == [(t.text, t.out_lens)
                                                for t in b]
    assert all(1 <= t.out_lens["p"] <= 128 for t in a)
