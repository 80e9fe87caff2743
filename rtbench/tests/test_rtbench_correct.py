"""``correct`` at test size on the CPU: sound runs pass the limit, the
control (the reference in float8) fails it, and a run whose timed path
is broken underneath comes out not correct.  These drive the whole run
except the look for a chip."""

import json
import time
import weakref
from collections import OrderedDict
from pathlib import Path

import jax.numpy as jnp
import pytest

from rtbench import gen, harness

HERE = Path(__file__).resolve().parents[1]
CONFIG = json.loads((HERE / "tests" / "data" / "tiny-gqa.json").read_text())
KNOBS = {"rate_per_s": 3.0, "policy": "up+c",
         "engine": {"input_bucket": 32, "chunk_size": 32, "num_slots": 4,
                    "kv_num_blocks": 128, "max_new_tokens": 352},
         "check_tokens": 1500,
         # program 0.0056-0.0096, control 0.039-0.077 on six seeds (CPU)
         "limits": {"served_logit_gap": 0.02}}
NO_METRICS = {"end_to_end": [], "per_layer": []}


def _cell():
    return harness.Cell(
        name="tiny-chat", entry={"name": "tiny-chat", "chips": 1},
        config=CONFIG, knobs=KNOBS,
        mix=gen.load_mix(HERE / "traffic" / "chat_uncertain.json"))


def _run(seed, tmp_path):
    return harness.Run(_cell(), seed, 4.0, False,
                       t_start=time.perf_counter(), out_dir=tmp_path,
                       log=lambda m: None, require_tpu=False)


def test_sound_runs_pass_and_the_control_fails(tmp_path):
    limit = KNOBS["limits"]["served_logit_gap"]
    for seed in (2**31 + 5, 2**31 + 6):
        run = _run(seed, tmp_path)
        result = harness.execute(run, NO_METRICS)
        assert result["correct"], result["checks"]
        assert result["failed"] == 0
        assert run.sample_tokens >= KNOBS["check_tokens"]
        assert run.control_gap() > limit


def _fresh_executables(monkeypatch):
    """New jitted entry points, so that the patched model is traced."""
    from repro.serving import generate
    monkeypatch.setattr(generate, "_fn_memo", weakref.WeakValueDictionary())
    monkeypatch.setattr(generate, "_fn_lru", OrderedDict())


def _state_unchanged(orig):
    def step(params, cfg, cache, token, tables, **kw):
        toks, _ = orig(params, cfg, cache, token, tables, **kw)
        return toks, cache
    return step


def _token_altered(orig):
    def step(params, cfg, cache, token, tables, **kw):
        toks, new = orig(params, cfg, cache, token, tables, **kw)
        return (toks + 1) % cfg.vocab_size, new
    return step


def _half_batch_left_out(orig):
    def step(params, cfg, cache, token, tables, **kw):
        toks, new = orig(params, cfg, cache, token, tables, **kw)
        half = toks.shape[0] // 2
        return toks.at[half:].set(jnp.zeros_like(toks[half:])), new
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_batch_left_out])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch, tmp_path):
    from repro.models import model as model_lib
    _fresh_executables(monkeypatch)
    monkeypatch.setattr(model_lib, "decode_steps_paged",
                        fault(model_lib.decode_steps_paged))
    result = harness.execute(_run(2**31 + 5, tmp_path), NO_METRICS)
    assert not result["correct"]
    gap = result["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
