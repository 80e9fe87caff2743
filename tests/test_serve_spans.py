"""The serve loop's host phases and the completion worker's readbacks on
the profiler's clock: every phase of the chunked serve loop runs under a
``serve:<phase>`` span and adds its wall seconds to ``host_phase_s``;
every readback runs under ``readback:<kind>``; none of it changes what
the serve computes."""

import collections
import glob
import os
import time

import jax
import pytest

from repro.launch import serve
from repro.serving import generate
from repro.serving.engine import Request
from repro.serving.pipeline import host_phase

PHASES = {"predict", "setup", "admit", "pack", "launch", "tables", "wait",
          "advance"}


@pytest.fixture(scope="module")
def smoke_setup():
    return serve.build("starcoder2-3b", smoke=True, n_requests=6,
                       max_new_tokens=5, seed=0)


def _requests(setup):
    """Half the requests at once, half after the first half has drained:
    admission does not hang on how long a launch takes on the host."""
    half = len(setup.texts) // 2
    return [Request(text=t, arrival=0.0 if i < half else 100.0, task_id=i,
                    max_new_tokens=setup.max_new_tokens)
            for i, t in enumerate(setup.texts)]


def _host_span_counts(log_dir) -> collections.Counter:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    counts: collections.Counter = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                counts.update(e.name for e in line.events)
    return counts


def _timed_serve(engine, reqs):
    t0 = time.perf_counter()
    res = engine.serve(reqs)
    return res, time.perf_counter() - t0


def test_phases_and_readbacks_in_a_profile(smoke_setup, tmp_path):
    engine = serve.make_engine(smoke_setup, input_bucket=32, chunk_size=16)
    plain, plain_wall = _timed_serve(engine, _requests(smoke_setup))
    with jax.profiler.trace(str(tmp_path)):
        traced, traced_wall = _timed_serve(engine, _requests(smoke_setup))
    counts = _host_span_counts(str(tmp_path))
    for p in PHASES:
        assert counts[f"serve:{p}"] > 0, p
    assert counts["readback:decode"] == traced["decode_dispatches"] > 0
    assert counts["readback:prefill"] == traced["prefill_dispatches"] > 0
    # the phases tile each serve, and the dict starts afresh per serve:
    # the first serve's AOT compiles would push the second's sum over
    # its wall time otherwise
    for res, wall in ((plain, plain_wall), (traced, traced_wall)):
        assert set(res["host_phase_s"]) == PHASES
        assert sum(res["host_phase_s"].values()) == \
            pytest.approx(wall, rel=0.1)
    assert plain["host_phase_s"]["setup"] > traced["host_phase_s"]["setup"]
    # a profiler attached changes nothing the serve computes
    assert traced["completion_order"] == plain["completion_order"]
    assert ([t.task.out_tokens for t in traced["tasks"]]
            == [t.task.out_tokens for t in plain["tasks"]])


def test_aot_misses_count_the_serves_jit_dispatches(smoke_setup, tmp_path):
    """Without AOT warm-up (and at dims no engine warmed) every launch
    misses the AOT store: each runs under ``dispatch:<kind>:jit`` and
    counts once, per serve."""
    engine = serve.make_engine(smoke_setup, input_bucket=32, chunk_size=16,
                               num_slots=5)
    engine.aot_warmup = False
    with jax.profiler.trace(str(tmp_path)):
        res = engine.serve(_requests(smoke_setup))
    jit_spans = sum(c for name, c in _host_span_counts(str(tmp_path)).items()
                    if name.startswith("dispatch:") and name.endswith(":jit"))
    assert res["aot_misses"] == jit_spans == (
        res["prefill_dispatches"] + res["decode_dispatches"]
        + res["cow_copies"]) > 0
    assert engine.serve(_requests(smoke_setup))["aot_misses"] == \
        res["aot_misses"]


def test_call_aot_miss_runs_the_jit_function_under_its_own_span():
    calls = []
    exe = generate.JitExecutable(
        lambda x, **kw: calls.append(("jit", x, kw)) or x, "probe")
    exe.aot["k"] = lambda x: calls.append(("aot", x)) or x
    assert exe.call_aot("k", 1) == 1
    assert exe.call_aot("other", 2, chunk_pad=4) == 2
    assert calls == [("aot", 1), ("jit", 2, {"chunk_pad": 4})]
    assert exe.aot_misses == 1
    assert (exe.name, exe.jit_name) == ("dispatch:probe",
                                        "dispatch:probe:jit")


def test_host_phase_adds_up_and_survives_a_raise():
    phases = {}
    with host_phase(phases, "pack"):
        time.sleep(0.01)
    with host_phase(phases, "pack"):
        pass
    with pytest.raises(RuntimeError):
        with host_phase(phases, "wait"):
            raise RuntimeError("device failed")
    assert set(phases) == {"pack", "wait"}
    assert phases["pack"] >= 0.01
