"""The harness without a chip: files found by name, the refusal to run
off the chip, and the end-to-end readers on a hand-made run record.
No TPU library is loaded: the runs below are child processes held to
the CPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from rtbench import harness

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent


def test_files_are_found_by_name(tmp_path):
    for sub in ("configs", "traffic", "cells", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "m-x.json").write_text(json.dumps({"a": 1}))
    (tmp_path / "traffic" / "t_y.json").write_text(json.dumps({"b": 2}))
    (tmp_path / "cells" / "c1.json").write_text(json.dumps({"c": 3}))
    (tmp_path / "metrics" / "new.metric_s.py").write_text(
        "def read(run):\n    return run.x * 2\n")
    bench = {"workloads": [{"name": "c1", "config": "m-x",
                            "traffic": "t_y", "chips": 1}]}
    cell = harness.load_cell("c1", bench, here=tmp_path)
    assert (cell.config, cell.mix, cell.knobs) == ({"a": 1}, {"b": 2},
                                                   {"c": 3})
    mod = harness.reader("new.metric_s", here=tmp_path)
    assert mod.read(SimpleNamespace(x=21)) == 42
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("c2", bench, here=tmp_path)


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = harness.load_bench(ROOT)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(harness.reader(m["name"]).read), m["name"]
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert cell.knobs["policy"] == "up+c"     # no emulated CPU lane
        harness.reference(cell.config["reference"])


def _run_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "rtbench/run.py", "--workload", "sc2-chat-steady",
         "--seed", "2147483655", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_without_a_tpu_fails_and_prints_no_result():
    p = _run_cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "rtbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cmd(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "program is missing" in p.stderr


def test_end_to_end_readers_on_a_hand_made_run():
    run = SimpleNamespace(
        ttft=[i / 10 for i in range(1, 21)], itl=[0.01] * 9 + [0.11],
        response=[1.0, 2.0, 4.5], tokens=1000, wall_s=4.0, setup_s=12.5,
        warmup_compile_s=3.0, queue_wait=[0.0, 0.2, 0.4],
        sched_overhead_s=0.02, res={"n_tasks": 4, "decode_steps_executed": 80},
        trace_summary=None,
        work=None, device={"kind": "cpu"})
    read = {m: harness.reader(m).read(run) for m in (
        "ttft_p95_s", "itl_p95_s", "response_mean_s", "step_wall_ms",
        "setup_s", "setup.warmup_compile_s", "sched.queue_wait_p95_s",
        "sched.overhead_ms_per_req", "device.idle_share",
        "mfu.prefill", "mfu.decode.lat", "paged_decode_roofline.lat")}
    # exact percentiles, linear between order statistics
    assert read["ttft_p95_s"] == pytest.approx(1.905)
    assert read["itl_p95_s"] == pytest.approx(0.01 + 0.55 * 0.1)
    assert read["response_mean_s"] == pytest.approx(2.5)
    assert read["step_wall_ms"] == pytest.approx(50.0)
    assert read["setup_s"] == 12.5
    assert read["setup.warmup_compile_s"] == 3.0
    assert read["sched.queue_wait_p95_s"] == pytest.approx(0.38)
    assert read["sched.overhead_ms_per_req"] == pytest.approx(5.0)
    # nothing to read without a trace: left out, never 0
    assert read["device.idle_share"] is None
    assert read["mfu.prefill"] is None
    assert read["mfu.decode.lat"] is None
    assert read["paged_decode_roofline.lat"] is None


def test_cached_upper_bound():
    import numpy as np
    a = np.array([0] * 16 + [5] * 16, np.int32)
    b = np.array([0] * 16 + [6] * 16, np.int32)
    c = np.array([7] * 32, np.int32)
    # a and b share their first block; a appears twice: all of it
    assert harness.cached_upper([a, b, c]) == [16, 16, 0]
    assert harness.cached_upper([a, a, c]) == [31, 31, 0]
