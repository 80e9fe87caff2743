"""The serving entry point, device placement and the compile cache."""

import os
import subprocess
import sys
import textwrap

import pytest

import jax

from repro.launch import compile_cache, serve
from repro.serving.engine import params_device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("env", [None, "/some/cache"])
def test_compile_cache_dir_follows_env_else_fixed_path(monkeypatch, env):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    if env is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env)
        want = env
    assert compile_cache.cache_dir() == want
    assert compile_cache.enable() == want
    # with the variable set, JAX reads it itself and no other is set
    assert updates == ([] if env else
                       [("jax_compilation_cache_dir", want)])


def test_benchmark_runner_exits_nonzero_when_a_phase_raises(monkeypatch,
                                                            capsys):
    from benchmarks import run

    def boom():
        raise RuntimeError("phase failed")

    monkeypatch.setattr(run.paper_tables, "ALL", {"broken": boom})
    for name in ("run_kernels", "run_continuous", "run_roofline"):
        monkeypatch.setattr(run, name, lambda *a, **k: None)
    monkeypatch.setattr(run.compile_cache, "enable", lambda: "")
    assert run.main([]) == 1
    out = capsys.readouterr().out
    assert "broken," in out and "ERROR:phase failed" in out


def test_params_device_is_the_default_device_for_uncommitted_params():
    params = {"w": jax.numpy.ones((2, 2))}
    assert params_device(params) == jax.devices()[0]
    committed = jax.device_put(params, jax.devices()[0])
    assert params_device(committed) == jax.devices()[0]


@pytest.fixture(scope="module")
def smoke_setup():
    return serve.build("starcoder2-3b", smoke=True, n_requests=4,
                       max_new_tokens=3, seed=0)


def test_serve_setup_drives_the_production_engine(smoke_setup):
    """``serve.build`` + ``make_engine`` (the path chip_smoke.py takes):
    continuous, paged, chunked prefill with the prefix cache, EOS off,
    so every request yields exactly max_new_tokens tokens."""
    engine = serve.make_engine(smoke_setup, input_bucket=32, chunk_size=16)
    assert (engine.mode, engine.kv, engine.prefill) == (
        "continuous", "paged", "chunked")
    assert engine.prefix_cache_enabled and engine.eos_id == -1
    reqs = smoke_setup.requests()
    res = engine.serve(reqs)
    assert len(res["tasks"]) == len(reqs) == 4
    assert all(len(t.task.out_tokens) == 3 for t in res["tasks"])
    assert engine.warmup_s > 0.0
    names = set(engine.warmed_executables())
    assert "dispatch:paged_decode_steps[1]" in names
    assert any(n.startswith("dispatch:ragged") for n in names)
    # requests() hands out fresh, unserved requests every time
    assert all(not r.out_tokens for r in smoke_setup.requests())


def test_replicas_on_their_own_devices_match_shared_device(tmp_path):
    """Four replicas spread over four (virtual CPU) devices place every
    request and produce every token exactly as four replicas sharing
    device 0 do, and each replica's params and KV pool live on its own
    device."""
    script = textwrap.dedent("""
        import jax
        from repro.launch import serve
        setup = serve.build("starcoder2-3b", smoke=True, n_requests=8,
                            max_new_tokens=3, seed=1)
        devs = jax.devices()
        assert len(devs) == 4
        out = {}
        for name, use in (("spread", devs), ("shared", devs[:1])):
            eng = serve.make_engine(setup, input_bucket=32, chunk_size=16,
                                    replicas=4, devices=use)
            res = eng.serve(setup.requests())
            for r, e in enumerate(eng.engines):
                want = {use[r % len(use)]}
                for tree in (e.params, e.paged_cache.state):
                    got = {d for x in jax.tree.leaves(tree)
                           for d in x.devices()}
                    assert got == want, (name, r, got, want)
            toks = {t.task.task_id: t.task.out_tokens
                    for rr in res["per_replica"] if rr for t in rr["tasks"]}
            assert len(toks) == 8
            out[name] = (res["placements"], toks)
        assert out["spread"] == out["shared"]
        assert len(set(out["spread"][0])) > 1
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=env, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().endswith("OK")
