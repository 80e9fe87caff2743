"""The benchmark's command: one run of one cell of BENCHMARK.json.

    python3 rtbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  It makes the weights and the requests from
``--seed``, sets up the program's serving engine, warms every shape the
cell's traffic uses, serves the requests due in the first ``--seconds``
of the engine's clock, and prints one JSON line: ``--trace 0`` with the
cell's end-to-end metrics, ``--trace 1`` with its per-layer metrics read
from a profiler trace of the same window.  ``correct`` compares the
served tokens with the plain reference (``reference/``).

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is missing.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / ".rtbench_run"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    log(f"rtbench: {msg}")
    sys.exit(1)


def devices(need: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < need:
        fail(f"the cell needs {need} chips, JAX sees {len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401  the program under test
    except ImportError as e:
        fail(f"the program is missing: {e}")
    import jax
    from repro.launch import compile_cache
    from rtbench import harness

    # the program's fixed cache directory (inside the checkout, or
    # $JAX_COMPILATION_CACHE_DIR), holding every program however small
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    bench = harness.load_bench(ROOT)
    cell = harness.load_cell(args.workload, bench)
    devices(cell.entry["chips"])
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, out_dir=OUT, log=log)
    result = harness.execute(run, bench, log)
    shutil.rmtree(OUT, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
