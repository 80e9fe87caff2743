"""Find a cell's knee: the highest Poisson rate, on the engine's clock,
at which the backlog does not grow through the window.

    python3 rtbench/sweep.py --workload <name> --seconds <s> --rates 4,6,8

One process: the cell is set up once, then its traffic is served at each
rate in turn as a Poisson stream (whatever the mix's arrival shape).  For each rate it
prints the requests due, the serve's wall seconds, tokens per wall
second, the queue wait of the first and the last quarter of arrivals,
and how far past the window the last request finished.  A rate past the
knee shows a last-quarter wait that keeps rising with the rate.  The
last line names the knee (``knee``) and the cell's rate at four fifths
of it.
"""

import argparse
import json
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def knee(rows):
    """The highest rate, going up from the lowest, at which the last
    quarter of arrivals waits at most max(2x, +0.1 s) what the first
    quarter waits, and TTFT p95 stays within 3x the lowest rate's."""
    best = None
    for r in rows:
        first, last = r["wait_first_q_mean"], r["wait_last_q_mean"]
        if (last > max(2 * first, first + 0.1)
                or r["ttft_p95_s"] > 3 * rows[0]["ttft_p95_s"]):
            break
        best = r["rate"]
    return best


def sweep(cell, rates, seconds: float, seed: int) -> None:
    """Set ``cell`` up once and serve its traffic at each rate."""
    import jax
    import numpy as np
    from rtbench import gen, harness
    run = harness.Run(cell, seed, seconds, False, t_start=T_START,
                      out_dir=ROOT / ".rtbench_run",
                      log=lambda m: print(m, file=sys.stderr))
    run.setup()
    print(json.dumps({"setup_s": run.setup_s, **run.setup_parts,
                      "device": jax.devices()[0].device_kind}), flush=True)
    poisson = dict(cell.mix, arrival={"segments": [[0.0, 1.0, 1.0]]})
    rows = []
    for rate in rates:
        run.requests = gen.generate(poisson, rate, seconds, seed)
        run.window()
        run.derive()
        n = len(run.served)
        order = sorted(range(n), key=lambda i: run.served[i].arrival)
        q = n // 4
        first = [run.served[i].queue_wait_s for i in order[:q]]
        last = [run.served[i].queue_wait_s for i in order[-q:]]
        end = max(r.finish for r in run.served)
        rows.append({
            "rate": rate, "requests": n, "tokens": run.tokens,
            "wall_s": run.wall_s, "tokens_per_wall_s": run.tokens / run.wall_s,
            "step_wall_ms": (1000 * run.wall_s
                             / run.res["decode_steps_executed"]),
            "wait_first_q_mean": float(np.mean(first)),
            "wait_last_q_mean": float(np.mean(last)),
            "finish_past_window_s": end - seconds,
            "ttft_p95_s": harness.percentile(run.ttft, 95),
            "itl_p50_s": harness.percentile(run.itl, 50),
            "itl_p95_s": harness.percentile(run.itl, 95),
            "response_mean_s": float(np.mean(run.response)),
            "kv_util_mean": run.res["kv_util_mean"],
            "peak_concurrency": run.res["peak_concurrency"],
            "rejected_for_memory": run.res["rejected_for_memory"],
            "compiles_in_window": len(run.window_compiles)})
        print(json.dumps(rows[-1]), flush=True)
    k = knee(rows)
    print(json.dumps({"knee": k, "rate_per_s": k and round(0.8 * k, 2)}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.launch import compile_cache
    from rtbench import harness
    compile_cache.enable()
    bench = harness.load_bench(ROOT)
    sweep(harness.load_cell(args.workload, bench),
          [float(r) for r in args.rates.split(",")], args.seconds, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
