"""Benchmark harness: one entry per paper table/figure + substrate
microbenches + the roofline report.

    PYTHONPATH=src python -m benchmarks.run [--only table3_max_response]
                                           [--seed N]

Prints ``name,us_per_call,derived`` CSV lines (harness contract) and
writes full payloads to experiments/bench/*.json.  A benchmark that
raises prints an ``ERROR:`` line, the others still run, and the exit
code is 1.  JAX's persistent compile cache is on
(``repro.launch.compile_cache``).  ``--seed`` threads
through the serving benchmarks (continuous_vs_batch,
prefill_interference) so the recorded JSONs are deterministic and
reproducible for any seed.
"""

from __future__ import annotations

import argparse
import time
import traceback

from repro.launch import compile_cache

from . import (chaos_failover, common, continuous_vs_batch, kernel_bench,
               paper_tables, prefill_interference, prefix_cache,
               roofline_report, router_policies, slo_calibration)


def run_paper_tables(only=None) -> int:
    """Run the paper tables; returns how many raised."""
    errors = 0
    for name, fn in paper_tables.ALL.items():
        if only and only != name:
            continue
        t0 = time.time()
        try:
            payload, derived = fn()
        except Exception as e:            # noqa: BLE001
            traceback.print_exc()
            common.emit(name, time.time() - t0, f"ERROR:{e}")
            errors += 1
            continue
        common.save(name, payload)
        common.emit(name, time.time() - t0, derived)
    return errors


def run_kernels(only=None):
    if only and only not in ("kernel_attention", "kernel_rmsnorm",
                             "ragged_prefill_kernel"):
        return
    if only is None or only == "kernel_attention":
        t0 = time.time()
        rows = kernel_bench.attention_bench()
        common.save("kernel_attention", rows)
        best = max(v["chunked_gflops"] for v in rows.values())
        common.emit("kernel_attention", time.time() - t0,
                    f"chunked_best={best}gflops_cpu")
    if only is None or only == "kernel_rmsnorm":
        t0 = time.time()
        rows = kernel_bench.rmsnorm_bench()
        common.save("kernel_rmsnorm", rows)
        best = max(v["effective_GBps"] for v in rows.values())
        common.emit("kernel_rmsnorm", time.time() - t0,
                    f"best={best}GBps_cpu")
    if only is None or only == "ragged_prefill_kernel":
        t0 = time.time()
        rows = kernel_bench.ragged_prefill_bench()
        common.save("ragged_prefill_kernel", rows)
        at4 = [v for v in rows.values() if v["num_chunks"] >= 4]
        worst = min(v["speedup"] for v in at4)
        common.emit("ragged_prefill_kernel", time.time() - t0,
                    f"min_speedup_at_ge4_chunks={worst}x")


def run_roofline(only=None):
    if only and only != "roofline":
        return
    t0 = time.time()
    rows = roofline_report.load()
    if not rows:
        common.emit("roofline", time.time() - t0,
                    "no dry-run artifacts (run repro.launch.dryrun_all)")
        return
    variants = [
        ("roofline_pod", dict(multi_pod=False)),
        ("roofline_multipod", dict(multi_pod=True)),
        ("roofline_pod_seqpar", dict(multi_pod=False, seq_parallel=True)),
        ("roofline_pod_serving", dict(multi_pod=False, fsdp=False,
                                      serving=True)),
    ]
    for name, kw in variants:
        tab = roofline_report.table(rows, **kw)
        if not any(r["status"] == "ok" for r in tab):
            continue
        s = roofline_report.summary(tab)
        common.save(name, tab)
        common.emit(name, time.time() - t0,
                    f"ok={s['ok']};mem_bound={s['memory_bound']};"
                    f"coll_bound={s['collective_bound']};"
                    f"compute_bound={s['compute_bound']};fits={s['fits']}")


def run_continuous(only=None, seed=0):
    if only == "decode_dispatch":
        t0 = time.time()
        dd = continuous_vs_batch.run_decode_dispatch("fifo", seed=seed)
        common.save("decode_dispatch", dd)
        spl = dd["stall"]["n%d" % dd["decode_steps"]]["steps_per_launch"]
        common.emit(
            "decode_dispatch", time.time() - t0,
            f"stall_dispatch_x={dd['stall']['dispatch_reduction_x']:.2f},"
            f"chunked_dispatch_x="
            f"{dd['chunked']['dispatch_reduction_x']:.2f},"
            f"steps_per_launch={spl:.0f}")
    if only is None or only in ("continuous_vs_batch_sim",
                                "continuous_vs_batch_engine",
                                "continuous_vs_batch",
                                "paged_vs_contiguous"):
        continuous_vs_batch.main(seed=seed)
    if only is None or only in ("chunked_prefill", "prefill_interference"):
        prefill_interference.main(seed=seed)
    if only is None or only == "prefix_cache":
        prefix_cache.main(seed=seed)
    if only is None or only == "slo_calibration":
        slo_calibration.main(seed=seed)
    if only is None or only == "router_policies":
        router_policies.main(seed=seed)
    if only is None or only == "chaos_failover":
        chaos_failover.main(seed=seed)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="workload/profile seed for the serving "
                         "benchmarks (deterministic JSONs per seed)")
    ap.add_argument("--summary", action="store_true",
                    help="collate experiments/bench/*.json into "
                         "BENCH_SUMMARY.json (runs no benchmarks)")
    args = ap.parse_args(argv)
    if args.summary:
        out = common.summarize()
        print(f"BENCH_SUMMARY.json: {out['n_benchmarks']} benchmarks")
        return 0
    compile_cache.enable()
    print("name,us_per_call,derived")
    errors = run_paper_tables(args.only)
    run_kernels(args.only)
    run_continuous(args.only, seed=args.seed)
    run_roofline(args.only)
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
