"""Readings that set the limit of ``correct``: the program's widest
served-logit gap and the control's, on several seeds, in one process.

    python3 rtbench/control.py --workload <name> --seconds <s> --seeds 1,2,3

For each seed, a whole run of the cell (weights and traffic from the
seed, warm-up, the window, the check's sample compared with the float32
reference), then the control on the same sample: the reference computed
in float8 (e4m3, weights per output channel and activations per row),
the token it puts first at each position, and how far that token's
float32 logit lies below the float32 best.  One JSON line per seed.
The benchmark's own runs never run the control.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.launch import compile_cache
    from rtbench import harness
    compile_cache.enable()
    bench = harness.load_bench(ROOT)
    cell = harness.load_cell(args.workload, bench)
    for seed in [int(x) for x in args.seeds.split(",")]:
        t = time.perf_counter()
        run = harness.Run(cell, seed, args.seconds, False, t_start=t,
                          out_dir=ROOT / ".rtbench_run",
                          log=lambda m: print(m, file=sys.stderr))
        run.setup()
        run.window()
        run.derive()
        checks = run.check()
        out = {"seed": seed, "program_gap": checks["served_logit_gap"]["value"],
               "requests_compared": len(run.sampled),
               "tokens_compared": run.sample_tokens,
               "requests_short": checks["requests_short"]["value"],
               "repeats": run.repeats}
        if not args.no_control:
            out["control_gap"] = run.control_gap()
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
