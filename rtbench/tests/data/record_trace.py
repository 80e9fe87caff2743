"""Record ``trace.json``, the small device trace that
``test_rtbench_trace.py`` reduces: a two-layer model at head size 128
served for two seconds of chat traffic on the chip with the profiler on,
cut to one ragged prefill launch and the decode launches after it.

    python3 rtbench/tests/data/record_trace.py    # on a machine with a TPU
"""

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from rtbench import gen, harness, trace_reduce  # noqa: E402

DECODES = 3                     # decode launches kept after the prefill


def cut(tr: trace_reduce.Trace) -> dict:
    """The events around the first ragged prefill launch and the
    ``DECODES`` decode launches after it, on a clock that starts there."""
    mods = sorted(tr.modules, key=lambda m: m[1])
    first = next(i for i, m in enumerate(mods) if "ragged" in m[0])
    after = [m for m in mods[first + 1:] if "decode" in m[0]][:DECODES]
    lo = mods[first][1] - 1000
    hi = after[-1][1] + after[-1][2] + 1000

    def keep(events, shortest=0):
        return [[n, s - lo, d] for n, s, d in events
                if s < hi and s + d > lo and shortest <= d < 100 * (hi - lo)]

    return {"window": [0, hi - lo], "ops": keep(tr.ops),
            "modules": keep(tr.modules), "host": keep(tr.host, shortest=1000)}


def main() -> int:
    config = dict(json.loads((HERE / "tiny-gqa.json").read_text()),
                  name="tiny-gqa-hd128", hidden_size=512,
                  num_attention_heads=4, num_key_value_heads=2,
                  head_dim=128, intermediate_size=1024)
    knobs = {"rate_per_s": 4.0, "policy": "up+c",
             "engine": {"input_bucket": 32, "chunk_size": 32,
                        "num_slots": 8, "kv_num_blocks": 256,
                        "max_new_tokens": 224},
             "check_tokens": 100}
    mix = gen.load_mix(ROOT / "rtbench" / "traffic" / "chat_uncertain.json")
    cell = harness.Cell("tiny-chat", {"name": "tiny-chat", "chips": 1},
                        config, mix, knobs)
    out = ROOT / ".rtbench_run" / "fixture"
    run = harness.Run(cell, 7, 2.0, True, t_start=time.perf_counter(),
                      out_dir=out, log=print)
    run.setup()
    run.window()
    tr = trace_reduce.load(trace_reduce.find(str(out / "trace")))
    fixture = dict(cut(tr), device_kind=run.device["kind"])
    (HERE / "trace.json").write_text(json.dumps(fixture, indent=0))
    print({k: len(v) for k, v in fixture.items() if isinstance(v, list)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
