"""Record ``trace_spans.json``, the small device trace that
``test_rtbench_spans.py`` reduces: ``record_trace.py``'s two-layer model
at head size 128 served for two seconds of chat traffic on the chip with
the profiler on, cut to one ragged prefill launch and the ``DECODES``
decode launches after it, with the serve loop's ``serve:<phase>`` and
``readback:<kind>`` spans.

    python3 rtbench/tests/data/record_trace_spans.py    # with a TPU
"""

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(HERE), str(ROOT), str(ROOT / "src")]

import record_trace  # noqa: E402
from rtbench import gen, harness, trace_reduce  # noqa: E402

DECODES = 4


def main() -> int:
    config = dict(json.loads((HERE / "tiny-gqa.json").read_text()),
                  name="tiny-gqa-hd128", hidden_size=512,
                  num_attention_heads=4, num_key_value_heads=2,
                  head_dim=128, intermediate_size=1024)
    knobs = {"rate_per_s": 4.0, "policy": "up+c",
             "engine": {"input_bucket": 32, "chunk_size": 32,
                        "num_slots": 8, "kv_num_blocks": 256,
                        "max_new_tokens": 352},
             "check_tokens": 100}
    mix = gen.load_mix(ROOT / "rtbench" / "traffic" / "chat_uncertain.json")
    cell = harness.Cell("tiny-chat", {"name": "tiny-chat", "chips": 1},
                        config, mix, knobs)
    out = ROOT / ".rtbench_run" / "fixture_spans"
    run = harness.Run(cell, 7, 2.0, True, t_start=time.perf_counter(),
                      out_dir=out, log=print)
    run.setup()
    run.window()
    tr = trace_reduce.load(trace_reduce.find(str(out / "trace")))
    record_trace.DECODES = DECODES
    fixture = dict(record_trace.cut(tr), device_kind=run.device["kind"])
    (HERE / "trace_spans.json").write_text(json.dumps(fixture, indent=0))
    print({k: len(v) for k, v in fixture.items() if isinstance(v, list)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
