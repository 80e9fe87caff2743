"""The serve loop's own spans in a trace: each decode launch paired with
the readback that waited for it, and the share of the device's idle
time that no host phase of the serve loop covers.

The program runs each host phase of its serve loop under a
``serve:<phase>`` span and each readback of its completion worker under
``readback:<kind>`` (``serving/pipeline.py``).  The worker reads back
strictly in launch order, one readback per decode launch, so the k-th
``readback:decode`` span waits for the k-th decode launch.  A program
without these spans gives no pairs, and the readers of the lags read
nothing.

``summarize`` works on a ``trace_reduce.Trace`` only, so a test can hand
it events made up by hand; ``of`` reads a run's trace once and keeps the
summary on the run.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

from rtbench import trace_reduce
from rtbench.trace_reduce import Trace, _clip, _union

READBACK = "readback:decode"
COVER = ("serve:", "readback:")


def _is_decode(name: str) -> bool:
    return "decode" in trace_reduce.module_kind(name)


def decode_lags(tr: Trace, window: Tuple[float, float]) -> List[float]:
    """For each decode launch that overlaps ``window`` (start, end ns),
    in launch order, the ns from its end on the device to the end of the
    ``readback:decode`` span that waited for it.  The pairing is FIFO
    over the whole trace; a readback that ends before the oldest
    unpaired launch has ended waited for a launch the trace does not
    hold, and is passed over."""
    lo, hi = window
    launches = sorted((s, s + d) for n, s, d in tr.modules if _is_decode(n))
    ends = sorted(s + d for n, s, d in tr.host if n == READBACK)
    lags, j = [], 0
    for s, e in launches:
        while j < len(ends) and ends[j] < e:
            j += 1
        if j == len(ends):
            break
        if s < hi and e > lo:
            lags.append(ends[j] - e)
        j += 1
    return lags


def uncovered_idle(tr: Trace, window: Tuple[float, float]
                   ) -> Optional[float]:
    """Share of the device's idle time in ``window`` that lies under no
    ``serve:`` or ``readback:`` span; None when the device never idled."""
    lo, hi = window
    busy = _union(filter(None, (_clip(e, lo, hi) for e in tr.ops)))
    idle, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    idle_ns = sum(e - s for s, e in idle)
    if not idle_ns:
        return None
    cover = _union(filter(None, (_clip(h, lo, hi) for h in tr.host
                                 if h[0].startswith(COVER))))
    covered, j = 0.0, 0
    for s, e in idle:                    # both lists sorted and disjoint
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            covered += min(e, cover[k][1]) - max(s, cover[k][0])
            k += 1
    return 1.0 - covered / idle_ns


def summarize(tr: Trace, window: Tuple[float, float]) -> Dict:
    lo, hi = window
    return {
        "decode_lags_s": [x / 1e9 for x in decode_lags(tr, window)],
        "decode_launches": sum(1 for m in tr.modules
                               if _is_decode(m[0]) and _clip(m, lo, hi)),
        "uncovered_idle_share": uncovered_idle(tr, window),
    }


def of(run) -> Optional[Dict]:
    """``summarize`` of the run's traced window, read from its trace
    directory once and kept as ``run.spans``; None for a run without a
    trace.  Logs the pairs, the uncovered idle share, the serve's host
    phases and its AOT misses on one ``spans:`` line."""
    if getattr(run, "spans", None) is None:
        if not getattr(run, "trace_summary", None):
            return None
        from rtbench.harness import WINDOW_SPAN
        tr = trace_reduce.load(trace_reduce.find(str(run.out_dir / "trace")))
        run.spans = summarize(tr, trace_reduce.host_window(tr, WINDOW_SPAN))
        s, res = run.spans, run.res
        lags = s["decode_lags_s"]
        share = s["uncovered_idle_share"]
        run.log(f"spans: {len(lags)} decode launches paired with their "
                f"readbacks of {s['decode_launches']} in the window, lag "
                f"mean {1e3 * sum(lags) / max(len(lags), 1):.3f} ms max "
                f"{max(lags, default=0.0):.4f} s; device idle under no "
                f"serve:/readback: span "
                f"{'n/a' if share is None else f'{100 * share:.2f}%'}; "
                f"host_phase_s {json.dumps(res.get('host_phase_s'))}, "
                f"aot_misses {res.get('aot_misses')}")
    return run.spans
