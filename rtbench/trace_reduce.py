"""From a profiler trace to device busy time, per-executable and kernel
device time, and the breakdown a result line carries.

The trace is JAX's ``.xplane.pb``.  Its device plane (``/device:TPU:0``)
has a line of executables (``XLA Modules``: one event per launch, named
``jit_<function>(<fingerprint>)``) and a line of operations (``XLA
Ops``: nested, a loop's event spans the operations inside it).  A Pallas
kernel is an operation whose text names ``custom_call_target=
"tpu_custom_call"``; it belongs to the executable whose launch contains
it, and is named by its operation's name less the ``.<n>`` that XLA
appends (``pl.pallas_call(name=...)``).  Host planes hold the program's
``TraceAnnotation`` spans (for example ``dispatch:<kind>``), which name
the host work during each gap in which the device is idle.

``load`` reads a trace into plain event lists; ``reduce`` works on those
lists only, so a test can hand it events made up by hand.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'

Event = Tuple[str, float, float]        # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    ops: List[Event]                    # device operations (nested)
    modules: List[Event]                # device executables, one per launch
    host: List[Event]                   # host spans of every thread


def find(log_dir: str) -> str:
    """The one ``.xplane.pb`` a trace session wrote under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def load(path: str, device: str = "/device:TPU:0") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    if not modules:
        raise RuntimeError(f"no device executables on {device} in {path}")
    return Trace(ops=ops, modules=modules, host=host)


def module_kind(name: str) -> str:
    """``jit_ragged_prefill_fn(123)`` -> ``ragged_prefill_fn``."""
    base = name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


def op_label(name: str) -> str:
    """``%fusion.110 = bf16[...] fusion(...)`` -> ``fusion.110``, with
    ``[kernel]`` appended for a Pallas kernel."""
    return _head(name) + (" [kernel]" if KERNEL_MARK in name else "")


def kernel_name(name: str) -> str:
    """``%paged_decode_kernel.12 = ... custom-call(...)`` ->
    ``paged_decode_kernel``."""
    return re.sub(r"\.\d+$", "", _head(name))


def _head(name: str) -> str:
    return name.split(" = ")[0].lstrip("%").strip()


def _clip(ev: Event, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
    return (s, e) if e > s else None


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _owner(modules: List[Event]):
    """A function from a time to the kind of the launch running then."""
    spans = sorted((m[1], m[1] + m[2], module_kind(m[0])) for m in modules)
    starts = [s for s, _, _ in spans]

    def owner(t: float) -> Optional[str]:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and spans[i][0] <= t < spans[i][1]:
            return spans[i][2]
        return None
    return owner


def _self_times(ops: List[Event]) -> Dict[int, float]:
    """Each operation's duration less that of the operations nested in
    it (events on one line nest or are disjoint)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_t = {i: ops[i][2] for i in order}
    stack: List[int] = []
    for i in order:
        s = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= ops[i][2]
        stack.append(i)
    return self_t


def reduce(tr: Trace, window: Tuple[float, float], top: int = 10) -> Dict:
    """Busy and idle time, device time per executable kind, of its
    kernels together and of each kernel by name, and the breakdown, all
    inside ``window`` (start, end ns)."""
    lo, hi = window
    busy = _union(filter(None, (_clip(e, lo, hi) for e in tr.ops)))
    busy_ns = sum(e - s for s, e in busy)
    per_module: Dict[str, float] = collections.defaultdict(float)
    launches: Dict[str, int] = collections.defaultdict(int)
    for m in tr.modules:
        c = _clip(m, lo, hi)
        if c:
            per_module[module_kind(m[0])] += c[1] - c[0]
            launches[module_kind(m[0])] += 1
    owner = _owner(tr.modules)
    kernels: Dict[str, float] = collections.defaultdict(float)
    by_name: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    by_op: Dict[str, float] = collections.defaultdict(float)
    inside = [i for i, e in enumerate(tr.ops) if _clip(e, lo, hi)]
    sub = [tr.ops[i] for i in inside]
    self_t = _self_times(sub)
    for j, ev in enumerate(sub):
        kind = owner(ev[1]) or "?"
        if KERNEL_MARK in ev[0]:
            kernels[kind] += ev[2]
            by_name[kind][kernel_name(ev[0])] += ev[2]
        by_op[f"{kind}:{op_label(ev[0])}"] += self_t[j]
    gaps = []
    prev = lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = [[_host_name(tr.host, s, e), (e - s) / 1e9]
             for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]]
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "module_s": {k: v / 1e9 for k, v in per_module.items()},
        "launches": dict(launches),
        "kernel_s": {k: v / 1e9 for k, v in kernels.items()},
        "kernel_by_name": {k: {n: v / 1e9 for n, v in d.items()}
                           for k, d in by_name.items()},
        "device_ops": [[k, v / 1e9] for k, v in ops_top],
        "idle_gaps": named,
    }


def _host_name(host: List[Event], s: float, e: float) -> str:
    """The host span that best covers the gap ``(s, e)``: the shortest
    one covering at least half of it, else the one covering most.
    Spans that enclose the whole gap by far (the serve call, a thread's
    main loop) say nothing of it and are passed over."""
    gap = e - s
    best, best_key = "no host span", None
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov <= 0 or hd > 100 * gap or name.startswith("rtbench:"):
            continue
        key = (ov < gap / 2, hd if ov >= gap / 2 else -ov)
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def host_window(tr: Trace, name: str) -> Tuple[float, float]:
    """The (start, end) ns of the host span called ``name``."""
    for n, s, d in tr.host:
        if n == name:
            return s, s + d
    raise RuntimeError(f"no host span {name!r} in the trace")
