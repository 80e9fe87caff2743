"""Device readings shared by the per-layer readers: the peak table, and
each launch kind's device time and kernel time from the reduced trace.

A launch kind is matched by a part of the executable's name:
``ragged`` for the fused chunked prefill, ``decode`` for the decode
window.  A reading with nothing to read returns None."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

from rtbench import flops

PART = {"prefill": "ragged", "decode": "decode"}


def peaks(kind: str) -> dict:
    """The peak FLOP/s and bytes/s of a device kind; an unknown kind is
    an error, never a default."""
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        table = json.load(f)
    if kind not in table or kind == "source":
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def _sum(times: dict, part: str) -> float:
    return sum(v for k, v in times.items() if PART[part] in k)


def exec_s(run, part: str) -> Optional[float]:
    t = run.trace_summary
    s = _sum(t["module_s"], part) if t else 0.0
    return s or None


def kernel_s(run, part: str) -> Optional[float]:
    t = run.trace_summary
    s = _sum(t["kernel_s"], part) if t else 0.0
    return s or None


def mfu(run, part: str) -> Optional[float]:
    """Useful FLOPs of ``part`` over its launches' device time, as a
    percentage of the chip's peak."""
    t = exec_s(run, part)
    if t is None or not run.work[part].flops:
        return None
    return 100.0 * run.work[part].flops / t / peaks(run.device["kind"])["flops_per_s"]


def roofline(run, part: str) -> Optional[float]:
    """The kernel's least time at the peaks over its measured device
    time, as a percentage."""
    t = kernel_s(run, part)
    if t is None or not run.work[part].kernel_ops:
        return None
    p = peaks(run.device["kind"])
    least, _ = flops.roofline_s(run.work[part], p["flops_per_s"],
                                p["bytes_per_s"])
    return 100.0 * least / t
