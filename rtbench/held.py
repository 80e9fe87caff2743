"""Kernel roofline shares over the launches a trace holds.

A traced run can lose its last launches from the trace while the run's
work (``run.work``, the serve's counters) counts every launch, so a
share computed from all the work over the trace's kernel time reads
high.  These readers scale the work to the launches the trace holds:
the trace summary's launches of the kind over the serve's dispatches of
it.  A reading with nothing to read returns None."""

from __future__ import annotations

from typing import Optional

from rtbench import device, flops

DISPATCHES = {"decode": "decode_dispatches", "prefill": "prefill_dispatches"}


def share(run, part: str) -> Optional[float]:
    """Launches of ``part`` the trace holds over those the serve made."""
    t = run.trace_summary
    if not t:
        return None
    held = sum(n for k, n in t["launches"].items() if device.PART[part] in k)
    total = run.res.get(DISPATCHES[part], 0)
    return held / total if held and total else None


def roofline(run, part: str, kernel: str,
             work: Optional[flops.Work]) -> Optional[float]:
    """The least time at the chip's peaks of ``work`` (the whole serve's,
    scaled to the launches held) over the device time of the kernel
    called ``kernel`` in ``part``'s launches, as a percentage."""
    t = device.kernel_s(run, part, kernel)
    s = share(run, part)
    if t is None or s is None or work is None or not work.kernel_ops:
        return None
    p = device.peaks(run.device["kind"])
    least, _ = flops.roofline_s(work, p["flops_per_s"], p["bytes_per_s"])
    return 100.0 * least * s / t


def gmm_work(run) -> Optional[flops.Work]:
    """The decode launches' grouped expert matmuls, from the serve's
    counters: operations of the routed token-expert pairs through the
    gate, up and down matrices; bytes of the weights of the experts
    reached (each read once a launch and layer) and of the pairs'
    activations in and out."""
    pairs = run.res.get("moe_decode_pairs")
    reached = run.res.get("moe_decode_experts")
    if not pairs or not reached:
        return None
    D = run.cell.config["hidden_size"]
    F = run.cell.config["moe_intermediate_size"]
    return flops.Work(kernel_ops=2.0 * 3 * D * F * pairs,
                      kernel_bytes=flops.BF16 * (3 * D * F * reached
                                                 + (3 * D + 3 * F) * pairs))
