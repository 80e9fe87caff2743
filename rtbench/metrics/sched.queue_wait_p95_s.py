"""Exact 95th percentile of the program's per-request queue wait
(admission minus arrival, engine clock)."""

from rtbench.harness import percentile


def read(run):
    return percentile(run.queue_wait, 95)
