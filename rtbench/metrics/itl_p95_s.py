"""Exact 95th percentile over all inter-token gaps of all requests, on
the engine's clock."""

from rtbench.harness import percentile


def read(run):
    return percentile(run.itl, 95)
