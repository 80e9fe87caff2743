"""Exact 95th percentile over all requests of first-token time minus
arrival, on the engine's clock."""

from rtbench.harness import percentile


def read(run):
    return percentile(run.ttft, 95)
