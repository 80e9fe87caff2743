"""Routed token-expert pairs over the experts they reached, summed over
the window's decode launches and MoE layers: how many tokens share each
expert's weight bytes.  A program without the counter reports nothing."""


def read(run):
    pairs = run.res.get("moe_decode_pairs")
    reached = run.res.get("moe_decode_experts")
    return pairs / reached if pairs and reached else None
