"""Operations and bytes of the work a run asked for, from shapes alone.

Counts are of the useful work: live tokens and live positions.  Padding
rows, dead decode slots and the tokens of a cached prefix are left out,
so a share of a peak computed from them can only be too low, never too
high.  Bytes are the least a kernel must move (each K/V byte read once
per call, each output written once).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

BF16 = 2


@dataclasses.dataclass(frozen=True)
class Shape:
    """The model sizes the counts need (from a configuration file)."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool = False

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated=bool(c.get("mlp_gated", False)))

    @property
    def layer_weights(self) -> int:
        """Weights one token meets in one layer's matmuls."""
        attn = self.d_model * self.head_dim * (2 * self.heads
                                               + 2 * self.kv_heads)
        return attn + (3 if self.gated else 2) * self.d_model * self.d_ff

    @property
    def head_weights(self) -> int:
        return self.d_model * self.vocab


def matmul_flops(s: Shape, tokens: int, head_rows: int) -> float:
    """Weight-matmul FLOPs of ``tokens`` through every layer plus
    ``head_rows`` rows through the output head."""
    return 2.0 * (tokens * s.layers * s.layer_weights
                  + head_rows * s.head_weights)


def attention_ops(s: Shape, keys: int) -> float:
    """QK and PV FLOPs of all layers for queries that see ``keys`` keys
    in total (summed over queries)."""
    return 4.0 * s.heads * s.head_dim * keys * s.layers


def kv_bytes(s: Shape, tokens: int) -> float:
    """K and V bytes of ``tokens`` positions in all layers."""
    return 2.0 * s.kv_heads * s.head_dim * BF16 * tokens * s.layers


def qo_bytes(s: Shape, rows: int) -> float:
    """Query and output bytes of ``rows`` query rows in all layers."""
    return 2.0 * s.heads * s.head_dim * BF16 * rows * s.layers


@dataclasses.dataclass
class Work:
    """One kind of launch's useful work over a window."""

    matmul_flops: float = 0.0
    kernel_ops: float = 0.0
    kernel_bytes: float = 0.0

    @property
    def flops(self) -> float:
        return self.matmul_flops + self.kernel_ops


def decode_work(s: Shape, seqs: Iterable[Tuple[int, int]]) -> Work:
    """Decode steps of sequences ``(prompt_len, tokens_out)``: output
    token ``j >= 1`` is computed from the token at position
    ``prompt_len + j - 1`` and attends to ``prompt_len + j`` keys."""
    w = Work()
    for p, n in seqs:
        steps = max(n - 1, 0)
        keys = steps * p + steps * (steps + 1) // 2
        w.matmul_flops += matmul_flops(s, steps, steps)
        w.kernel_ops += attention_ops(s, keys)
        w.kernel_bytes += kv_bytes(s, keys) + qo_bytes(s, steps)
    return w


def prefill_work(s: Shape, seqs: Iterable[Tuple[int, int]]) -> Work:
    """Prefill of sequences ``(prompt_len, cached)``: positions
    ``cached .. prompt_len - 1`` are computed, each attending causally,
    and one head row gives the first token.  Bytes: the whole prompt's
    K/V read once, the computed positions' K/V written once, their
    queries read and outputs written once."""
    w = Work()
    for p, c in seqs:
        n = p - c
        keys = (p * (p + 1) - c * (c + 1)) // 2
        w.matmul_flops += matmul_flops(s, n, 1)
        w.kernel_ops += attention_ops(s, keys)
        w.kernel_bytes += kv_bytes(s, p + n) + qo_bytes(s, n)
    return w


def roofline_s(w: Work, peak_flops: float, peak_bytes: float):
    """The least time the chip could take for the kernel's part of ``w``
    and which bound sets it."""
    t_ops = w.kernel_ops / peak_flops
    t_bytes = w.kernel_bytes / peak_bytes
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
