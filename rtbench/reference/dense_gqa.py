"""Plain float32 forward pass of a dense decoder stack with grouped-query
attention: the reference that decides ``correct`` for the configurations
that name it (``"reference": "dense_gqa"``).

Straight ``jax.numpy``: no KV cache, no paging, no kernels, no batching;
every matmul in float32 at the highest precision, attention as a full
causal softmax.  It reads the configuration file as it is run and the
weights the benchmark made (``rtbench/weights.py``); it imports nothing
of the program.

It computes what the configuration file states, including the
departures from the published models that the program shares (listed
under ``departures`` in each configuration file): RMSNorm scaled by
``(1 + w)`` before attention, before the MLP and before the head; no
biases; rotary embedding over the whole head with its two halves
rotated as pairs; the embedding multiplied by ``sqrt(hidden_size)``;
an ungated MLP (``gelu_pytorch_tanh`` or ``relu``); a tied or separate
output head.

``quant="fp8"`` is the control: the same pass with every weight matmul
computed in float8 (e4m3) on both sides, weights scaled per output
channel and activations per row, accumulated in float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0                        # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes and conventions the reference needs, from a config file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    act: str
    rope_theta: float
    eps: float
    tied: bool

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        if c.get("mlp_gated") or c.get("sliding_window") \
                or c.get("use_bias") or c.get("norm_type") != "rms_norm_1p" \
                or c.get("partial_rotary_factor", 1.0) != 1.0:
            raise ValueError(f"{c['name']}: dense_gqa covers ungated, "
                             "biasless, full-attention, full-rotary stacks "
                             "with (1 + w) RMSNorm")
        eps = c.get("norm_epsilon", c.get("norm_eps"))
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   vocab=c["vocab_size"], act=c["hidden_act"],
                   rope_theta=float(c["rope_theta"]), eps=float(eps),
                   tied=bool(c["tie_word_embeddings"]))


def _q8(x, axes):
    """Round ``x`` to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, x, w, x_axes, w_axes, quant):
    """A weight matmul: float32 at HIGHEST, or both sides in float8."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x, x_axes), _q8(w, w_axes)
    return jnp.einsum(eq, x, w, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """x: (T, heads, hd); the first and second halves rotate as pairs."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(name, x):
    if name == "gelu_pytorch_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                         * (x + 0.044715 * x ** 3)))
    if name == "relu":
        return jnp.maximum(x, 0.0)
    raise ValueError(f"activation {name!r} not covered")


def _layer(spec: Spec, quant, x, p):
    T = x.shape[0]
    pos = jnp.arange(T)
    G = spec.heads // spec.kv_heads
    a = p["attn"]
    h = _norm(x, p["ln1"], spec.eps)
    q = _rope(_mm("td,dhk->thk", h, a["wq"], 1, 0, quant), pos,
              spec.rope_theta)
    k = _rope(_mm("td,dhk->thk", h, a["wk"], 1, 0, quant), pos,
              spec.rope_theta)
    v = _mm("td,dhk->thk", h, a["wv"], 1, 0, quant)
    q = q.reshape(T, spec.kv_heads, G, spec.head_dim)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HIGHEST)
    s = s / jnp.sqrt(jnp.float32(spec.head_dim))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    o = o.reshape(T, spec.heads, spec.head_dim)
    x = x + _mm("thk,hkd->td", o, a["wo"], (1, 2), (0, 1), quant)
    h = _norm(x, p["ln2"], spec.eps)
    u = _act(spec.act, _mm("td,df->tf", h, p["mlp"]["w_up"], 1, 0, quant))
    return x + _mm("tf,fd->td", u, p["mlp"]["w_down"], 1, 0, quant)


def _head_blocks(vocab_padded: int) -> int:
    """Vocabulary blocks of at most 32768 rows that divide it evenly."""
    n = -(-vocab_padded // 32768)
    while vocab_padded % n:
        n += 1
    return n


@functools.partial(jax.jit, static_argnames=("spec", "first", "rows",
                                             "quant"))
def logits(params, tokens, *, spec: Spec, first: int, rows: int,
           quant=None):
    """Run the stack over ``tokens`` (T,) and return the logits (rows, V)
    at positions ``first .. first + rows - 1``.  Layers run one at a time
    (a scan over the stacked weights, each cast to float32 in turn) and
    the head in vocabulary blocks, so the float32 copies stay small."""
    emb = params["embed"]["embedding"]
    x = emb[tokens].astype(jnp.float32) * jnp.sqrt(jnp.float32(spec.d_model))
    x, _ = lax.scan(lambda x, p: (_layer(spec, quant, x, p), None), x,
                    params["stack"]["scan0"])
    x = _norm(x, params["final_ln"], spec.eps)
    h = lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    head = emb.T if spec.tied else params["embed"]["lm_head"]   # (D, Vp)
    vp = head.shape[1]
    nblk = _head_blocks(vp)
    blk = vp // nblk

    def block(i, out):
        w = lax.dynamic_slice_in_dim(head, i * blk, blk, axis=1)
        return lax.dynamic_update_slice_in_dim(
            out, _mm("td,dv->tv", h, w, 1, 0, quant), i * blk, axis=1)

    out = lax.fori_loop(0, nblk, block, jnp.zeros((rows, vp), jnp.float32))
    return out[:, :spec.vocab]


@functools.partial(jax.jit, static_argnames=("spec", "first", "rows",
                                             "quant"))
def score(params, tokens, targets, *, spec: Spec, first: int, rows: int,
          quant=None):
    """Per row of ``logits``: the largest logit, the logit of ``targets``
    (rows,) and the argmax."""
    lg = logits(params, tokens, spec=spec, first=first, rows=rows,
                quant=quant)
    at = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
    return lg.max(axis=1), at, jnp.argmax(lg, axis=1)
