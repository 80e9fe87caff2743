"""Plain float32 forward pass of a DeepSeek-V2 stack: latent attention
(MLA) and DeepSeekMoE.  The reference that decides ``correct`` for the
configurations that name it (``"reference": "deepseek_v2"``).

Straight ``jax.numpy``: no KV cache, no paging, no kernels, no batching;
every matmul in float32 at the highest precision, attention as a full
causal softmax.  It reads the configuration file as it is run and the
weights the benchmark made (``rtbench/weights.py``); it imports nothing
of the program.

What it computes, as the paper and the published ``config.json`` state
it, with the departures the configuration file lists:

  * MLA in the published, expanded form (arXiv:2405.04434 §2.1): per
    token a latent ``c = RMSNorm(h W_kv_a[:rank])`` and one rope key
    ``k_pe = rope(h W_kv_a[rank:])`` shared by every head; per head
    ``k = [c W_kb, k_pe]``, ``v = c W_vb``, ``q = h W_q`` split into a
    no-rope and a rope part; scores scaled by ``(nope + rope)^-0.5``
    times yarn's ``mscale(factor, mscale_all_dim)`` squared.  No query
    compression (``q_lora_rank`` null).
  * Yarn rope over the rope dims (``rope_scaling``), the two halves of a
    head rotated as pairs.
  * DeepSeekMoE: a float32 router, softmax over the routed experts,
    greedy top-k; the gates are renormalised only if ``norm_topk_prob``
    and scaled by ``routed_scaling_factor``; EVERY routed expert is
    computed for every token and weighted by its gate (zero outside the
    top k), plus the shared experts (one SiLU-gated MLP of
    ``n_shared_experts * moe_intermediate_size``).  The first
    ``first_k_dense_replace`` layers hold a dense SiLU-gated MLP.
  * RMSNorm scaled by ``(1 + w)``; the embedding multiplied by
    ``sqrt(hidden_size)``; a separate output head.

Layers run one at a time, each upcast to float32 in turn (a scan over
the stacked weights), and the head in vocabulary blocks.

``quant="fp8"`` is the control: the same pass with every weight matmul
(router included) computed in float8 (e4m3) on both sides, weights
scaled per output channel and activations per row, accumulated in
float32.

``prefill_work`` and ``decode_work`` count the useful work of a run as
the program serves it (absorbed MLA over one latent of ``rank + rope``
values a token and layer, ``k`` routed and the shared experts a token),
and the latent attention kernels' operations and bytes.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from rtbench import flops

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0                        # largest finite float8_e4m3fn

# leaf name -> standard deviation of its normal draw; None scales by
# EMBED_SCALE/sqrt(d_model).  Names shared by the dense MLP, the routed
# and the shared experts (w_gate, w_up, w_down) take one std.
SCALES = {"embedding": None, "lm_head": 0.02, "final_ln": 0.1,
          "ln1": 0.1, "ln2": 0.1, "kv_a_norm": 0.1,
          "q_proj": 0.04, "kv_a_proj": 0.04, "kv_b_k": 0.04,
          "kv_b_v": 0.02, "o_proj": 0.02,
          "router": 0.05, "w_gate": 0.02, "w_up": 0.02, "w_down": 0.02}
# The residual stream starts at this std per element (the program
# multiplies the embedding by sqrt(d_model)).  The head is separate, so
# no token's own logit stands out; half the usual std keeps each layer's
# attention a real share of the stream, so that tokens depend on their
# context and do not settle into repeats.
EMBED_SCALE = 0.5
# Query, rope-key and latent-key weights at twice the others' std give
# scores of a few units (std ~2.6 at the published widths), so
# attention is neither flat nor one-hot.  The router at 0.05 gives
# logits of std ~2.3 over 64 experts: the top six take about 0.8 of the
# softmax's mass, and the routed experts' output has about 0.39 of the
# RMS of the two shared experts' (at the published widths, drawn in
# numpy); at 0.02 the six would take 0.3 and give 0.09, swamped by the
# shared experts.  The sixth and seventh experts are then seldom tied.


@dataclasses.dataclass(frozen=True)
class Spec:
    """The sizes and conventions the reference needs, from a config file."""

    layers: int
    dense_layers: int
    d_model: int
    heads: int
    rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    experts: int
    topk: int
    moe_ff: int
    shared: int
    norm_topk: bool
    routed_scale: float
    vocab: int
    rope_theta: float
    yarn: tuple                 # (factor, original, beta_fast, beta_slow,
                                #  mscale, mscale_all_dim) or ()
    eps: float
    tied: bool

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        refuse = {
            "model_type": c.get("model_type") != "deepseek_v2",
            "q_lora_rank": c.get("q_lora_rank") is not None,
            "topk_method": c.get("topk_method", "greedy") != "greedy",
            "n_group": (c.get("n_group") or 1) > 1,
            "scoring_func": c.get("scoring_func", "softmax") != "softmax",
            "hidden_act": c.get("hidden_act") != "silu",
            "attention_bias": bool(c.get("attention_bias")),
            "moe_layer_freq": c.get("moe_layer_freq", 1) != 1,
            "norm_type": c.get("norm_type") != "rms_norm_1p",
        }
        bad = [k for k, v in refuse.items() if v]
        rs = c.get("rope_scaling") or {}
        if bad or (rs and rs.get("type") != "yarn"):
            raise ValueError(f"{c['name']}: deepseek_v2 does not compute "
                             f"{bad or 'rope_scaling'}")
        yarn = ((float(rs["factor"]),
                 int(rs["original_max_position_embeddings"]),
                 float(rs.get("beta_fast", 32)),
                 float(rs.get("beta_slow", 1)),
                 float(rs.get("mscale", 1)),
                 float(rs.get("mscale_all_dim", 0))) if rs else ())
        return cls(layers=c["num_hidden_layers"],
                   dense_layers=c.get("first_k_dense_replace", 0),
                   d_model=c["hidden_size"], heads=c["num_attention_heads"],
                   rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                   rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                   d_ff=c["intermediate_size"],
                   experts=c["n_routed_experts"],
                   topk=c["num_experts_per_tok"],
                   moe_ff=c["moe_intermediate_size"],
                   shared=c.get("n_shared_experts") or 0,
                   norm_topk=bool(c.get("norm_topk_prob")),
                   routed_scale=float(c.get("routed_scaling_factor", 1.0)),
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   yarn=yarn, eps=float(c["rms_norm_eps"]),
                   tied=bool(c["tie_word_embeddings"]))


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_freqs(spec: Spec):
    """(frequencies (rope/2,), cos/sin scale) over the rope dims: plain,
    or yarn (arXiv:2309.00071, DeepSeek-V2's formulas)."""
    d, base = spec.rope, spec.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not spec.yarn:
        return extra, 1.0
    factor, orig, fast, slow, ms, ms_all = spec.yarn

    def corr(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo, hi = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    freqs = extra / factor * ramp + extra * (1.0 - ramp)
    return freqs, _mscale(factor, ms) / _mscale(factor, ms_all)


def softmax_scale(spec: Spec) -> float:
    s = (spec.nope + spec.rope) ** -0.5
    if spec.yarn and spec.yarn[5]:
        m = _mscale(spec.yarn[0], spec.yarn[5])
        s *= m * m
    return s


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


def _rope(x, pos, freqs, mscale):
    """x: (T, heads, d); the first and second halves rotate as pairs."""
    d = x.shape[-1]
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(spec: Spec, quant, h, a):
    """Expanded MLA over the whole sequence ``h`` (T, D), causal."""
    T = h.shape[0]
    pos = jnp.arange(T)
    freqs, ms = rope_freqs(spec)
    R, N = spec.rank, spec.nope
    q = _mm("td,dhk->thk", h, a["q_proj"], 1, 0, quant)
    q = jnp.concatenate([q[..., :N], _rope(q[..., N:], pos, freqs, ms)], -1)
    kv = _mm("td,dl->tl", h, a["kv_a_proj"], 1, 0, quant)
    c = _norm(kv[:, :R], a["kv_a_norm"], spec.eps)
    k_pe = _rope(kv[:, None, R:], pos, freqs, ms)              # (T, 1, rope)
    k_nope = _mm("tr,rhn->thn", c, a["kv_b_k"], 1, 0, quant)
    v = _mm("tr,rhv->thv", c, a["kv_b_v"], 1, 0, quant)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (T, spec.heads, spec.rope))], -1)
    s = jnp.einsum("thk,shk->hts", q, k, precision=HIGHEST)
    s = s * softmax_scale(spec)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return _mm("thv,hvd->td", o, a["o_proj"], (1, 2), (0, 1), quant)


def _mlp(quant, h, m):
    """A SiLU-gated MLP: (D, F) gate and up, (F, D) down."""
    g = _mm("td,df->tf", h, m["w_gate"], 1, 0, quant)
    u = _mm("td,df->tf", h, m["w_up"], 1, 0, quant)
    return _mm("tf,fd->td", jax.nn.silu(g) * u, m["w_down"], 1, 0, quant)


def gates(spec: Spec, quant, h, router):
    """(T, E) routed gates: softmax, greedy top-k, zero elsewhere."""
    probs = jax.nn.softmax(_mm("td,de->te", h, router, 1, 0, quant), -1)
    top_p, top_e = lax.top_k(probs, spec.topk)
    if spec.norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    g = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None],
                                 top_e].set(top_p)
    return g * spec.routed_scale


def moe(spec: Spec, quant, h, m):
    """Every routed expert on every token, weighted by its gate, plus
    the shared experts."""
    g = gates(spec, quant, h, m["router"])
    a = _mm("td,edf->tef", h, m["w_gate"], 1, 1, quant)
    u = _mm("td,edf->tef", h, m["w_up"], 1, 1, quant)
    y = _mm("tef,efd->ted", jax.nn.silu(a) * u, m["w_down"], 2, 1, quant)
    out = jnp.einsum("te,ted->td", g, y, precision=HIGHEST)
    if spec.shared:
        out = out + _mlp(quant, h, m["shared"])
    return out


def _layer(spec: Spec, quant, x, p):
    x = x + _attention(spec, quant, _norm(x, p["ln1"], spec.eps), p["attn"])
    h = _norm(x, p["ln2"], spec.eps)
    if "moe" in p:
        return x + moe(spec, quant, h, p["moe"])
    return x + _mlp(quant, h, p["mlp"])


def _head_blocks(vocab_padded: int) -> int:
    """Vocabulary blocks of at most 32768 rows that divide it evenly."""
    n = -(-vocab_padded // 32768)
    while vocab_padded % n:
        n += 1
    return n


def _stack_layers(stack: dict):
    """The program's layers in order: the dense prefix layers one by
    one, then the scanned groups (``scan0``, ``scan1``, ... each stacked
    over repeats of the pattern, layer ``i`` of the pattern in
    ``scan<i>``) as a tuple to scan over together."""
    def groups(name):
        keys = sorted((k for k in stack if k.startswith(name)),
                      key=lambda k: int(k[len(name):]))
        return [stack[k] for k in keys]
    return groups("prefix"), tuple(groups("scan"))


@functools.partial(jax.jit, static_argnames=("spec", "first", "rows",
                                             "quant"))
def logits(params, tokens, *, spec: Spec, first: int, rows: int,
           quant=None):
    """Run the stack over ``tokens`` (T,) and return the logits (rows, V)
    at positions ``first .. first + rows - 1``."""
    emb = params["embed"]["embedding"]
    x = emb[tokens].astype(jnp.float32) * jnp.sqrt(jnp.float32(spec.d_model))
    dense, scanned = _stack_layers(params["stack"])
    for p in dense:
        x = _layer(spec, quant, x, p)

    def repeat(x, ps):
        for p in ps:
            x = _layer(spec, quant, x, p)
        return x, None

    if scanned:
        x, _ = lax.scan(repeat, x, scanned)
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


# ---------------------------------------------------------------------------
# the work of a run, as the program serves it
# ---------------------------------------------------------------------------

BF16 = flops.BF16


def token_weights(spec: Spec) -> float:
    """Weights one token meets in the matmuls of every layer, as served:
    absorbed MLA (query, latent and rope-key projections, the per-head
    absorption of W_kb into the query and W_vb after attention, the
    output), the dense MLP or the router with ``topk`` routed and the
    shared experts."""
    D, H, R = spec.d_model, spec.heads, spec.rank
    attn = (D * H * (spec.nope + spec.rope) + D * (R + spec.rope)
            + H * spec.nope * R + H * R * spec.v_dim + H * spec.v_dim * D)
    dense = attn + 3 * D * spec.d_ff
    moe_ = (attn + D * spec.experts
            + (spec.topk + spec.shared) * 3 * D * spec.moe_ff)
    return (spec.dense_layers * dense
            + (spec.layers - spec.dense_layers) * moe_)


def _latent_ops(spec: Spec, keys: int) -> float:
    """Scores over ``rank + rope`` lanes and values over ``rank``, every
    head, every layer, for queries that see ``keys`` keys in all."""
    return 2.0 * spec.heads * (2 * spec.rank + spec.rope) * keys * spec.layers


def _latent_bytes(spec: Spec, tokens: int) -> float:
    """Latent bytes of ``tokens`` positions in every layer."""
    return float((spec.rank + spec.rope) * BF16 * tokens * spec.layers)


def _qo_bytes(spec: Spec, rows: int) -> float:
    """Absorbed query and latent output bytes of ``rows`` query rows."""
    return float(spec.heads * (2 * spec.rank + spec.rope) * BF16 * rows
                 * spec.layers)


def decode_work(config: dict, seqs):
    """Decode steps of sequences ``(prompt_len, tokens_out)``: output
    token ``j >= 1`` comes from position ``prompt_len + j - 1`` and
    attends to ``prompt_len + j`` keys.  ``decode``: the launch's useful
    work; ``mla_decode``: the latent decode kernel's."""
    spec = Spec.from_config(config)
    w, k = flops.Work(), flops.Work()
    for p, n in seqs:
        steps = max(n - 1, 0)
        keys = steps * p + steps * (steps + 1) // 2
        k.kernel_ops += _latent_ops(spec, keys)
        k.kernel_bytes += _latent_bytes(spec, keys) + _qo_bytes(spec, steps)
        w.matmul_flops += 2.0 * steps * (token_weights(spec)
                                         + spec.d_model * spec.vocab)
    w.kernel_ops, w.kernel_bytes = k.kernel_ops, k.kernel_bytes
    return {"decode": w, "mla_decode": k}


def prefill_work(config: dict, seqs):
    """Prefill of sequences ``(prompt_len, cached)``: positions ``cached
    .. prompt_len - 1`` are computed, each attending causally, and one
    head row gives the first token.  ``mla_prefill``: the latent prefill
    kernel's work, the whole prompt's latents read once."""
    spec = Spec.from_config(config)
    w, k = flops.Work(), flops.Work()
    for p, c in seqs:
        n = p - c
        keys = (p * (p + 1) - c * (c + 1)) // 2
        k.kernel_ops += _latent_ops(spec, keys)
        k.kernel_bytes += _latent_bytes(spec, p) + _qo_bytes(spec, n)
        w.matmul_flops += 2.0 * (n * token_weights(spec)
                                 + spec.d_model * spec.vocab)
    w.kernel_ops, w.kernel_bytes = k.kernel_ops, k.kernel_bytes
    return {"prefill": w, "mla_prefill": k}
