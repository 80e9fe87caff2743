"""Latent attention (MLA), yarn rope and the dropless expert layer at
smoke size on the CPU: the absorbed form the program serves equals the
published expanded form, yarn follows its formula, the Pallas kernels
(interpret mode) equal their jnp paths, and the serving expert layer
computes every routed token where the training dispatch's capacity cut
drops some."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import mla_decode, mla_prefill, moe_gmm
from repro.kvcache import paged as paged_lib
from repro.models import layers, mla, moe as moe_lib

CFG = dataclasses.replace(configs.get_smoke_config("deepseek-v2-lite"),
                          num_experts=8, experts_per_token=3)


def _expanded(p, h, cfg):
    """The published form over a whole sequence h (T, D), float32:
    per-head keys [c W_kb, k_pe], values c W_vb."""
    T = h.shape[0]
    pos = jnp.arange(T)
    R, N = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    freqs, ms = layers.rope_for(cfg, cfg.qk_rope_head_dim)
    q = jnp.einsum("td,dhk->thk", h, f32(p["q_proj"]))
    q = jnp.concatenate([q[..., :N], layers.apply_rope(
        q[..., N:], pos, freqs=freqs, mscale=ms)], -1)
    kv = h @ f32(p["kv_a_proj"])
    c = layers.rms_norm(kv[:, :R], f32(p["kv_a_norm"]), cfg.norm_eps)
    k_pe = layers.apply_rope(kv[:, None, R:], pos, freqs=freqs, mscale=ms)
    k = jnp.concatenate([jnp.einsum("tr,rhn->thn", c, f32(p["kv_b_k"])),
                         jnp.broadcast_to(k_pe, (T, cfg.num_heads,
                                                 cfg.qk_rope_head_dim))], -1)
    v = jnp.einsum("tr,rhv->thv", c, f32(p["kv_b_v"]))
    s = jnp.einsum("thk,shk->hts", q, k) * mla.softmax_scale(cfg)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), v)
    return jnp.einsum("thv,hvd->td", o, f32(p["o_proj"]))


def _mla_params(dtype):
    p = mla.init_mla(jax.random.PRNGKey(1), CFG, dtype)
    p["q_proj"] = p["q_proj"] * 3             # scores of a few units
    return p


def test_absorbed_decode_equals_the_expanded_form():
    """Each position's output through the paged decode path (absorbed:
    queries over the latent pool) equals the expanded form's."""
    p = _mla_params(jnp.float32)
    T = 21
    h = jax.random.normal(jax.random.PRNGKey(2), (T, CFG.d_model))
    want = _expanded(p, h, CFG)
    bs, nb = 8, 3
    pool = jnp.zeros((nb + 1, bs, CFG.latent_lanes), jnp.float32)
    tables = jnp.arange(nb, dtype=jnp.int32)[None]
    got = []
    for t in range(T):
        y, pool = mla.decode_paged(p, h[None, t:t + 1], pool,
                                   jnp.asarray([t], jnp.int32), tables, CFG)
        got.append(y[0, 0])
    np.testing.assert_allclose(np.stack(got), want, rtol=2e-4, atol=2e-4)
    # the whole sequence at once (training and contiguous prefill)
    seq, _ = mla.attend_seq(p, h[None], jnp.arange(T), CFG)
    np.testing.assert_allclose(seq[0], want, rtol=2e-4, atol=2e-4)


def test_yarn_frequencies_and_scale_follow_the_formula():
    """DeepSeek-V2's yarn, transcribed directly: correction range from
    beta_fast/beta_slow over the original context, a linear ramp
    between interpolated and kept frequencies, mscale 0.1 m ln(s) + 1."""
    dim, base, s, orig = 64, 10000.0, 40.0, 4096

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    lo, hi = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    want = []
    for i in range(dim // 2):
        extra = 1.0 / base ** (2 * i / dim)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(extra / s * ramp + extra * (1.0 - ramp))
    got = layers.yarn_freqs(dim, base, s, orig)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == 1.0 and abs(got[-1] - want[-1]) < 1e-12
    m = 0.1 * 0.707 * math.log(40) + 1.0
    full = configs.get_config("deepseek-v2-lite")
    assert mla.softmax_scale(full) == pytest.approx(192 ** -0.5 * m * m)
    freqs, ms = layers.rope_for(full, 64)
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert ms == 1.0                          # mscale == mscale_all_dim


def _all_experts(params, x, cfg):
    """Every expert on every token, weighted by its gate (zero outside
    the top k), plus the shared experts: the reference's expert layer."""
    xt = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    probs = jax.nn.softmax(xt @ params["router"], -1)
    top_p, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    g = jnp.zeros_like(probs).at[jnp.arange(xt.shape[0])[:, None],
                                 top_e].set(top_p)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    a = jnp.einsum("td,edf->tef", xt, f32(params["w_gate"]))
    u = jnp.einsum("td,edf->tef", xt, f32(params["w_up"]))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(a) * u, f32(params["w_down"]))
    out = jnp.einsum("te,ted->td", g, y)
    sh = params["shared"]
    out += (jax.nn.silu(xt @ f32(sh["w_gate"])) * (xt @ f32(sh["w_up"]))
            ) @ f32(sh["w_down"])
    return out.reshape(x.shape)


def _skewed_moe(T):
    """Expert parameters and a batch whose router sends every token to
    expert 5 first."""
    params = moe_lib.init_moe(jax.random.PRNGKey(3), CFG, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, T, CFG.d_model))
    bias = jnp.zeros((CFG.num_experts,)).at[5].set(8.0)
    x = x.at[..., :8].set(1.0)
    params["router"] = params["router"].at[:8].add(bias / 8)
    return params, x


@pytest.mark.parametrize("use_pallas", [False, True])
def test_dropless_layer_computes_every_routed_token(use_pallas):
    T = 64
    params, x = _skewed_moe(T)
    _, top_e = moe_lib.route(params, x.reshape(T, -1), CFG)
    assert bool((top_e == 5).any(axis=1).all())    # one expert, every token
    want = _all_experts(params, x, CFG)
    got, aux = moe_lib.apply_moe_dropless(params, x, CFG,
                                          use_pallas=use_pallas)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert int(aux["moe_pairs"]) == T * CFG.experts_per_token
    # the training dispatch's capacity cut drops most of expert 5's rows
    cut, aux_cut = moe_lib.apply_moe_local(params, x, CFG)
    assert float(aux_cut["moe_drop_frac"]) > 0.1
    assert float(jnp.abs(cut - want).max()) > 0.1 * float(jnp.abs(want).max())


def test_dropless_layer_routes_invalid_rows_nowhere():
    params, x = _skewed_moe(8)
    valid = jnp.asarray([[True] * 5 + [False] * 3])
    got, aux = moe_lib.apply_moe_dropless(params, x, CFG, valid)
    want = _all_experts(params, x, CFG)
    np.testing.assert_allclose(got[0, :5], want[0, :5], rtol=2e-4,
                               atol=2e-4)
    assert int(aux["moe_pairs"]) == 5 * CFG.experts_per_token


@pytest.mark.parametrize("sizes", [[5, 0, 0, 17, 3, 0, 10, 0],
                                   [0, 0, 0, 0, 0, 0, 0, 40],
                                   [0] * 8])
def test_grouped_matmul_kernel_equals_ragged_dot(sizes):
    M, K, N = 40, 128, 256
    lhs = jax.random.normal(jax.random.PRNGKey(5), (M, K)).astype(jnp.bfloat16)
    rhs = jax.random.normal(jax.random.PRNGKey(6),
                            (len(sizes), K, N)).astype(jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm.gmm(lhs, rhs, gs, interpret=True).astype(jnp.float32)
    want = jax.lax.ragged_dot(lhs, rhs, gs,
                              preferred_element_type=jnp.float32)
    used = int(gs.sum())
    # bfloat16 output: one rounding of values up to ~50
    np.testing.assert_allclose(got[:used], want[:used], atol=0.25, rtol=0)
    assert float(jnp.abs(got[used:]).max(initial=0.0)) == 0.0


def test_grouped_matmul_kernel_reads_one_layer_of_a_stack():
    """The layer scan hands the kernel the whole stack of experts and the
    layer's index: it multiplies by that layer's experts alone."""
    M, K, N, E = 24, 128, 128, 4
    lhs = jax.random.normal(jax.random.PRNGKey(11),
                            (M, K)).astype(jnp.bfloat16)
    stack = jax.random.normal(jax.random.PRNGKey(12),
                              (3, E, K, N)).astype(jnp.bfloat16)
    gs = jnp.asarray([6, 0, 10, 8], jnp.int32)
    for layer in range(3):
        got = moe_gmm.gmm(lhs, stack, gs, layer=jnp.int32(layer),
                          interpret=True)
        want = moe_gmm.gmm(lhs, stack[layer], gs, interpret=True)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        jnp_path = moe_lib.grouped_matmul(lhs, stack, gs, layer=layer)
        np.testing.assert_allclose(np.asarray(jnp_path, np.float32),
                                   np.asarray(want, np.float32), atol=0.25)


def test_latent_decode_kernel_equals_the_jnp_path():
    B, H, L, R, bs, nb = 3, 4, 128, 64, 16, 4
    q = jax.random.normal(jax.random.PRNGKey(7),
                          (B, H, L)).astype(jnp.bfloat16)
    pool = jax.random.normal(jax.random.PRNGKey(8),
                             (12, bs, L)).astype(jnp.bfloat16)
    tables = jnp.asarray([[3, 5, 7, 1], [2, 0, 11, 11], [11] * 4], jnp.int32)
    lens = jnp.asarray([50, 20, 0], jnp.int32)
    got = mla_decode.mla_decode_attention(q, pool, tables, lens, rank=R,
                                          interpret=True)
    seq = paged_lib.gather_tokens(pool, tables)
    want = layers.decode_attention(
        q[:, None], seq[:, :, None], mla._values(seq, R)[:, :, None],
        q_position=lens - 1, kv_positions=jnp.broadcast_to(
            jnp.arange(nb * bs), (B, nb * bs)), valid_len=lens)[:, 0, :, :R]
    np.testing.assert_allclose(np.asarray(got[:2], np.float32),
                               np.asarray(want[:2], np.float32), atol=2e-2)
    assert float(jnp.abs(got[2].astype(jnp.float32)).max()) == 0.0


def test_latent_prefill_kernel_equals_the_jnp_path():
    C, Tp, H, L, R, bs = 2, 8, 4, 128, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(9),
                          (C, Tp, H, L)).astype(jnp.bfloat16)
    pool = jax.random.normal(jax.random.PRNGKey(10),
                             (12, bs, L)).astype(jnp.bfloat16)
    tables = jnp.asarray([[3, 5, 7, 1], [2, 0, 11, 11]], jnp.int32)
    meta = jnp.asarray([[0, 20, 8, 0], [1, 0, 5, 8]], jnp.int32)
    got = mla_prefill.mla_prefill_attention(q, pool, tables, meta, rank=R,
                                            interpret=True)
    seq = paged_lib.gather_tokens(pool, tables)
    for c, n in ((0, 8), (1, 5)):
        want = layers.chunked_attention(
            q[c:c + 1], seq[c:c + 1, :, None],
            mla._values(seq, R)[c:c + 1, :, None],
            q_positions=meta[c, 1] + jnp.arange(Tp),
            kv_positions=jnp.arange(seq.shape[1]), causal=True)[0, ..., :R]
        np.testing.assert_allclose(np.asarray(got[c, :n], np.float32),
                                   np.asarray(want[:n], np.float32),
                                   atol=2e-2)


def test_expert_counter_is_per_serve_with_a_persistent_pool():
    """``moe_count`` lives in the paged cache state, which a persistent
    prefix cache keeps across serves: each serve still reports its own
    decode pairs, the hand count requests x decode tokens x top-k x MoE
    layers, as a fresh engine does."""
    from repro.launch import serve
    from repro.serving.engine import Request, ServingEngine

    st = serve.build("deepseek-v2-lite", smoke=True, n_requests=4,
                     max_new_tokens=6)
    cfg = st.cfg
    kw = dict(mode="continuous", kv="paged", prefill="chunked",
              prefix_cache=True, input_bucket=32, max_new_tokens=6,
              eos_id=-1, chunk_size=32, num_slots=4, kv_num_blocks=32)

    def reqs():
        return [Request(text=t, arrival=0.0, task_id=i)
                for i, t in enumerate(st.texts[:4])]

    want = 4 * 5 * cfg.experts_per_token * (cfg.num_layers
                                            - cfg.num_dense_layers)
    eng = ServingEngine(st.params, cfg, st.policy, st.profile,
                        persist_prefix_cache=True, **kw)
    first, second = eng.serve(reqs()), eng.serve(reqs())
    assert second["prefix_hit_rate"] > 0            # the pool persisted
    fresh = ServingEngine(st.params, cfg, st.policy, st.profile,
                          **kw).serve(reqs())
    for res in (first, second, fresh):
        assert res["moe_decode_pairs"] == want
        assert 0 < res["moe_decode_experts"] <= res["moe_decode_pairs"]
