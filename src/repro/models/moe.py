"""Mixture-of-Experts layer: top-k router + sort-based capacity dispatch
for training, and a dropless grouped-matmul layer for serving
(``apply_moe_dropless``).

TPU-idiomatic design notes:
  * dispatch is sort-based (argsort by expert id + rank-within-expert
    capacity cut) rather than the classic (tokens, E, C) one-hot einsum —
    the one-hot dispatch tensor for the 1T Kimi-K2 config (65k tokens/device
    x 384 experts x ~1.7k capacity) would be ~4e13 elements; the sort-based
    path moves only (E*C, D) activations and lets GSPMD lower the
    expert-parallel exchange to all-to-all style collectives.
  * expert weights are stacked (E, D, F) and sharded on the expert axis
    ("model" mesh axis) + FSDP on "data" for the trillion-param config.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.kernels import moe_gmm
from repro.kernels import ops as kernel_ops
from repro.sharding import context as shctx

from . import layers

Array = jax.Array


def init_moe(key: Array, cfg, dtype) -> dict:
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (D, E), jnp.float32),
        "w_gate": layers.dense_init(ks[1], (E, D, F), dtype),
        "w_up": layers.dense_init(ks[2], (E, D, F), dtype),
        "w_down": layers.dense_init(ks[3], (E, F, D), dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = layers.init_mlp(
            ks[4], D, F * cfg.num_shared_experts, "swiglu", dtype)
    return p


def _capacity(num_tokens: int, cfg) -> int:
    cap = int(cfg.capacity_factor * num_tokens * cfg.experts_per_token
              / cfg.num_experts)
    return max(8, -(-cap // 8) * 8)


# the serving layer's aux: no loss, nothing dropped
NO_DROP = {"moe_aux_loss": jnp.float32(0.0), "moe_drop_frac": jnp.float32(0.0)}


def apply_moe(params: dict, x: Array, cfg) -> tuple[Array, dict]:
    """Dispatcher: expert-parallel shard_map path when a sharding policy is
    active (distributed runs), single-device reference path otherwise."""
    policy = shctx.current()
    if policy is not None:
        return apply_moe_ep(params, x, cfg, policy)
    return apply_moe_local(params, x, cfg)


def route(params: dict, xt: Array, cfg) -> tuple[Array, Array]:
    """Router in float32: (top-k gates (T, K), top-k experts (T, K)).
    Softmax then greedy top-k; the gates are
    renormalised only where ``cfg.norm_topk_prob``."""
    logits = jnp.dot(xt.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_e


def grouped_matmul(lhs: Array, rhs: Array, group_sizes: Array,
                   use_pallas: bool = False, layer=None) -> Array:
    """Rows of ``lhs`` sorted by group times their group's ``rhs``
    (``rhs[layer]`` of a layer stack where ``layer`` is given): the
    Pallas ``moe_gmm`` kernel, or ``lax.ragged_dot``."""
    if use_pallas:
        return moe_gmm.gmm(lhs, rhs, group_sizes, layer=layer,
                           interpret=kernel_ops._default_interpret())
    if layer is not None:
        rhs = rhs[layer]
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=jnp.float32
                          ).astype(lhs.dtype)


def _experts_here(xt: Array, live, router, wg, wu, wd, cfg, e0, n_here,
                  use_pallas: bool = False, layer=None):
    """Every routed copy of the rows ``xt`` (T, D) that goes to one of
    the experts ``e0 .. e0 + n_here - 1``, whose weights ``wg``/``wu``/
    ``wd`` are, through that expert: (output (T, D) float32, the group
    size of each of those experts).  No capacity: the copies are sorted
    by expert and each expert multiplies exactly its own group
    (``grouped_matmul``); copies bound elsewhere, and every copy of a row
    that ``live`` (T,) marks False, sort after the last group, into none."""
    T, D = xt.shape
    K = cfg.experts_per_token
    top_p, top_e = route({"router": router}, xt, cfg)
    here = top_e.reshape(-1) - e0
    mine = (here >= 0) & (here < n_here)
    if live is not None:
        mine = mine & jnp.repeat(live, K)
    here = jnp.where(mine, here, n_here)
    order = jnp.argsort(here, stable=True)
    sorted_tok = order // K
    group_sizes = jnp.bincount(here, length=n_here + 1)[:n_here].astype(
        jnp.int32)
    xs = xt[sorted_tok]
    h = jax.nn.silu(grouped_matmul(xs, wg, group_sizes, use_pallas,
                                   layer)) * \
        grouped_matmul(xs, wu, group_sizes, use_pallas, layer)
    ys = grouped_matmul(h, wd, group_sizes, use_pallas, layer)
    w = jnp.where(mine[order], top_p.reshape(-1)[order], 0.0)
    out = jnp.zeros((T, D), jnp.float32).at[sorted_tok].add(
        ys.astype(jnp.float32) * w[:, None])
    return out, group_sizes


def apply_moe_dropless(params: dict, x: Array, cfg, valid=None,
                       use_pallas: bool = False,
                       layer=None) -> tuple[Array, dict]:
    """The serving expert layer: every routed token reaches its experts
    (``_experts_here``), on one device or, where a sharding policy is
    active, on the expert shards (``apply_moe_ep(dropless=True)``).

    x: (B, S, D); valid: optional (B, S) bool, False rows (padding,
    dead decode slots) are routed nowhere and get only the shared
    experts' output.  ``layer``: the experts' weights are a layer stack
    (``transformer.apply_stack``) and this is the layer's index.  On one
    device the aux dict counts the routed token-expert pairs
    (``moe_pairs``) and the experts they reached (``moe_experts``)."""
    policy = shctx.current()
    if policy is not None:
        return apply_moe_ep(params, x, cfg, policy, dropless=True,
                            valid=valid)
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    live = None if valid is None else valid.reshape(-1)
    out, group_sizes = _experts_here(
        xt, live, params["router"], params["w_gate"], params["w_up"],
        params["w_down"], cfg, 0, cfg.num_experts, use_pallas, layer)
    if cfg.num_shared_experts:
        out = out + layers.apply_mlp(params["shared"], xt,
                                     "swiglu").astype(jnp.float32)
    aux = dict(NO_DROP, moe_pairs=jnp.sum(group_sizes),
               moe_experts=jnp.sum(group_sizes > 0).astype(jnp.int32))
    return out.astype(x.dtype).reshape(B, S, D), aux


def apply_moe_local(params: dict, x: Array, cfg) -> tuple[Array, dict]:
    """x: (B, S, D) -> (out, aux_metrics).

    aux_metrics carries the load-balance and z losses (summed into the
    training loss) plus drop-fraction diagnostics.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    C = _capacity(T, cfg)
    xt = x.reshape(T, D)

    router_logits = xt.astype(jnp.float32) @ params["router"]       # (T, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_e = lax.top_k(probs, K)                              # (T, K)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)      # renorm

    # ---- sort-based dispatch -------------------------------------------
    flat_e = top_e.reshape(-1)                                      # (T*K,)
    flat_w = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    sorted_w = flat_w[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))           # (E,)
    rank = jnp.arange(T * K) - seg_start[sorted_e]
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)              # drop->OOB

    xe = jnp.zeros((E * C, D), x.dtype)
    xe = xe.at[slot].set(xt[sorted_tok] *
                         keep[:, None].astype(x.dtype), mode="drop")
    xe = xe.reshape(E, C, D)

    # ---- expert computation (batched over experts) ---------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(E * C, D)

    # ---- combine --------------------------------------------------------
    contrib = ye[jnp.where(keep, slot, 0)] * \
        (sorted_w * keep).astype(x.dtype)[:, None]
    out = jnp.zeros((T, D), x.dtype).at[sorted_tok].add(contrib)

    if cfg.num_shared_experts:
        out = out + layers.apply_mlp(params["shared"], xt, "swiglu")
    out = out.reshape(B, S, D)

    # ---- aux losses ------------------------------------------------------
    me = jnp.mean(probs, axis=0)                                     # (E,)
    ce = jnp.mean(
        (jax.nn.one_hot(top_e, E).sum(axis=1)).astype(jnp.float32), axis=0)
    load_balance = E * jnp.sum(me * ce) / K
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(router_logits, axis=-1)))
    dropped = 1.0 - jnp.sum(keep) / (T * K)
    aux = {
        "moe_aux_loss": cfg.router_aux_weight * load_balance
        + cfg.router_z_weight * z_loss,
        "moe_drop_frac": dropped,
    }
    return out, aux


# ---------------------------------------------------------------------------
# expert-parallel path (shard_map)
# ---------------------------------------------------------------------------
#
# Activation layout under the production mesh: x is sharded over the batch
# axes ("pod","data") and *replicated* over "model"; expert weights are
# sharded E -> "model" (kimi: 384/16 = 24 local experts) and FSDP-sharded
# over ("data","pod").  Because x is replicated over "model", each expert
# owner can gather its tokens locally — dispatch needs NO all-to-all; the
# only inter-device traffic is (a) the FSDP all-gather of the local expert
# weights and (b) one psum over "model" of the (T_loc, D) combined output,
# which is exactly the all-reduce a dense TP layer would pay anyway.
#
# When E does not divide the model axis (mixtral: 8 experts on a 16-wide
# axis) every model shard keeps all E experts but shards the expert d_ff
# ("mlp" -> "model"); the same closing psum then completes the partial
# w_down contraction instead.  Both cases are one code path below.
#
# Serving (``dropless=True``) keeps both layouts and their closing
# reductions, and replaces the capacity dispatch by ``_experts_here``:
# each shard's experts take every copy routed to them.


def _axes_tuple(r):
    if r is None:
        return ()
    return (r,) if isinstance(r, str) else tuple(r)


def apply_moe_ep(params: dict, x: Array, cfg, policy, dropless: bool = False,
                 valid=None) -> tuple[Array, dict]:
    mesh = policy.mesh
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    batch_axes = _axes_tuple(policy.resolve(B, "batch"))
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    model_sz = mesh.shape["model"] if "model" in mesh.axis_names else 1
    experts_sharded = E % model_sz == 0 and model_sz > 1
    E_loc = E // model_sz if experts_sharded else E
    T_loc = (B // n_batch_shards) * S
    serving = getattr(policy, "serving", False)
    # serving-layout decode: the whole token set is tiny, so replicate it
    # and never move weights (EXPERIMENTS.md §Perf pair B) — one psum of
    # (T, D) replaces the per-layer FSDP all-gather of expert weights.
    # (batch_axes may be empty — long_500k's B=1 is replicated already.)
    token_replicated = serving and B * S * K <= 32768
    C = _capacity(B * S if token_replicated else T_loc, cfg)

    x_spec = P(batch_axes if batch_axes else None, None, None)
    if valid is None:
        valid = jnp.ones((B, S), bool)
    wg_spec = policy.spec(params["w_gate"].shape, policy.moe_axes("gate_up"))
    wd_spec = policy.spec(params["w_down"].shape, policy.moe_axes("down"))
    router_spec = P(None, None)
    # axes the weights are sharded over besides "experts" (gathered in the
    # big-token path; left in place in the token-replicated path)
    gath_axes_g = tuple(_axes_tuple(wg_spec[2 if serving else 1]))
    gath_axes_d = tuple(_axes_tuple(wd_spec[1 if serving else 2]))

    def f(xl, vl, router, wg, wu, wd):
        # xl: (B_loc, S, D); vl: (B_loc, S); router: (D, E) replicated
        if token_replicated:
            return _f_token_replicated(xl, vl, router, wg, wu, wd)
        # train/prefill: gather the expert weights' non-expert shard axis
        # (ZeRO layout: d_model; serving layout: d_ff)
        if gath_axes_g:
            ax = 2 if serving else 1
            wg = lax.all_gather(wg, gath_axes_g, axis=ax, tiled=True)
            wu = lax.all_gather(wu, gath_axes_g, axis=ax, tiled=True)
        if gath_axes_d:
            ax = 1 if serving else 2
            wd = lax.all_gather(wd, gath_axes_d, axis=ax, tiled=True)
        xt = xl.reshape(T_loc, D)
        e0 = (lax.axis_index("model") * E_loc) if experts_sharded else 0
        if dropless:
            out, _ = _experts_here(xt, vl.reshape(-1), router, wg, wu, wd,
                                   cfg, e0, E_loc)
            if model_sz > 1:
                out = lax.psum(out, "model")
            return out.astype(xl.dtype).reshape(xl.shape), NO_DROP

        router_logits = xt.astype(jnp.float32) @ router          # (T, E)
        probs = jax.nn.softmax(router_logits, axis=-1)
        top_p, top_e = lax.top_k(probs, K)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        flat_e = top_e.reshape(-1)
        flat_w = top_p.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(T_loc), K)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        sorted_tok = flat_tok[order]
        sorted_w = flat_w[order]
        local_e = sorted_e - e0
        valid = (local_e >= 0) & (local_e < E_loc)
        seg_start = jnp.searchsorted(sorted_e, e0 + jnp.arange(E_loc))
        rank = jnp.arange(T_loc * K) - \
            seg_start[jnp.clip(local_e, 0, E_loc - 1)]
        keep = valid & (rank < C)
        slot = jnp.where(keep, local_e * C + rank, E_loc * C)    # drop->OOB

        xe = jnp.zeros((E_loc * C, D), xl.dtype)
        xe = xe.at[slot].set(xt[sorted_tok] *
                             keep[:, None].astype(xl.dtype), mode="drop")
        xe = xe.reshape(E_loc, C, D)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
            jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_loc * C, D)

        contrib = ye[jnp.where(keep, slot, 0)] * \
            (sorted_w * keep).astype(xl.dtype)[:, None]
        out = jnp.zeros((T_loc, D), jnp.float32).at[sorted_tok].add(
            contrib.astype(jnp.float32))
        if model_sz > 1:
            out = lax.psum(out, "model")
        out = out.astype(xl.dtype).reshape(xl.shape)

        # aux losses: router tensors are replicated over "model", so the
        # load-balance statistics only need averaging over the batch axes.
        # The per-expert rates me/ce must be averaged BEFORE the product:
        # the loss is bilinear in the global rates, and a mean of
        # per-shard products picks up the across-shard covariance (~1%
        # off the single-device oracle on an E=64 smoke config).
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            (jax.nn.one_hot(top_e, E).sum(axis=1)).astype(jnp.float32),
            axis=0)
        if batch_axes:
            me = lax.pmean(me, batch_axes)
            ce = lax.pmean(ce, batch_axes)
        load_balance = E * jnp.sum(me * ce) / K
        z_loss = jnp.mean(
            jnp.square(jax.nn.logsumexp(router_logits, axis=-1)))
        n_drop = jnp.sum(valid & ~keep).astype(jnp.float32)
        if model_sz > 1 and experts_sharded:
            n_drop = lax.psum(n_drop, "model")
        elif model_sz > 1:
            n_drop = lax.pmean(n_drop, "model")
        dropped = n_drop / (T_loc * K)
        aux = {
            "moe_aux_loss": cfg.router_aux_weight * load_balance
            + cfg.router_z_weight * z_loss,
            "moe_drop_frac": dropped,
        }
        if batch_axes:
            aux = jax.tree.map(lambda v: lax.pmean(v, batch_axes), aux)
        return out, aux

    def _f_token_replicated(xl, vl, router, wg, wu, wd):
        # wg/wu: (E_loc, D, F_loc); wd: (E_loc, F_loc, D) — weights stay
        # put; the (tiny) decode token set is gathered instead.
        T_all = B * S
        xt = xl.reshape(T_loc, D)
        live = vl.reshape(T_loc)
        if batch_axes:
            xt = lax.all_gather(xt, batch_axes, axis=0,
                                tiled=True)              # (T_all, D)
            live = lax.all_gather(live, batch_axes, axis=0, tiled=True)
        e0 = (lax.axis_index("model") * E_loc) if experts_sharded else 0
        if dropless:
            out_all, _ = _experts_here(xt, live, router, wg, wu, wd, cfg,
                                       e0, E_loc)
            return _close_replicated(out_all, xl), NO_DROP

        router_logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(router_logits, axis=-1)
        top_p, top_e = lax.top_k(probs, K)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        flat_e = top_e.reshape(-1)
        flat_w = top_p.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(T_all), K)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        sorted_tok = flat_tok[order]
        sorted_w = flat_w[order]
        local_e = sorted_e - e0
        valid = (local_e >= 0) & (local_e < E_loc)
        seg_start = jnp.searchsorted(sorted_e, e0 + jnp.arange(E_loc))
        rank = jnp.arange(T_all * K) - \
            seg_start[jnp.clip(local_e, 0, E_loc - 1)]
        keep = valid & (rank < C)
        slot = jnp.where(keep, local_e * C + rank, E_loc * C)

        xe = jnp.zeros((E_loc * C, D), xl.dtype)
        xe = xe.at[slot].set(xt[sorted_tok] *
                             keep[:, None].astype(xl.dtype), mode="drop")
        xe = xe.reshape(E_loc, C, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
            jnp.einsum("ecd,edf->ecf", xe, wu)           # (E_loc, C, F_loc)
        ye = jnp.einsum("ecf,efd->ecd", h, wd)           # partial over F
        ye = ye.reshape(E_loc * C, D)

        contrib = ye[jnp.where(keep, slot, 0)] * \
            (sorted_w * keep).astype(ye.dtype)[:, None]
        out_all = jnp.zeros((T_all, D), jnp.float32).at[sorted_tok].add(
            contrib.astype(jnp.float32))
        out = _close_replicated(out_all, xl)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            (jax.nn.one_hot(top_e, E).sum(axis=1)).astype(jnp.float32),
            axis=0)
        load_balance = E * jnp.sum(me * ce) / K
        z_loss = jnp.mean(
            jnp.square(jax.nn.logsumexp(router_logits, axis=-1)))
        n_drop = jnp.sum(valid & ~keep).astype(jnp.float32)
        if model_sz > 1 and experts_sharded:
            n_drop = lax.psum(n_drop, "model")
        elif model_sz > 1:
            n_drop = lax.pmean(n_drop, "model")
        # F-sharding replicates the drop count across the batch axes
        if batch_axes:
            n_drop = lax.pmean(n_drop, batch_axes)
        dropped = n_drop / (T_all * K)
        aux = {
            "moe_aux_loss": cfg.router_aux_weight * load_balance
            + cfg.router_z_weight * z_loss,
            "moe_drop_frac": dropped,
        }
        return out, aux

    def _close_replicated(out_all, xl):
        # One reduction closes BOTH partial sums — over "model" iff the
        # expert dim is actually partitioned there, and over exactly the
        # axes that shard d_ff (axes where computation was identical must
        # NOT be summed: they hold replicas, not partials).
        f_axes = tuple(_axes_tuple(wg_spec[2]))
        psum_axes = (("model",) if experts_sharded else ()) + f_axes
        if psum_axes:
            out_all = lax.psum(out_all, psum_axes)
        idx = jnp.int32(0)
        for a in batch_axes:
            idx = idx * mesh.shape[a] + lax.axis_index(a)
        out = lax.dynamic_slice_in_dim(out_all, idx * T_loc, T_loc, 0)
        return out.astype(xl.dtype).reshape(xl.shape)

    fn = jax.shard_map(
        f, mesh=mesh,
        in_specs=(x_spec, P(*x_spec[:2]), router_spec, wg_spec, wg_spec,
                  wd_spec),
        out_specs=(x_spec, {"moe_aux_loss": P(), "moe_drop_frac": P()}),
        check_vma=False)
    out, aux = fn(x, valid, params["router"], params["w_gate"],
                  params["w_up"], params["w_down"])
    if cfg.num_shared_experts:
        xt = x.reshape(B * S, D)
        out = out + layers.apply_mlp(params["shared"], xt,
                                     "swiglu").reshape(B, S, D)
    return out, aux
