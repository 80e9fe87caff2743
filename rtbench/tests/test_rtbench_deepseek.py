"""DeepSeek-V2 through the benchmark at test size on the CPU: the
program's translation of the configuration, the reference's weights
table and work counts, prefill then paged decode against the reference
(jnp paths and the Pallas kernels in interpret mode), whole runs through
the serving engine whose expert layer is sound or broken, and the
readers of the new per-layer metrics."""

import dataclasses
import json
import time
import weakref
from collections import OrderedDict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rtbench import flops, gen, harness, weights

HERE = Path(__file__).resolve().parents[1]
DATA = HERE / "tests" / "data"
TINY = json.loads((DATA / "tiny-mla.json").read_text())
LITE = json.loads((HERE / "configs" / "deepseek-v2-lite.json").read_text())
KNOBS = {"rate_per_s": 3.0, "policy": "up+c",
         "engine": {"input_bucket": 32, "chunk_size": 32, "num_slots": 16,
                    "kv_num_blocks": 128, "max_new_tokens": 352},
         "check_tokens": 1500,
         # program 0.0073-0.0089 on three seeds, control 0.024-0.047 (CPU)
         "limits": {"served_logit_gap": 0.02}}
NO_METRICS = {"end_to_end": [], "per_layer": []}
S, STEPS = 32, 8


def test_the_program_translates_deepseek_v2_lite():
    from repro.configs import ModelConfig
    got = harness.model_config(LITE)
    assert got == ModelConfig(
        name="deepseek-v2-lite", family="moe", num_layers=9, d_model=2048,
        vocab_size=102400, num_heads=16, num_kv_heads=16, head_dim=192,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=10000.0, rope_factor=40.0,
        rope_original_max_pos=4096, rope_mscale=0.707,
        rope_mscale_all_dim=0.707,
        d_ff=10944, mlp_act="swiglu", num_experts=64, experts_per_token=6,
        moe_d_ff=1408, num_shared_experts=2, num_dense_layers=1,
        norm_topk_prob=False, norm_eps=1e-6,
        tie_embeddings=False, source=LITE["source"])
    assert got.latent_dim == 576 and got.latent_lanes == 640


@pytest.mark.parametrize("key,value", [("q_lora_rank", 1536),
                                       ("topk_method", "group_limited_greedy"),
                                       ("n_group", 8),
                                       ("scoring_func", "sigmoid")])
def test_what_is_not_computed_is_refused(key, value):
    ref = harness.reference("deepseek_v2")
    with pytest.raises(ValueError, match=key):
        harness.model_config(dict(LITE, **{key: value}))
    with pytest.raises(ValueError):
        ref.Spec.from_config(dict(LITE, **{key: value}))


@pytest.mark.parametrize("key,value", [
    ("routed_scaling_factor", 16.0),
    ("rope_scaling", dict(LITE["rope_scaling"], beta_fast=16)),
    ("rope_scaling", dict(LITE["rope_scaling"], beta_slow=2)),
], ids=["routed_scaling_factor", "beta_fast", "beta_slow"])
def test_the_program_refuses_other_constants(key, value):
    """The program computes DeepSeek-V2-Lite's gate scale (1) and
    yarn correction range (32, 1) as constants; other values are
    refused, where the reference computes them."""
    with pytest.raises(ValueError, match=key):
        harness.model_config(dict(LITE, **{key: value}))
    harness.reference("deepseek_v2").Spec.from_config(
        dict(LITE, **{key: value}))


def test_weights_come_from_the_references_table():
    """Every leaf of the program's tree is drawn at a std the reference
    names, and the table names no leaf the tree lacks."""
    cfg = harness.model_config(TINY)
    ref = harness.reference("deepseek_v2")
    names = {p[-1].key for p, _ in
             jax.tree_util.tree_flatten_with_path(weights.shapes(cfg))[0]}
    assert names == set(ref.SCALES)
    params = weights.make(cfg, 2**31 + 9, ref.SCALES, ref.EMBED_SCALE)
    assert params["stack"]["scan0"]["moe"]["router"].dtype == jnp.float32
    std = float(jnp.std(params["stack"]["scan0"]["moe"]["router"]))
    assert abs(std - ref.SCALES["router"]) < 0.1 * ref.SCALES["router"]


def test_decode_work_is_the_hand_count():
    ref = harness.reference("deepseek_v2")
    got = ref.decode_work(TINY, [(32, 4)])
    # one token's weights, as served, in tiny-mla's sizes: absorbed MLA
    # 256*4*48 + 256*80 + 4*32*64 + 4*64*32 + 4*32*256 = 118784 a layer;
    # the dense layer 118784 + 3*256*512 = 512000; a MoE layer 118784 +
    # router 256*8 + (3 routed + 1 shared)*3*256*128 = 514048; so
    # 512000 + 2*514048 = 1540096, and the head 256*2048 = 524288.
    # Four tokens out are three decode steps, seeing 33, 34, 35 keys.
    keys = 33 + 34 + 35
    assert got["decode"].matmul_flops == 2 * 3 * (1540096 + 524288)
    # scores over 64 + 16 lanes and values over 64, 4 heads, 3 layers
    assert got["mla_decode"].kernel_ops == 2 * 4 * (64 + 16 + 64) * keys * 3
    # latents of 80 values in bf16 read once a key and layer; queries of
    # 80 and outputs of 64 values a head, step and layer
    assert got["mla_decode"].kernel_bytes == (80 * 2 * keys * 3
                                              + 4 * 144 * 2 * 3 * 3)
    assert got["decode"].kernel_ops == got["mla_decode"].kernel_ops


# At test size the table's stds leave the routed experts a small share
# of each layer, so that no fault of the expert layer shows in the
# logits.  Stronger experts, and a router whose logits have std 1.3 (the
# top three of eight take about two thirds of the mass, so renormalising
# the gates changes them), make the three faults below read 0.088,
# 1.46 and 0.092 of the largest logit, against 0.009 for the sound path
# (CPU).
TEST_SCALES = {"router": 0.08, "w_gate": 0.05, "w_up": 0.05, "w_down": 0.05}
ROWS = 16         # decode rows, all the same sequence: routed alike


def _compare(use_pallas):
    """One prompt in each of ``ROWS`` slots, prefilled in two chunks a
    slot through the fused ragged executable into the latent pool, then
    decoded step by step against it: every row's tokens go to the same
    experts, so a capacity would cut rows.  Returns the program's logits
    (steps + 1, ROWS, V), the reference's for row 0's sequence, the
    largest of those and the final cache."""
    from repro.kvcache.paged import PagedKVCache
    from repro.prefill import build_packed_arrays
    from repro.serving import generate

    cfg = harness.model_config(TINY)
    ref = harness.reference(TINY["reference"])
    params = weights.make(cfg, 2**31 + 3, dict(ref.SCALES, **TEST_SCALES),
                          ref.EMBED_SCALE)
    ids = gen.hash_ids("what do you think about pollution in rural areas? "
                       "please give reasons and implications.",
                       cfg.vocab_size, S)
    kvc = PagedKVCache(cfg, ROWS, 3 * ROWS, 16, S + STEPS + 8)
    for r in range(ROWS):
        kvc.set_table(r, [3 * r, 3 * r + 1, 3 * r + 2])
    ragged = generate.make_ragged_prefill_fn(cfg, use_pallas=use_pallas)
    cache = kvc.state
    for start in (0, 16):                  # two chunks a row, two launches
        toks, tc, meta, tabs = build_packed_arrays(
            (16 * ROWS, ROWS, 16),
            [(r, start, ids[start:start + 16], kvc.tables[r])
             for r in range(ROWS)],
            pad_slot=ROWS, table_width=kvc.max_blocks_per_seq,
            trash_block=kvc.trash_block)
        cache, last = ragged(params, cache, {"tokens": jnp.asarray(toks)},
                             jnp.asarray(tc), jnp.asarray(meta),
                             jnp.asarray(tabs), chunk_pad=16)
    rows = [np.asarray(last)]
    served = [np.argmax(rows[0], axis=-1).astype(np.int32)]
    decode = generate.make_paged_decode_fn(cfg, use_pallas=use_pallas)
    for _ in range(STEPS):
        tok, lg, cache = decode(params, cache,
                                jnp.asarray(served[-1])[:, None],
                                kvc.tables_device())
        rows.append(np.asarray(lg))
        served.append(np.asarray(tok[:, 0]))
    program = np.stack(rows)[..., :cfg.vocab_size]
    seq = np.concatenate([ids, np.stack(served[:-1])[:, 0]])
    want = np.asarray(ref.logits(params, jnp.asarray(seq),
                                 spec=ref.Spec.from_config(TINY),
                                 first=S - 1, rows=STEPS + 1))
    return program, want, np.abs(want).max(), cache, params, seq


# bfloat16 (8 significant bits) against float32: a few dozen roundings
# on the way move a logit by up to 2^-8 of the values it is made of, so
# allow 2^-5 of the largest logit.  A wrong mask, page, position, rope,
# gate or expert moves logits by their own size.
TOL = 2.0 ** -5


@pytest.mark.parametrize("use_pallas", [False, True])
def test_paged_prefill_then_decode_match_the_reference(use_pallas):
    program, want, scale, cache, params, seq = _compare(use_pallas)
    # every decode step routed each row's token to three experts in
    # each of the two MoE layers
    pairs, reached = np.asarray(cache["moe_count"])
    assert pairs == STEPS * ROWS * 3 * 2 and 0 < reached <= pairs
    assert np.abs(program - want[:, None]).max() <= TOL * scale
    ref = harness.reference(TINY["reference"])
    other = np.asarray(ref.logits(params, jnp.asarray(np.roll(seq, 1)),
                                  spec=ref.Spec.from_config(TINY),
                                  first=S - 1, rows=STEPS + 1))
    assert np.abs(other - want).max() > 4 * TOL * scale


def _fresh_executables(monkeypatch):
    """New jitted entry points, so that the patched layer is traced."""
    from repro.serving import generate
    monkeypatch.setattr(generate, "_fn_memo", weakref.WeakValueDictionary())
    monkeypatch.setattr(generate, "_fn_lru", OrderedDict())


def _capacity_cut(moe_lib):
    """The training dispatch (capacity 1.25 of the mean) in serving."""
    def layer(params, x, cfg, valid=None, use_pallas=False, layer=None):
        if layer is not None:                # the layer's own experts
            params = dict(params, **{k: params[k][layer] for k in
                                     ("w_gate", "w_up", "w_down")})
        y, aux = moe_lib.apply_moe_local(params, x, cfg)
        return y, dict(aux, moe_pairs=jnp.int32(0),
                       moe_experts=jnp.int32(0))
    return "apply_moe_dropless", layer


def _renormalised(moe_lib):
    orig = moe_lib.apply_moe_dropless

    def layer(params, x, cfg, valid=None, use_pallas=False, layer=None):
        return orig(params, x, dataclasses.replace(cfg, norm_topk_prob=True),
                    valid, use_pallas, layer)
    return "apply_moe_dropless", layer


def _next_expert(moe_lib):
    """The (k+1)-th expert in place of the k-th."""
    orig = moe_lib.route

    def route(params, xt, cfg):
        k = cfg.experts_per_token
        p, e = orig(params, xt, dataclasses.replace(
            cfg, experts_per_token=k + 1))
        keep = list(range(k - 1)) + [k]
        return p[:, keep], e[:, keep]
    return "route", route


@pytest.mark.parametrize("fault", [_capacity_cut, _renormalised,
                                   _next_expert])
def test_a_broken_expert_layer_fails_the_comparison(fault, monkeypatch):
    from repro.models import moe as moe_lib
    _fresh_executables(monkeypatch)
    name, patched = fault(moe_lib)
    monkeypatch.setattr(moe_lib, name, patched)
    program, want, scale, _, _, _ = _compare(False)
    assert np.abs(program - want[:, None]).max() > 2 * TOL * scale


def _cell():
    return harness.Cell(
        name="tiny-mla-chat", entry={"name": "tiny-mla-chat", "chips": 1},
        config=TINY, knobs=KNOBS,
        mix=gen.load_mix(HERE / "traffic" / "chat_uncertain.json"))


def _run(seed, tmp_path):
    return harness.Run(_cell(), seed, 4.0, False,
                       t_start=time.perf_counter(), out_dir=tmp_path,
                       log=lambda m: None, require_tpu=False)


def test_served_tokens_match_the_reference(tmp_path):
    """Through the serving engine (continuous, paged, chunked prefill,
    prefix cache): sound, with prefix hits, and the control fails."""
    run = _run(2**31 + 5, tmp_path)
    result = harness.execute(run, NO_METRICS)
    assert result["correct"], result["checks"]
    assert run.cached_total > 0                    # prefix hits served
    assert run.res["moe_decode_pairs"] > run.res["moe_decode_experts"] > 0
    assert run.control_gap() > KNOBS["limits"]["served_logit_gap"]


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


class _Run:
    """What a reader reads of a traced run: the serve's counts, the
    trace summary, the reference's work and the device."""

    def __init__(self, held, total, kernel_s, res=None, work=None):
        self.device = {"kind": "TPU v5 lite"}
        self.cell = harness.Cell(name="x", entry={}, config=LITE, mix={},
                                 knobs={})
        self.res = dict(decode_dispatches=total, prefill_dispatches=total,
                        **(res or {}))
        self.work = work or {}
        self.trace_summary = {
            "launches": {"paged_decode_steps_fn": held,
                         "ragged_prefill_fn": held},
            "kernel_by_name": {"paged_decode_steps_fn": kernel_s,
                               "ragged_prefill_fn": kernel_s}}


def _at_peak(work: flops.Work) -> float:
    return flops.roofline_s(work, PEAKS["flops_per_s"],
                            PEAKS["bytes_per_s"])[0]


@pytest.mark.parametrize("name,part,kernel,entry", [
    ("mla_decode_roofline.lat", "decode", "mla_decode_kernel", "mla_decode"),
    ("mla_prefill_roofline", "prefill", "mla_prefill_kernel", "mla_prefill")])
def test_latent_rooflines_count_the_launches_held(name, part, kernel, entry):
    """A kernel at its roofline whose trace lost three of four launches
    reads 100%, not 400%."""
    w = flops.Work(kernel_ops=4e9, kernel_bytes=8e9)
    quarter = _at_peak(w) / 4
    run = _Run(25, 100, {kernel: quarter}, work={entry: w})
    assert harness.reader(name).read(run) == pytest.approx(100.0)
    run = _Run(100, 100, {kernel: 2 * _at_peak(w)}, work={entry: w})
    assert harness.reader(name).read(run) == pytest.approx(50.0)
    # a program without the kernel or the trace reports nothing
    assert harness.reader(name).read(_Run(25, 100, {}, work={entry: w})) \
        is None


def test_expert_matmul_roofline_reads_the_counters():
    D, F = LITE["hidden_size"], LITE["moe_intermediate_size"]
    res = {"moe_decode_pairs": 6 * 64 * 8 * 1000,
           "moe_decode_experts": 63 * 8 * 1000}
    w = flops.Work(kernel_ops=2.0 * 3 * D * F * res["moe_decode_pairs"],
                   kernel_bytes=2.0 * (3 * D * F * res["moe_decode_experts"]
                                       + (3 * D + 3 * F)
                                       * res["moe_decode_pairs"]))
    # bound by the experts' weight bytes: 63 experts of 8.65 MB a layer
    assert w.kernel_bytes / PEAKS["bytes_per_s"] > \
        w.kernel_ops / PEAKS["flops_per_s"]
    held_half = _at_peak(w) / 2
    run = _Run(500, 1000, {"moe_gmm_kernel": held_half}, res=res)
    reader = harness.reader("moe_gmm_roofline.lat")
    assert reader.read(run) == pytest.approx(100.0)
    assert reader.read(_Run(500, 1000, {"moe_gmm_kernel": held_half})) \
        is None


def test_tokens_per_expert_reads_the_counter():
    reader = harness.reader("moe.tokens_per_expert")
    run = _Run(1, 1, {}, res={"moe_decode_pairs": 3000,
                              "moe_decode_experts": 1200})
    assert reader.read(run) == pytest.approx(2.5)
    assert reader.read(_Run(1, 1, {})) is None
