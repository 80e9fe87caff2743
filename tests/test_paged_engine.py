"""Paged KV-cache engine coverage.

Acceptance properties of the kvcache subsystem (ISSUE 2):

  * token parity — with the same slot count and an ample block budget,
    ``kv="paged"`` reproduces the contiguous continuous engine's output
    TOKEN FOR TOKEN (the paged gather view is bit-identical to the
    contiguous layout; masked tails contribute exp(-inf) == 0 exactly);
  * no leaks — after a full ``serve()`` every block is back on the free
    list;
  * engine-vs-sim parity extends to memory: with a tight block budget
    the engine's admission gate and the simulator's block-budget model
    make identical decisions (same completion order, same rejection
    count, same utilization trace);
  * capacity — at an equal KV-memory budget, paging admits strictly
    more concurrent sequences than the contiguous cache.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import datagen, personas, priority as prio
from repro.core import scheduler as sched, simulator
from repro.kvcache import paged as paged_lib
from repro.models import model as model_lib, transformer
from repro.obs import Observability
from repro.serving import generate
from repro.serving.engine import Request, ServingEngine

SLOTS = 3
MAX_NEW = 6
BUCKET = 8
CAPS = [2, 6, 1, 4, 6, 2, 3, 5, 1, 6, 2, 4]


def _persona(batch_size=SLOTS):
    return dataclasses.replace(personas.get_persona("bart"),
                               batch_size=batch_size)


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke_config("starcoder2-3b")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    corpus = datagen.generate_corpus(
        datagen.VARIANCE_MIXES["normal"], 64, seed=0)
    train, test = datagen.train_test_split(corpus, train_frac=0.5)
    persona = _persona()
    profile = sched.offline_profile(train, persona, epochs=15)
    return cfg, params, persona, profile, test


def _requests(test, caps):
    return [Request(text=t.text, arrival=0.0, task_id=i,
                    max_new_tokens=c)
            for i, (t, c) in enumerate(zip(test, caps))]


def _sim_tasks(test, caps, profile, persona, xi=2.0):
    out = []
    for i, (t, c) in enumerate(zip(test, caps)):
        u = profile.predictor.score(t.text)
        d = prio.priority_point(0.0, len(t.text.split()), persona.phi,
                                None, xi=xi)
        out.append(prio.SimTask(
            task=Request(text=t.text, arrival=0.0, task_id=i),
            u=float(max(u, 0.0)), r=0.0, d=d,
            input_len=float(len(t.text.split())), true_out_len=int(c)))
    return out


def _engine(setup, policy_name="fifo", **kw):
    cfg, params, persona, profile, _ = setup
    pcfg = dataclasses.replace(profile.policy_config(), tau=1e18)
    return ServingEngine(
        params, cfg, sched.POLICIES[policy_name](persona, pcfg), profile,
        input_bucket=BUCKET, max_new_tokens=MAX_NEW, mode="continuous",
        eos_id=-1, **kw)


def test_paged_matches_contiguous_token_for_token(setup):
    """Same slots, ample blocks: identical scheduling AND identical
    greedy tokens, request by request."""
    _, _, _, _, test = setup
    res = {}
    for kv in ("contiguous", "paged"):
        res[kv] = _engine(setup, kv=kv, kv_block_size=4).serve(
            _requests(test, CAPS))
    assert (res["paged"]["completion_order"]
            == res["contiguous"]["completion_order"])
    cont = {t.task.task_id: t.task for t in res["contiguous"]["tasks"]}
    pagd = {t.task.task_id: t.task for t in res["paged"]["tasks"]}
    for i, c in enumerate(CAPS):
        assert pagd[i].out_len == cont[i].out_len == c
        assert pagd[i].out_tokens == cont[i].out_tokens
    # the paged pool holds the same live tokens in fewer reserved
    # blocks: its utilization peak must come in strictly under the
    # contiguous engine's all-slots-busy 1.0
    assert res["paged"]["kv_util_peak"] < res["contiguous"]["kv_util_peak"]


def test_no_block_leaks_after_full_serve(setup):
    _, _, _, _, test = setup
    eng = _engine(setup, kv="paged", kv_block_size=4)
    res = eng.serve(_requests(test, CAPS))
    assert res["n_tasks"] == len(CAPS)
    eng.allocator.check_no_leaks()
    assert eng.allocator.num_free == eng.kv_num_blocks
    # memory metrics are reported
    assert res["kv"]["kind"] == "paged"
    assert 0.0 < res["kv_util_mean"] <= res["kv_util_peak"] <= 1.0
    assert res["rejected_for_memory"] == 0          # ample default budget


@pytest.mark.parametrize("policy_name", ["fifo", "rt-lm"])
def test_engine_vs_sim_parity_block_budget(setup, policy_name):
    """Tight budget (forces rejections): the engine's reservation gate
    and the simulator's block-budget model decide identically."""
    cfg, params, persona, profile, test = setup
    bs, nb, slots = 4, 7, 4      # worst case ceil((8+5)/4)=4 of 7 blocks
    eng = _engine(setup, policy_name, kv="paged", num_slots=slots,
                  kv_block_size=bs, kv_num_blocks=nb)
    res = eng.serve(_requests(test, CAPS))
    eng.allocator.check_no_leaks()
    assert res["rejected_for_memory"] > 0            # budget actually binds

    pcfg = dataclasses.replace(profile.policy_config(), tau=1e18)
    sim = simulator.simulate_continuous(
        _sim_tasks(test, CAPS, profile, persona),
        sched.POLICIES[policy_name](persona, pcfg),
        num_slots=slots, kv_block_size=bs, kv_num_blocks=nb,
        prompt_len=BUCKET)
    assert res["completion_order"] == [t.task.task_id for t in sim.tasks]
    assert res["rejected_for_memory"] == sim.kv_rejected
    np.testing.assert_allclose(res["kv_util_peak"], sim.kv_util_peak)
    np.testing.assert_allclose(res["kv_util_mean"], sim.kv_util_mean)


def test_paged_admits_more_concurrency_at_equal_budget():
    """Simulator form of the capacity acceptance gate: same KV-token
    budget, heterogeneous outputs — the block-table cache runs strictly
    more concurrent sequences than C contiguous slots (the real-engine
    version is benchmarks/continuous_vs_batch.py::run_paged)."""
    persona = _persona(batch_size=8)
    rng = np.random.default_rng(0)
    n = 96
    caps = np.where(rng.random(n) < 0.25, 48, 4).astype(int)
    arrivals = np.sort(rng.uniform(0.0, 0.5, n))

    def tasks():
        return [prio.SimTask(task=i, u=5.0, r=float(r), d=float(r) + 4.0,
                             input_len=5.0, true_out_len=int(c))
                for i, (c, r) in enumerate(zip(caps, arrivals))]

    pcfg = sched.PolicyConfig(u_scale=30.0, tau=1e18)
    bucket, max_new, bs = 8, 48, 16
    max_len = bucket + max_new + 8
    budget_blocks = paged_lib.default_num_blocks(persona.batch_size,
                                                 max_len, bs)
    cont = simulator.run_policy(tasks(), "fifo", persona, pcfg,
                                mode="continuous")
    paged = simulator.run_policy(tasks(), "fifo", persona, pcfg,
                                 mode="continuous",
                                 num_slots=3 * persona.batch_size,
                                 kv_block_size=bs,
                                 kv_num_blocks=budget_blocks,
                                 prompt_len=bucket)
    assert cont.peak_concurrency == persona.batch_size
    assert paged.peak_concurrency > cont.peak_concurrency
    assert paged.throughput_per_min > cont.throughput_per_min


def test_paged_validation():
    cfg = configs.get_smoke_config("starcoder2-3b")
    persona = _persona()
    pcfg = sched.PolicyConfig()
    policy = sched.POLICIES["fifo"](persona, pcfg)
    with pytest.raises(ValueError, match="continuous"):
        ServingEngine(None, cfg, policy, None, mode="batch", kv="paged")
    with pytest.raises(ValueError, match="deadlock"):
        ServingEngine(None, cfg, policy, None, mode="continuous",
                      kv="paged", kv_block_size=4, kv_num_blocks=2)
    # paging needs full attention / no recurrent state
    ssm_cfg = configs.get_smoke_config("mamba2-1.3b")
    with pytest.raises(NotImplementedError):
        transformer.init_paged_cache(ssm_cfg, 2, 8, 4)
    hyb_cfg = configs.get_smoke_config("recurrentgemma-9b")
    with pytest.raises(NotImplementedError):
        ServingEngine(None, hyb_cfg, policy, None, mode="continuous",
                      kv="paged")


def test_dead_row_gives_kernel_length_zero(setup):
    """The paged decode call site passes a length of 0 for a row whose
    table starts at the trash page (the pool's last page): the kernel
    walks none of its pages, so its attention adds nothing, and the live
    rows' outputs are bit-identical to a run where that row is live."""
    cfg, params, _, _, _ = setup
    p = jax.tree.map(lambda a: a[0], params["stack"]["scan0"])
    bs, nb, N = 4, 4, 9                # 8 pages plus the trash page
    trash = N - 1
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    shape = (N, bs, cfg.num_kv_heads, cfg.head_dim)
    kp = jax.random.normal(ks[0], shape).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[1], shape).astype(jnp.bfloat16)
    x = jax.random.normal(ks[2], (3, 1, cfg.d_model)).astype(jnp.bfloat16)
    pos = jnp.asarray([5, 9, 6], jnp.int32)
    live = np.full((3, nb), trash, np.int32)
    live[0, :2], live[1, :3], live[2, :2] = [0, 1], [2, 3, 4], [5, 6]
    dead = live.copy()
    dead[0] = trash                    # row 0: an evicted slot
    out = {}
    for name, tables in (("live", live), ("dead", dead)):
        out[name], _, _ = transformer._attn_decode_paged(
            p, x, kp, vp, pos, jnp.asarray(tables), cfg, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(out["dead"][1:]),
                                  np.asarray(out["live"][1:]))
    # a length-0 row attends to nothing: the residual passes through
    np.testing.assert_array_equal(np.asarray(out["dead"][0]),
                                  np.asarray(x[0]))
    ref_out, _, _ = transformer._attn_decode_paged(
        p, x, kp, vp, pos, jnp.asarray(live), cfg, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out["live"], np.float32),
                               np.asarray(ref_out, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_persistent_pool_survives_the_donated_decode_window(setup):
    """The decode window donates the cache and updates the stacked pools
    in place: the cache passed in is gone after the launch.  Two serves
    on one engine with a persistent prefix cache, the second hitting
    prefixes the first wrote into the pool, read no donated buffer and
    serve the tokens a cold engine serves."""
    cfg, params, _, _, test = setup
    window = generate.make_paged_decode_steps_fn(cfg, False)
    cache = transformer.init_paged_cache(cfg, SLOTS, 8, 4)
    tables = jnp.full((SLOTS, 2), 7, jnp.int32)
    window(params, cache, jnp.zeros((SLOTS, 1), jnp.int32), tables,
           num_steps=1)
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))

    kw = dict(kv="paged", kv_block_size=4, prefix_cache=True,
              prefill="chunked", chunk_size=4, token_budget=8)
    eng = _engine(setup, persist_prefix_cache=True, **kw)
    first = eng.serve(_requests(test, CAPS))
    second = eng.serve(_requests(test, CAPS))
    cold = _engine(setup, **kw).serve(_requests(test, CAPS))
    assert second["prefix_hit_rate"] > first["prefix_hit_rate"]

    def tokens(res):
        return {t.task.task_id: t.task.out_tokens for t in res["tasks"]}

    assert tokens(second) == tokens(first) == tokens(cold)


@pytest.mark.parametrize("prefill", ["stall", "chunked"])
def test_decode_page_share_matches_hand_count(setup, prefill):
    """``decode_page_share`` (and the ``decode.pages`` counter) count
    the pages the decode kernel walks: a request of cap c decodes c - 1
    launches after its prefill token, launch g attending over
    BUCKET + g positions, ceil((BUCKET + g) / bs) pages; dead rows walk
    none."""
    _, _, _, _, test = setup
    bs = 4
    kw = (dict(prefill="chunked", chunk_size=3, token_budget=8)
          if prefill == "chunked" else {})
    obs = Observability()
    eng = _engine(setup, kv="paged", kv_block_size=bs, obs=obs, **kw)
    res = eng.serve(_requests(test, CAPS))
    hand = sum(-(-(BUCKET + g) // bs) for c in CAPS for g in range(1, c))
    nb = -(-eng.max_len // bs)
    assert obs.metrics.counters()["decode.pages"] == hand
    walked = (res["decode_page_share"] * SLOTS * nb
              * res["decode_dispatches"])
    assert walked == pytest.approx(hand)
    assert 0.0 < res["decode_page_share"] < 1.0
