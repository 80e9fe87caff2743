"""The plain reference against the program's served path at test size
on the CPU: a prompt prefilled in two chunks through the fused ragged
executable into the paged pool, then decoded step by step against the
pool, must give the logits of the reference's full forward pass."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rtbench import gen, harness, weights

DATA = Path(__file__).resolve().parent / "data"
S, STEPS = 32, 8


@pytest.mark.parametrize("tied", [False, True])
def test_paged_prefill_then_decode_match_the_reference(tied):
    from repro.kvcache import BlockAllocator
    from repro.kvcache.paged import PagedKVCache
    from repro.prefill import build_packed_arrays
    from repro.serving import generate

    config = json.loads((DATA / "tiny-gqa.json").read_text())
    config["tie_word_embeddings"] = tied
    cfg = harness.model_config(config)
    ref = harness.reference(config["reference"])
    params = weights.make(cfg, 2**31 + 3, ref.SCALES, ref.EMBED_SCALE)
    ids = gen.hash_ids("what do you think about pollution in rural areas? "
                       "please give reasons and implications.",
                       cfg.vocab_size, S)
    kvc = PagedKVCache(cfg, 1, 16, 16, S + STEPS + 8)
    kvc.set_table(0, BlockAllocator(16, 16).allocate_n(0, 3))
    ragged = generate.make_ragged_prefill_fn(cfg, use_pallas=False)
    cache = kvc.state
    for start in (0, 16):                  # two chunks, two launches
        toks, tc, meta, tabs = build_packed_arrays(
            (16, 1, 16), [(0, start, ids[start:start + 16], kvc.tables[0])],
            pad_slot=1, table_width=kvc.max_blocks_per_seq,
            trash_block=kvc.trash_block)
        cache, last = ragged(params, cache, {"tokens": jnp.asarray(toks)},
                             jnp.asarray(tc), jnp.asarray(meta),
                             jnp.asarray(tabs), chunk_pad=16)
    rows = [np.asarray(last[0])]
    served = [int(np.argmax(rows[0]))]
    decode = generate.make_paged_decode_fn(cfg, use_pallas=False)
    for _ in range(STEPS):
        tok, lg, cache = decode(params, cache,
                                jnp.asarray([[served[-1]]], jnp.int32),
                                kvc.tables_device())
        rows.append(np.asarray(lg[0]))
        served.append(int(tok[0, 0]))
    program = np.stack(rows)

    seq = np.concatenate([ids, np.asarray(served[:-1], np.int32)])
    want = np.asarray(ref.logits(params, jnp.asarray(seq),
                                 spec=ref.Spec.from_config(config),
                                 first=S - 1, rows=STEPS + 1))
    # the program computes in bfloat16 (8 significant bits): each of a
    # few dozen roundings on the way moves a logit by up to 2^-8 of the
    # values it is made of, so allow 2^-5 of the largest logit.  A wrong
    # mask, page, position or norm moves logits by their own size.
    scale = np.abs(want).max()
    assert np.abs(program - want).max() <= 2.0 ** -5 * scale
    # and the reference is not trivially close: another prompt differs
    other = np.asarray(ref.logits(params, jnp.asarray(np.roll(seq, 1)),
                                  spec=ref.Spec.from_config(config),
                                  first=S - 1, rows=STEPS + 1))
    assert np.abs(other - want).max() > 2.0 ** -3 * scale


def test_reference_refuses_what_it_does_not_compute():
    config = json.loads((DATA / "tiny-gqa.json").read_text())
    ref = harness.reference(config["reference"])
    for key, value in (("use_bias", True), ("sliding_window", 4096),
                       ("norm_type", "layer_norm"),
                       ("partial_rotary_factor", 0.5)):
        with pytest.raises(ValueError):
            ref.Spec.from_config(dict(config, **{key: value}))
        with pytest.raises(ValueError):
            harness.model_config(dict(config, **{key: value}))


def test_weights_follow_the_seed():
    config = json.loads((DATA / "tiny-gqa.json").read_text())
    cfg = harness.model_config(config)
    ref = harness.reference(config["reference"])
    a = weights.make(cfg, 2**33 + 1, ref.SCALES, ref.EMBED_SCALE)
    b = weights.make(cfg, 2**33 + 1, ref.SCALES, ref.EMBED_SCALE)
    c = weights.make(cfg, 1, ref.SCALES, ref.EMBED_SCALE)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["stack"]["scan0"]["attn"]["wq"]
                     == c["stack"]["scan0"]["attn"]["wq"]).all())
    assert jax.tree.structure(a) == jax.tree.structure(weights.shapes(cfg))


# The translation field for field, as the benchmark has made it since the
# first cell ran: every field the mapping sets; the others keep
# ModelConfig's defaults.
TRANSLATED = {
    "starcoder2-3b": dict(
        name="starcoder2-3b", family="dense", num_layers=30, d_model=3072,
        vocab_size=49152, num_heads=24, num_kv_heads=2, head_dim=128,
        d_ff=12288, mlp_act="gelu", rope_theta=999999.4420358813,
        norm_eps=1e-05, tie_embeddings=True,
        source="https://huggingface.co/bigcode/starcoder2-3b"),
    "tiny-gqa": dict(
        name="tiny-gqa", family="dense", num_layers=2, d_model=256,
        vocab_size=2048, num_heads=8, num_kv_heads=2, head_dim=32,
        d_ff=512, mlp_act="gelu", rope_theta=100000.0, norm_eps=1e-06,
        tie_embeddings=False,
        source="a two-layer stand-in at test size, the StarCoder2 file's "
               "conventions with an untied head"),
}
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("path", [CONFIGS / "starcoder2-3b.json",
                                  DATA / "tiny-gqa.json"],
                         ids=lambda p: p.stem)
def test_model_config_field_for_field(path):
    from repro.configs import ModelConfig
    config = json.loads(path.read_text())
    got = harness.model_config(config)
    assert got == ModelConfig(**TRANSLATED[config["name"]])


def test_the_program_translates_where_it_has_a_translation(monkeypatch):
    """Once the program has ``repro.configs.from_published``, its
    translation and its refusals are the ones a run gets."""
    from repro import configs
    config = json.loads((DATA / "tiny-gqa.json").read_text())

    def from_published(c):
        if c.get("use_bias"):
            raise ValueError("the program has no biases")
        return ("program", c["name"])

    monkeypatch.setattr(configs, "from_published", from_published,
                        raising=False)
    assert harness.model_config(config) == ("program", "tiny-gqa")
    with pytest.raises(ValueError, match="the program has no biases"):
        harness.model_config(dict(config, use_bias=True))


# the table and the embedding's scale as weights.py held them before the
# reference named its own
OLD_SCALES = {"embedding": None, "lm_head": 0.02, "wq": 0.04, "wk": 0.04,
              "wv": 0.02, "wo": 0.02, "w_up": 0.02, "w_down": 0.02,
              "ln1": 0.1, "ln2": 0.1, "final_ln": 0.1}
OLD_EMBED_SCALE = 0.25


def _old_draw(cfg, seed):
    """The draw as weights.make made it from its own constants."""
    tree = weights.shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]

    @jax.jit
    def draw(key):
        leaves = []
        for i, (path, leaf) in enumerate(paths):
            scale = OLD_SCALES[path[-1].key]
            if scale is None:
                scale = OLD_EMBED_SCALE * cfg.d_model ** -0.5
            x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            leaves.append((scale * x).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(tree), leaves)

    return draw(weights.key_for(seed))


@pytest.mark.parametrize("tied", [False, True])
def test_dense_gqa_table_draws_the_old_weights(tied):
    config = dict(json.loads((DATA / "tiny-gqa.json").read_text()),
                  tie_word_embeddings=tied)
    cfg = harness.model_config(config)
    ref = harness.reference("dense_gqa")
    assert (ref.SCALES, ref.EMBED_SCALE) == (OLD_SCALES, OLD_EMBED_SCALE)
    new = weights.make(cfg, 2**33 + 7, ref.SCALES, ref.EMBED_SCALE)
    old = _old_draw(cfg, 2**33 + 7)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))


MOE_SCALES = dict(OLD_SCALES, router=0.02, w_gate=0.02)


def test_a_table_naming_the_moe_leaves_draws_the_moe_tree():
    from repro import configs
    cfg = configs.get_smoke_config("kimi-k2-1t-a32b")
    params = weights.make(cfg, 2**31 + 11, MOE_SCALES, OLD_EMBED_SCALE)
    want = weights.shapes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    moe = params["stack"]["scan0"]["moe"]
    assert moe["router"].dtype == jnp.float32
    assert float(jnp.std(moe["router"])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(moe["w_gate"].astype(jnp.float32))) == \
        pytest.approx(0.02, rel=0.1)


@pytest.mark.parametrize("missing", ["router", "w_gate", "embedding"])
def test_a_leaf_the_table_does_not_name_is_an_error(missing):
    from repro import configs
    cfg = configs.get_smoke_config("kimi-k2-1t-a32b")
    table = {k: v for k, v in MOE_SCALES.items() if k != missing}
    with pytest.raises(ValueError, match="unknown parameter"):
        weights.make(cfg, 1, table, OLD_EMBED_SCALE)


def test_the_embedding_needs_its_scale():
    cfg = harness.model_config(json.loads((DATA / "tiny-gqa.json")
                                          .read_text()))
    with pytest.raises(ValueError, match="embedding's scale"):
        weights.make(cfg, 1, OLD_SCALES)
