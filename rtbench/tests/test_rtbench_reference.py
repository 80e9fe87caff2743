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
    params = weights.make(cfg, 2**31 + 3)
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

    ref = harness.reference(config["reference"])
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
    a = weights.make(cfg, 2**33 + 1)
    b = weights.make(cfg, 2**33 + 1)
    c = weights.make(cfg, 1)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["stack"]["scan0"]["attn"]["wq"]
                     == c["stack"]["scan0"]["attn"]["wq"]).all())
    assert jax.tree.structure(a) == jax.tree.structure(weights.shapes(cfg))
