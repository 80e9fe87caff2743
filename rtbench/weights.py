"""Random weights from the seed, made by the benchmark on the device.

The benchmark makes the weights itself, so that the reference can use
them without taking anything the program made.  Their layout is the
program's parameter tree (``repro.models.model.init_params``, read as
shapes only); every leaf is drawn here, in one jitted call, in the
dtype it is served in.  A leaf whose name this module does not know is
an error: the reference could not know what it means.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# leaf name -> standard deviation of its normal draw; None scales by
# EMBED_SCALE/sqrt(d_model) (the embedding, which the program multiplies
# by sqrt(d_model)).  Norm weights enter as (1 + w), so a small random w
# tests that convention too.
SCALES = {"embedding": None, "lm_head": 0.02, "wq": 0.04, "wk": 0.04,
          "wv": 0.02, "wo": 0.02, "w_up": 0.02, "w_down": 0.02,
          "ln1": 0.1, "ln2": 0.1, "final_ln": 0.1}

# With a tied head a token's own logit is about d_model * std / rms(x)
# standard deviations above the others.  At std 1/sqrt(d_model) and the
# residual's rms after 30 random layers (about 10 at d_model 3072) that
# is 5.4: the model repeats its input token forever and no logit is near
# a tie, so no precision could change a served token.  A quarter of that
# std puts the own logit at about 1.3 standard deviations.
EMBED_SCALE = 0.25
# Query and key weights are drawn at twice the others' std.  At 0.02 the
# attention of 30 random layers is flat enough that greedy decoding falls
# into repeating one token (98% of StarCoder2-3B's served tokens on a
# v5e), where no logit is near a tie.  At 0.04 about half to four fifths
# repeat, and near-ties are common enough that a float8 pass changes
# served tokens by logit gaps ten times those of the bfloat16 program.


def key_for(seed: int) -> jax.Array:
    """A key for any whole-number seed (more than 32 bits included), on
    the hardware generator: threefry takes seconds for 3B weights."""
    seed %= 1 << 64
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 32)


def shapes(cfg) -> dict:
    """The program's parameter tree as shapes."""
    from repro.models import model as model_lib
    return jax.eval_shape(
        functools.partial(model_lib.init_params, cfg=cfg),
        jax.random.PRNGKey(0))


def make(cfg, seed: int) -> dict:
    """All weights of ``cfg`` from ``seed``, on the default device."""
    tree = shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, _ in paths:
        name = path[-1].key
        if name not in SCALES:
            raise ValueError(f"unknown parameter {jax.tree_util.keystr(path)}"
                             ": the reference cannot interpret it")

    @jax.jit
    def draw(key):
        leaves = []
        for i, (path, leaf) in enumerate(paths):
            scale = SCALES[path[-1].key]
            if scale is None:
                scale = EMBED_SCALE * cfg.d_model ** -0.5
            x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            leaves.append((scale * x).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(tree), leaves)

    return draw(key_for(seed))
