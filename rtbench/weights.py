"""Random weights from the seed, made by the benchmark on the device.

The benchmark makes the weights itself, so that the reference can use
them without taking anything the program made.  Their layout is the
program's parameter tree (``repro.models.model.init_params``, read as
shapes only); every leaf is drawn here, in one jitted call, in the
dtype it is served in, with the spread that the configuration's
reference names for it (``reference/<name>.py``: ``SCALES`` and
``EMBED_SCALE``).  A leaf whose name the reference's table does not hold
is an error: the reference could not know what it means.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


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


def make(cfg, seed: int, scales: dict,
         embed_scale: Optional[float] = None) -> dict:
    """All weights of ``cfg`` from ``seed``, on the default device.
    ``scales`` maps a leaf's name to the standard deviation of its normal
    draw; None scales by ``embed_scale / sqrt(d_model)``."""
    tree = shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, _ in paths:
        name = path[-1].key
        if name not in scales:
            raise ValueError(f"unknown parameter {jax.tree_util.keystr(path)}"
                             ": the reference cannot interpret it")
        if scales[name] is None and embed_scale is None:
            raise ValueError(f"{jax.tree_util.keystr(path)} is scaled by "
                             "the embedding's scale, and none is given")

    @jax.jit
    def draw(key):
        leaves = []
        for i, (path, leaf) in enumerate(paths):
            scale = scales[path[-1].key]
            if scale is None:
                scale = embed_scale * cfg.d_model ** -0.5
            x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            leaves.append((scale * x).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(tree), leaves)

    return draw(key_for(seed))
