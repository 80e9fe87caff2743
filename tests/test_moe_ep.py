"""Expert-parallel MoE (shard_map) vs the single-device oracle.

Needs a multi-device mesh, so it runs in a subprocess with 8 placeholder
CPU devices (the main pytest process keeps its single real device).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
from repro import configs
from repro.launch import mesh as mesh_lib
from repro.sharding import context as shctx, policy as policy_lib
from repro.models import moe

mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)

for arch in ("kimi-k2-1t-a32b", "mixtral-8x22b"):
    cfg = dataclasses.replace(configs.get_smoke_config(arch),
                              capacity_factor=8.0)
    params = moe.init_moe(key, cfg, jnp.float32)
    for B, S in ((4, 8), (1, 1)):
        x = jax.random.normal(jax.random.PRNGKey(B), (B, S, cfg.d_model))
        want, aux_want = moe.apply_moe_local(params, x, cfg)
        for serving in (False, True):
            policy = policy_lib.make_policy(mesh)
            policy.serving = serving
            with mesh, shctx.use_policy(policy):
                got, aux = jax.jit(
                    lambda p, x: moe.apply_moe(p, x, cfg))(params, x)
            err = float(jnp.abs(got - want).max())
            assert err < 2e-3, (arch, B, S, serving, err)
            da = abs(float(aux["moe_aux_loss"])
                     - float(aux_want["moe_aux_loss"]))
            assert da < 1e-4, (arch, B, S, serving, da)
print("EP_OK")
"""


@pytest.mark.parametrize("rep", [0])
def test_moe_ep_matches_oracle(rep, tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, timeout=480,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "EP_OK" in r.stdout


_DROPLESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
from repro import configs
from repro.launch import mesh as mesh_lib
from repro.sharding import context as shctx, policy as policy_lib
from repro.models import moe, transformer

mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))

for arch in ("deepseek-v2-lite", "kimi-k2-1t-a32b", "mixtral-8x22b"):
    cfg = configs.get_smoke_config(arch)
    tight = dataclasses.replace(cfg, capacity_factor=0.5)
    params = moe.init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    # every token's first choice is expert 1: a capacity cuts some of it
    params["router"] = params["router"].at[:8, 1].add(1.0)
    block = {"moe": params, "ln2": jnp.zeros((cfg.d_model,))}
    for B, S in ((4, 8), (2, 1)):
        x = jax.random.normal(jax.random.PRNGKey(B), (B, S, cfg.d_model))
        x = x.at[..., :8].set(1.0)
        valid = jnp.arange(B * S).reshape(B, S) % 3 != 0
        _, top_e = moe.route(params, x.reshape(B * S, -1), cfg)
        assert bool((top_e[:, 0] == 1).all()), arch
        want, _ = moe.apply_moe_dropless(params, x, cfg, valid)
        want_block, _ = transformer._moe_part(block, x, cfg, serving=True,
                                              valid=valid)
        scale = float(jnp.abs(want).max())
        for serving in (False, True):
            policy = policy_lib.make_policy(mesh)
            policy.serving = serving
            with mesh, shctx.use_policy(policy):
                got, aux = jax.jit(lambda p, x, v: moe.apply_moe_dropless(
                    p, x, cfg, v))(params, x, valid)
                got_block, _ = jax.jit(lambda b, x, v: transformer._moe_part(
                    b, x, cfg, serving=True, valid=v))(block, x, valid)
                _, cut = jax.jit(lambda p, x: moe.apply_moe(p, x, tight))(
                    params, x)
            err = float(jnp.abs(got - want).max())
            assert err < 1e-5 * scale, (arch, B, S, serving, err)
            err = float(jnp.abs(got_block - want_block).max())
            assert err < 1e-5 * scale, (arch, B, S, serving, err)
            assert float(aux["moe_drop_frac"]) == 0.0
            if B * S > 8:
                # the capacity dispatch that training keeps cuts copies
                assert float(cut["moe_drop_frac"]) > 0.0, (arch, serving)
print("DROPLESS_OK")
"""


def test_sharded_serving_drops_no_token():
    """A sharded serve's expert layer (both weight layouts) equals the
    one-device dropless layer on a batch whose every token picks one
    expert first, where the training dispatch's capacity drops copies;
    rows marked invalid are routed nowhere on the shards too."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _DROPLESS_SCRIPT],
                       capture_output=True, text=True, timeout=480,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DROPLESS_OK" in r.stdout
