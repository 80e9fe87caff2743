"""Production mesh construction (TPU v5e target).

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — jax locks the device count on
first backend initialization, and only launch/dryrun.py is allowed to
set the 512-placeholder-device XLA flag before that happens.
"""

from __future__ import annotations

import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = math.prod(shape)
    have = len(jax.devices())
    if have < ndev:
        raise RuntimeError(
            f"mesh {shape} needs {ndev} devices, have {have} — run under "
            f"launch/dryrun.py (XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=512)")
    return jax.make_mesh(
        shape, axes, devices=jax.devices()[:ndev],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary (test-scale) mesh over the first prod(shape) devices."""
    ndev = math.prod(shape)
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=jax.devices()[:ndev],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def replica_groups(R: int, devices=None):
    """Device groups for R serving replicas (PR 9 multi-replica pool).

    With at least R devices the replicas get contiguous equal
    data-parallel slices (leftover devices stay unused — equal pools
    keep the replicas interchangeable for the router).  With fewer
    devices than replicas the groups wrap round-robin onto single
    devices: R engine instances time-sharing one host device, the CPU
    test case ``serving.replica.ReplicatedEngine`` models.
    """
    if R < 1:
        raise ValueError(f"R must be >= 1, got {R}")
    devs = list(devices) if devices is not None else list(jax.devices())
    if not devs:
        raise RuntimeError("no devices available for replica_groups")
    if len(devs) >= R:
        per = len(devs) // R
        return [devs[r * per:(r + 1) * per] for r in range(R)]
    return [[devs[r % len(devs)]] for r in range(R)]
