"""Production mesh builders.

Defined as functions (not module constants) so importing never touches jax
device state. Single pod: 16x16 = 256 chips ("data", "model"); multi-pod:
2x16x16 = 512 chips ("pod", "data", "model") — DCN over "pod", ICI inside.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_serving_mesh(n_devices: int, model_parallel: int = 1):
    """("data", "model") mesh over the first ``n_devices`` devices.

    Unlike make_production_mesh's hard-coded pod shapes, this validates
    against the actual device count and raises actionable errors on small
    hosts (where a 16x16 mesh would fail opaquely inside jax). CPU
    multi-device testing: set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` BEFORE jax
    initializes.
    """
    if n_devices < 1 or model_parallel < 1:
        raise ValueError(
            f"need n_devices >= 1 and model_parallel >= 1, got "
            f"{n_devices} and {model_parallel}")
    if n_devices % model_parallel != 0:
        raise ValueError(
            f"n_devices={n_devices} not divisible by "
            f"model_parallel={model_parallel}; the serving mesh is "
            "(data, model) = (n_devices // model_parallel, model_parallel)")
    avail = len(jax.devices())
    if n_devices > avail:
        raise ValueError(
            f"mesh wants {n_devices} devices but only {avail} are "
            "visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            "before jax initializes")
    import numpy as np
    devs = np.asarray(jax.devices()[:n_devices]).reshape(
        n_devices // model_parallel, model_parallel)
    return jax.sharding.Mesh(devs, ("data", "model"))
