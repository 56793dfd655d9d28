"""Production mesh construction (function, not constant — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax


def _mesh(dev_array, axes):
    return jax.sharding.Mesh(dev_array, axes,
                             axis_types=(jax.sharding.AxisType.Auto,)
                             * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16,16) ("data","model") = 256 chips.
    Multi-pod: (2,16,16) ("pod","data","model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)}; the dry-run "
            "sets XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (launch/dryrun.py)")
    import numpy as np
    dev_array = np.asarray(devs[:n]).reshape(shape)
    return _mesh(dev_array, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (subprocess sets device count)."""
    import numpy as np
    n = int(np.prod(shape))
    dev_array = np.asarray(jax.devices()[:n]).reshape(shape)
    return _mesh(dev_array, axes)
