"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state. Single pod: (data=16, model=16) = 256 chips (v5e pod). Multi-pod adds
a leading "pod" axis (2 pods = 512 chips); the pod axis carries only
gradient/data-parallel traffic (DCN-class links), never TP.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (real or fake) local devices exist —
    used by tests and examples, never by the dry-run."""
    devs = jax.devices()[: n_data * n_model]
    arr = np.asarray(devs).reshape(n_data, n_model)
    return jax.sharding.Mesh(arr, ("data", "model"))
