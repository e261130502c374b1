"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so importing this
module never touches jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax import to
obtain placeholder devices; smoke tests and benchmarks see the real single device.

Topology (TPU v5e pods):
  * single-pod: (16, 16)    = ('data', 'model')          — 256 chips
  * multi-pod:  (2, 16, 16) = ('pod', 'data', 'model')   — 512 chips, 'pod' is the
    DCN-connected data-parallel axis; 'model' stays inside a pod (ICI-only), which is
    why the parameter shardings in models/sharding.py never touch 'pod'.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — the dry-run "
            "launcher must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def make_local_mesh(model_axis: int = 1) -> Mesh:
    """Whatever devices exist, as ('data', 'model') — for tests and CPU drivers."""
    devices = np.asarray(jax.devices())
    data_axis = len(devices) // model_axis
    return Mesh(devices[: data_axis * model_axis].reshape(data_axis, model_axis),
                ("data", "model"))
