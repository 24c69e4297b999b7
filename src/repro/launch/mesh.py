"""Production mesh builders.

Functions, not module-level constants — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

Topology (task spec): one pod = 16x16 = 256 chips (TPU v5e-class, 2-D mesh
over ICI); the multi-pod config is 2 pods = 512 chips with the ``pod`` axis
crossing the (slower) inter-pod links — which is why default strategies keep
parameters replicated across pods and only the batch crosses the pod axis.

XLA flags recorded here for real-TPU runs (latency-hiding scheduler /
collective overlap); they are no-ops on the CPU dry-run:
  --xla_enable_async_collective_permute=true
  --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_overlap_compute_collective_tc=true
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    assert n % model_axis == 0, (n, model_axis)
    return _make_mesh((n // model_axis, model_axis), ("data", "model"))


def mesh_devices(mesh: Mesh) -> int:
    import numpy as np
    return int(np.prod(mesh.devices.shape))
