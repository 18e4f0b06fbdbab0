"""Production meshes (functions, never module-level constants — importing
this module must not initialise the jax backend).

Axes:
  * ``pod``   — hierarchical DP across pods (2 pods in the dry-run; scales
                to any pod count: gradient reduce-scatter intra-pod,
                all-reduce across pods);
  * ``data``  — within-pod data parallelism + FSDP/ZeRO param sharding;
  * ``model`` — tensor/expert parallelism (Megatron-style).

16x16 = 256 chips per pod (TPU v5e pod slice); 2x16x16 = 512 chips for the
multi-pod pass.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(*, model: int = 1):
    """Development/test mesh over whatever devices exist (CPU: 1 device
    unless the caller set --xla_force_host_platform_device_count)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


def dp_degree(mesh) -> int:
    """Total data-parallel replicas = product of non-'model' axes."""
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names if a != "model"]))


def batch_axes(mesh):
    """Mesh axes the global batch dim is sharded over."""
    axes = tuple(a for a in mesh.axis_names if a != "model")
    return axes if len(axes) > 1 else axes[0]
