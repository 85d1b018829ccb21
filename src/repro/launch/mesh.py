"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; everyone else
sees the real single CPU device).

Mesh semantics (DESIGN.md §5):
  pod   — federated client axis (one pod = one hospital/client); SCBF's
          channel-masked gradient exchange is the ONLY cross-pod traffic
  data  — batch + FSDP weight sharding
  model — tensor/expert parallelism
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` with Auto axes.

    ``make_mesh`` defaults to Explicit axes, under which
    ``with_sharding_constraint`` and ``vmap(spmd_axis_name=...)`` cannot
    name the mesh axes; every mesh here is an Auto-sharded one.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax")
    return _auto_mesh(shape, axes, devices[:n])


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Single-device mesh for CPU smoke tests of the sharded code path."""
    n = int(np.prod(shape))
    return _auto_mesh(shape, axes, jax.devices()[:n])


def make_pod_mesh(num_pods: int):
    """1-D ``("pod",)`` mesh for federated cohort sharding.

    The pod axis is the federated client axis (DESIGN.md §5): the
    batched engine shards its bucketed ``(B, n_max, d)`` cohort over it,
    one group of participant slots per device, with weights replicated.
    On CPU, multiple pods come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before
    the first jax import (same contract as the dry-run).
    """
    devices = jax.devices()
    if len(devices) < num_pods:
        raise RuntimeError(
            f"need {num_pods} devices for a pod mesh, have {len(devices)} — "
            "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{num_pods} before importing jax")
    return _auto_mesh((num_pods,), ("pod",), devices[:num_pods])
