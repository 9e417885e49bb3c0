"""Device meshes.

Every mesh in the repo is built by ``make_mesh`` so its axes are
``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` (``parallel/sharding.py``), which JAX accepts
only on Auto axes (``jax.make_mesh`` defaults to Explicit axes).

Axes are (``data``, ``model``), or (``pod``, ``data``, ``model``) across
pods: the `model` axis stays intra-pod (ICI); `pod` carries only
data-parallel gradient all-reduce (+ optional FSDP, see
ParallelConfig.fsdp_axes).

For CPU development the same mesh machinery runs against simulated host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
``launch/train.py --mesh DxM`` builds arbitrary (data, model) shapes for
the distributed Trainer (tests/test_trainer_distributed.py).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over ``axes`` with every axis Auto; ``devices``
    defaults to all visible devices."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )
