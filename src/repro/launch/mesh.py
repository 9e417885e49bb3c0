"""Device meshes.

Every mesh in the repo is built by ``make_mesh`` so its axes are
``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` (``parallel/sharding.py``), which JAX accepts
only on Auto axes (``jax.make_mesh`` defaults to Explicit axes).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  Shapes:

  single-pod:  (16, 16)      axes (data, model)        — 256 chips (v5e pod)
  multi-pod:   (2, 16, 16)   axes (pod, data, model)   — 512 chips

The `model` axis stays intra-pod (ICI); `pod` carries only data-parallel
gradient all-reduce (+ optional FSDP, see ParallelConfig.fsdp_axes).

For CPU development the same mesh machinery runs against simulated host
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``):
``make_test_mesh`` is the 8-device integration-test shape, and
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


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for 8-host-device integration tests."""
    return make_mesh(shape, axes)
