"""Logical→physical sharding rules.

The mesh has axes (``data``, ``model``) on one pod and (``pod``, ``data``,
``model``) across pods.  Models annotate params/activations with *logical*
axis names; this module maps them onto mesh axes per :class:`ParallelConfig`.

Consumers of these rules span both halves of the system:

  * training — ``training/train_step.make_sharded_train_step`` turns
    ``train_state_specs(model)`` (built from ``spec_tree`` over these
    rules) into the jit in/out shardings of the distributed train step,
    and ``training/loop.Trainer`` places host batches on the ``data``
    axes via ``host_batch_sharding``; parity with the single-device run
    is asserted in tests/test_trainer_distributed.py (8-virtual-device
    CPU mesh) and tests/test_parallel_numerics.py.
  * serving — ``serving/engine.Engine`` runs tensor-parallel inference
    end to end: ``Model.cache_specs`` (built from these rules) pins the
    in/out shardings of every per-step jit so the paged K/V pools shard
    over the head (``model``) axis while the host-side page allocator
    stays global — parity with the single-device engine is asserted in
    tests/test_serving_sharded.py on (1,8) and (2,4) CPU meshes.

Weight storage convention (uniform across archs — see DESIGN.md §5):
  * every large 2-D weight is stored (fsdp-dim, tp-dim) — combined FSDP+TP,
    ZeRO-3-like.  GSPMD inserts the all-gathers at use sites.
  * expert weights carry a leading `experts` dim on the `model` axis.
  * activations: batch over (pod?, data); in context-parallel attention the
    sequence dim is constrained to `model`.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.config import ParallelConfig


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def axis_rules(pc: ParallelConfig, mesh: Mesh) -> Dict[str, Any]:
    """Logical-name -> mesh-axis (or tuple) mapping."""
    names = mesh.axis_names
    has_pod = "pod" in names
    batch_axes: Tuple[str, ...] = ("pod", "data") if has_pod else ("data",)
    fsdp = tuple(a for a in pc.fsdp_axes if a in names)
    rules: Dict[str, Any] = {
        "batch": batch_axes,
        "seq": None,            # sequence replicated by default
        "seq_cp": "model",      # context-parallel sequence shard
        "embed": None,          # residual stream dim: replicated
        "fsdp": fsdp or None,
        "tp": "model",
        "experts": pc.expert_axis,
        "layers": None,
        "cache_seq": "model",
        "cache_batch": batch_axes,
        "vocab": "model",
        "kv_tp": "model",
        "stats": None,
        # flattened (batch*seq) token dim (loss computation)
        "tokens": (
            (*batch_axes, "model")
            if pc.attention_parallelism == "context"
            else batch_axes
        ),
    }
    if len(fsdp) == 1:
        rules["fsdp"] = fsdp[0]
    return rules


def spec(rules: Dict[str, Any], *logical: Optional[str]) -> PartitionSpec:
    phys = [rules.get(ax) if ax is not None else None for ax in logical]
    while phys and phys[-1] is None:
        phys.pop()
    return PartitionSpec(*phys)


def named(mesh: Mesh, pspec: PartitionSpec) -> NamedSharding:
    return NamedSharding(mesh, pspec)


def fit_spec(shape, mesh: Mesh, pspec: PartitionSpec) -> PartitionSpec:
    """Drop mesh axes that do not evenly divide their dimension.

    ``with_sharding_constraint`` tolerates uneven dims (XLA pads), but
    *placement* shardings — ``jax.device_put`` and jit ``in_shardings`` /
    ``out_shardings`` — require exact divisibility.  Callers building
    placement shardings for concrete buffers use this to degrade per-dim
    to replication instead of erroring (e.g. 3 serving slots on a data=2
    mesh axis keep the slot dim replicated while the KV heads of the same
    cache still shard over ``model``)."""
    sizes = mesh_axis_sizes(mesh)
    phys = []
    for dim, ax in zip(shape, tuple(pspec) + (None,) * len(shape)):
        if ax is None:
            phys.append(None)
            continue
        n = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            n *= sizes.get(a, 1)
        phys.append(ax if dim % n == 0 else None)
    while phys and phys[-1] is None:
        phys.pop()
    return PartitionSpec(*phys)


def constrain(x, mesh: Mesh, pspec: PartitionSpec):
    """with_sharding_constraint that is a no-op off-mesh (CPU unit tests)."""
    if mesh is None or mesh.empty or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))


class ShardingCtx:
    """Bundles mesh + rules; threaded through model apply fns.

    When ``mesh`` is None (pure single-device CPU tests) every constraint is
    a no-op, so the same model code runs everywhere.
    """

    def __init__(self, mesh: Optional[Mesh], pc: ParallelConfig):
        self.mesh = mesh
        self.pc = pc
        self.rules = axis_rules(pc, mesh) if mesh is not None else {}

    @property
    def context_parallel(self) -> bool:
        return self.pc.attention_parallelism == "context"

    def cons(self, x, *logical: Optional[str]):
        if self.mesh is None:
            return x
        return constrain(x, self.mesh, spec(self.rules, *logical))

    def sp(self, *logical: Optional[str]) -> PartitionSpec:
        if self.mesh is None:
            return PartitionSpec()
        return spec(self.rules, *logical)

    def fit(self, shape, *logical: Optional[str]) -> PartitionSpec:
        """``sp(*logical)`` with the axes that do not divide ``shape``
        dropped (``fit_spec``): a spec ``per_shard`` can split by."""
        if self.mesh is None:
            return PartitionSpec()
        return fit_spec(shape, self.mesh, spec(self.rules, *logical))

    def per_shard(self, fn, in_specs, out_specs, *, when: bool = True):
        """``fn`` run on each device's shard (``jax.shard_map``) on a real
        mesh when ``when``; ``fn`` itself otherwise.  Pallas (Mosaic)
        kernels need this: the compiler cannot partition them, so a kernel
        on a mesh sees its local block of batch rows / heads / tokens."""
        if not when or self.mesh is None or self.mesh.empty or self.mesh.size == 1:
            return fn
        # check_vma=False: a pallas_call's out_shape carries no
        # varying-axes annotation for the checker to use
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    # ------------------------------------------------------- expert axis
    def expert_axis_size(self) -> int:
        """Product of the mesh axes the logical ``experts`` dim maps to
        (1 off-mesh or when the rule is unmapped)."""
        if self.mesh is None:
            return 1
        ax = self.rules.get("experts")
        if ax is None:
            return 1
        sizes = mesh_axis_sizes(self.mesh)
        n = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            n *= sizes.get(a, 1)
        return n

    def expert_parallel(self, num_experts: int) -> bool:
        """True when the expert-parallel MoE path applies: a real mesh
        whose expert axis evenly divides the expert count.  Otherwise
        MoE degrades to the replicated ragged path (and ``fit_spec``
        degrades the expert-dim weight placement to replication)."""
        n = self.expert_axis_size()
        return n > 1 and num_experts % n == 0


def null_ctx() -> ShardingCtx:
    return ShardingCtx(None, ParallelConfig())
