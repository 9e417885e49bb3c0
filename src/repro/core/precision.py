"""Mixed-precision policy (BioNeMo/Megatron convention).

Parameters are stored in ``param_dtype`` (fp32 master by default), compute
runs in ``compute_dtype`` (bf16), and reductions/losses in fp32.  The policy
is a tiny pure object; models call ``policy.cast_compute`` on params entering
a matmul and ``policy.cast_output`` on residual-stream outputs.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


@dataclass(frozen=True)
class Policy:
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    reduce_dtype: str = "float32"

    @property
    def pdt(self):
        return _DTYPES[self.param_dtype]

    @property
    def cdt(self):
        return _DTYPES[self.compute_dtype]

    @property
    def rdt(self):
        return _DTYPES[self.reduce_dtype]

    def cast_compute(self, tree):
        import jax

        return jax.tree.map(
            lambda x: x.astype(self.cdt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            tree,
        )

    def cast_reduce(self, x):
        return x.astype(self.rdt)


def compute_view(policy: Policy, params):
    """Compute-dtype view of the master params (Megatron bf16 recipe).

    The trainer keeps the fp32 master copy in ``TrainState`` (the optimizer
    updates it in full precision) and casts the whole tree to the compute
    dtype ONCE per step before the forward pass; ``jax.grad`` through the
    cast accumulates gradients back in the master dtype.  No-op when the
    two dtypes coincide (CPU fp32 unit tests), so numerics are unchanged
    off the mixed-precision path.
    """
    if policy.pdt == policy.cdt:
        return params
    return policy.cast_compute(params)


def policy_for(model_cfg) -> Policy:
    return Policy(param_dtype=model_cfg.param_dtype, compute_dtype=model_cfg.dtype)
