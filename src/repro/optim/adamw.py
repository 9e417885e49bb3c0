"""AdamW with sharded states (no optax dependency).

States inherit the parameter PartitionSpecs (ZeRO-3-like: fully sharded
optimizer).  ``state_dtype`` selects fp32 (faithful Megatron; the
trainer's choice) or bf16 moments (beyond-paper memory optimization that
halves the optimizer state).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import TrainConfig


class AdamWState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


def init_state(params: Any, state_dtype=jnp.float32) -> AdamWState:
    z = lambda p: jnp.zeros(p.shape, state_dtype)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree.map(z, params),
        nu=jax.tree.map(z, params),
    )


def state_specs(param_specs: Any) -> AdamWState:
    from jax.sharding import PartitionSpec

    return AdamWState(step=PartitionSpec(), mu=param_specs, nu=param_specs)


def apply_updates(
    params: Any,
    grads: Any,
    state: AdamWState,
    lr: jax.Array,
    tc: TrainConfig,
) -> Tuple[Any, AdamWState]:
    step = state.step + 1
    b1, b2 = tc.beta1, tc.beta2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, n):
        gf = g.astype(jnp.float32)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        n_new = b2 * n.astype(jnp.float32) + (1 - b2) * gf * gf
        mhat = m_new / c1
        nhat = n_new / c2
        delta = mhat / (jnp.sqrt(nhat) + tc.eps)
        if tc.weight_decay > 0 and p.ndim >= 2:  # no decay on norms/bias
            delta = delta + tc.weight_decay * p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - lr * delta
        return p_new.astype(p.dtype), m_new.astype(m.dtype), n_new.astype(n.dtype)

    out = jax.tree.map(upd, params, grads, state.mu, state.nu)
    params_new = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    mu_new = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    nu_new = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return params_new, AdamWState(step=step, mu=mu_new, nu=nu_new)


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


def clip_by_global_norm(grads: Any, max_norm: float) -> Tuple[Any, jax.Array]:
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), gn
