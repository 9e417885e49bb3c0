"""Train step builders: loss + grad + clip + AdamW, with shardings.

``make_train_step`` builds the raw ``step_fn(state, batch)`` — including
microbatch gradient accumulation (``TrainConfig.accum_steps``) and the
mixed-precision policy (bf16 compute params cast once per step from the
fp32 master copy held in ``TrainState``; see ``core/precision.compute_view``).

``make_sharded_train_step`` is the distributed entry point: it consumes
``train_state_specs(model)`` / the model's ``ShardingCtx`` and returns
``jit(step_fn, in_shardings=…, out_shardings=…, donate_argnums=0)`` — the
same builder serves CPU unit tests (mesh=None), the 8-virtual-device CPU
mesh (``--xla_force_host_platform_device_count=8``) and the chip.
``training/loop.Trainer`` drives it end-to-end.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.config import TrainConfig
from repro.core.precision import compute_view
from repro.models.model import Model
from repro.optim import adamw
from repro.optim.schedule import lr_at


class TrainState:
    """Plain pytree: params + optimizer state."""

    def __init__(self, params, opt: adamw.AdamWState):
        self.params = params
        self.opt = opt

    def tree_flatten(self):
        return (self.params, self.opt), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def init_train_state(model: Model, key, tc: TrainConfig) -> TrainState:
    params = model.init(key)
    return TrainState(params, adamw.init_state(params))


def abstract_train_state(model: Model) -> TrainState:
    params = model.abstract_params()
    z = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    opt = adamw.AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=jax.tree.map(z, params),
        nu=jax.tree.map(z, params),
    )
    return TrainState(params, opt)


def train_state_specs(model: Model) -> TrainState:
    pspecs = model.param_specs()
    return TrainState(pspecs, adamw.state_specs(pspecs))


def state_shardings(model: Model) -> TrainState:
    """``train_state_specs`` mapped onto the model's mesh as NamedShardings
    (the checkpoint-restore / device_put / jit in_shardings currency)."""
    mesh = model.ctx.mesh
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), train_state_specs(model)
    )


def host_batch_sharding(model: Model) -> NamedSharding:
    """Pytree-prefix sharding for any host batch dict: the leading (batch)
    dim of every leaf lands on the mesh's data axes, the rest replicated."""
    return NamedSharding(
        model.ctx.mesh, PartitionSpec(model.ctx.rules.get("batch"))
    )


def _split_micro(batch: Dict[str, jax.Array], accum: int):
    """(B, …) -> (accum, B/accum, …) microbatch stack for lax.scan."""

    def sp(x):
        if x.shape[0] % accum:
            raise ValueError(
                f"global batch {x.shape[0]} not divisible by "
                f"accum_steps {accum}"
            )
        return x.reshape(accum, x.shape[0] // accum, *x.shape[1:])

    return jax.tree.map(sp, batch)


def make_train_step(model: Model, tc: TrainConfig):
    """Returns step_fn(state, batch) -> (state, metrics).

    * Mixed precision: the forward/backward runs on a compute-dtype view of
      the master params (``compute_view``); gradients land back in the
      master dtype and AdamW updates the fp32 copy.
    * Gradient accumulation: ``tc.accum_steps > 1`` scans microbatches with
      fp32 grad accumulators, weighting each microbatch gradient by its
      token count, so ``accum=N`` matches one N×-larger batch exactly for
      the masked-mean CE loss (MLM microbatches mask different token
      counts); the MoE aux term is token-weighted too, which coincides with
      the large-batch value when microbatch token counts are equal.
    """
    accum = max(int(tc.accum_steps), 1)
    policy = model.policy

    def loss_and_grads(params, mb):
        def loss_of(p):
            return model.loss_fn(compute_view(policy, p), mb)

        return jax.value_and_grad(loss_of, has_aux=True)(params)

    def step_fn(state: TrainState, batch: Dict[str, jax.Array]):
        params = state.params
        if accum == 1:
            (loss, metrics), grads = loss_and_grads(params, batch)
            metrics = dict(metrics)
            metrics["loss"] = loss
        else:
            micro = _split_micro(batch, accum)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            # token-weighted: loss/ce average over tokens; microbatch-mean:
            # aux/router stats are already per-layer-summed means per
            # microbatch, so they average over the accum steps
            moe = bool(model.cfg.num_experts)
            acc0 = {"loss": 0.0, "ce_loss": 0.0, "tokens": 0.0,
                    "aux_loss": 0.0}
            if moe:
                acc0.update(
                    router_entropy=0.0, router_drop_frac=0.0,
                    router_load=jnp.zeros((model.cfg.num_experts,)),
                )
            acc0 = jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), acc0)
            init = (zeros, acc0)

            def one(carry, mb):
                g_acc, acc = carry
                (loss, m), grads = loss_and_grads(params, mb)
                d = m["tokens"].astype(jnp.float32)
                g_acc = jax.tree.map(
                    lambda a, g: a + d * g.astype(jnp.float32), g_acc, grads
                )
                upd = {
                    "loss": acc["loss"] + d * loss,
                    "ce_loss": acc["ce_loss"] + d * m["ce_loss"],
                    "tokens": acc["tokens"] + d,
                    "aux_loss": acc["aux_loss"] + m["aux_loss"] / accum,
                }
                if moe:
                    for k in ("router_entropy", "router_drop_frac",
                              "router_load"):
                        upd[k] = acc[k] + m[k] / accum
                return (g_acc, upd), None

            (g_acc, acc), _ = jax.lax.scan(one, init, micro)
            d_acc = acc["tokens"]
            grads = jax.tree.map(
                lambda g, p: (g / d_acc).astype(p.dtype), g_acc, params
            )
            metrics = dict(
                acc, loss=acc["loss"] / d_acc, ce_loss=acc["ce_loss"] / d_acc
            )
        with jax.named_scope("optimizer"):
            grads, gnorm = adamw.clip_by_global_norm(grads, tc.grad_clip)
            # first update uses step 1 (warmup>0)
            lr = lr_at(tc, state.opt.step + 1)
            new_params, new_opt = adamw.apply_updates(
                params, grads, state.opt, lr, tc
            )
            # non-finite guard: a diverged/poisoned step (NaN/inf loss or
            # grad norm — the clip already rescaled by gnorm, so one bad
            # grad taints EVERY param) applies NO update.  Params and
            # AdamW moments keep their old values and opt.step does not
            # advance, so the lr schedule is unaffected; the host-side
            # Trainer counts consecutive skips and aborts past
            # TrainConfig.max_nonfinite_skips.
            ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(gnorm)
            sel = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new, old
            )
            params, opt = sel(new_params, params), sel(new_opt, state.opt)
        metrics.update(
            grad_norm=gnorm, lr=lr,
            skipped=(~ok).astype(jnp.float32),
        )
        return TrainState(params, opt), metrics

    return step_fn


def make_sharded_train_step(model: Model, tc: TrainConfig):
    """The distributed train step: ``make_train_step`` jitted against the
    model's mesh with state/batch in_shardings, state out_shardings and a
    donated input state.  Off-mesh (mesh=None or a 1-device mesh) it
    degrades to a plain donated jit, so the same builder runs everywhere.
    """
    step_fn = make_train_step(model, tc)
    mesh = model.ctx.mesh
    if mesh is None or mesh.empty or mesh.size == 1:
        return jax.jit(step_fn, donate_argnums=0)
    state_sh = state_shardings(model)
    batch_sh = host_batch_sharding(model)
    metrics_sh = NamedSharding(mesh, PartitionSpec())
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, metrics_sh),
        donate_argnums=0,
    )


def init_sharded_train_state(model: Model, key, tc: TrainConfig) -> TrainState:
    """Initialize the TrainState, then place it onto its mesh shardings.

    Init runs un-sharded on the default device so the draws are identical
    to the single-device reference regardless of mesh shape (legacy
    non-partitionable threefry changes values when the RNG computation is
    partitioned); ``device_put`` then scatters the leaves.  At true
    3B-on-256-chips scale, enable ``jax_threefry_partitionable`` and jit
    the init with ``out_shardings=state_shardings(model)`` instead so
    params materialize pre-sharded.
    """
    state = init_train_state(model, key, tc)
    mesh = model.ctx.mesh
    if mesh is None or mesh.empty or mesh.size == 1:
        return state
    return jax.device_put(state, state_shardings(model))
