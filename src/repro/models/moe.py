"""Mixture-of-Experts FFN — sort-by-expert ragged dispatch (megablocks-style).

Routing (always fp32 — see ``_route``) → stable sort of the ``T·K``
(token, choice) slots by expert → capacity truncation (dropped slots are
re-keyed past every real expert so the second stable sort pushes them
beyond ``sum(group_sizes)``, where the ragged kernel returns zeros and
spends no compute) → per-expert GEMMs through
``kernels/ops.grouped_matmul`` (ragged Pallas kernel with custom-VJP
backward on TPU; elsewhere the capacity-batched XLA GEMM selected by the
static ``max_group_size=C`` bound, whose cost is independent of E) →
unsort-and-combine scatter-add in fp32.  No dense ``(T, E)`` one-hot
dispatch/combine tensor ever materializes — the old einsum formulation
built ``(T, E, C)`` tensors on the hot path, quadratic-ish in tokens.

Expert parallelism: on a mesh whose ``experts`` axis divides E
(``ShardingCtx.expert_parallel``), the expert FFN instead scatters kept
slots into a static ``(E, C, d)`` buffer that is sharding-constrained
over the expert axis — GSPMD inserts the all-to-all token exchange at
the group boundary — and each shard runs its local experts' batched
GEMMs.  When experts don't divide the mesh axis the layer degrades to
the replicated ragged path (weight placement falls back to replication
via ``fit_spec``).  Both paths share routing/capacity/drop semantics, so
mesh runs are token/loss-comparable to single-device runs.

Capacity & drops: global capacity ``C = capacity(cfg, T)`` per layer
call; within an expert, slots keep their token order (stable sort), so
earlier tokens win capacity — dropped slots contribute nothing and the
residual stream passes their activations through unchanged.

Aux channel: ``moe_apply`` returns a fixed-shape fp32 vector
(``aux_shape(cfg)``) summed across layers by the transformer scan:
``[load-balance loss, entropy deficit, dropped slots, total slots,
per-expert kept-load fractions…]``.  Entries past the first two are
``stop_gradient``-ed statistics; ``models/model.py`` unpacks them into
router metrics and applies ``router_aux_coef`` / ``router_entropy_coef``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.core.module import P
from repro.kernels import ops
from repro.models.layers import _act, mlp_apply, mlp_defs
from repro.obs.profile import scoped
from repro.parallel.sharding import ShardingCtx

AUX_BASE = 4  # [lb_loss, entropy_deficit, dropped_slots, total_slots]


def aux_shape(cfg: ModelConfig) -> Tuple[int, ...]:
    """Shape of the per-layer aux vector carried through the layer scan.

    Dense models keep the legacy scalar; MoE models carry
    ``(AUX_BASE + E,)`` so per-expert load rides along."""
    return (AUX_BASE + cfg.num_experts,) if cfg.num_experts else ()


def moe_defs(cfg: ModelConfig) -> Dict[str, Any]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    gated = cfg.act in ("swiglu", "geglu")
    defs: Dict[str, Any] = {
        "router": P((d, e), (None, None), init="normal", scale=0.02),
        "w_in": P((e, d, f), ("experts", "fsdp", None), fan_in=d),
        "w_out": P((e, f, d), ("experts", None, "fsdp"), fan_in=f),
    }
    if gated:
        defs["w_gate"] = P((e, d, f), ("experts", "fsdp", None), fan_in=d)
    if cfg.n_shared_experts:
        defs["shared"] = mlp_defs(cfg, d, cfg.d_ff * cfg.n_shared_experts)
    return defs


def capacity(cfg: ModelConfig, tokens: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.num_experts_per_tok / cfg.num_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for layout friendliness


def _route(cfg: ModelConfig, params, x2d: jax.Array):
    """fp32 routing: logits, softmax and top-k all run in float32 even
    under the bf16 compute view — half-precision routing flips expert
    assignments between otherwise-equivalent runs (e.g. accum vs
    no-accum microbatching), which capacity truncation then amplifies
    into different outputs.  Returns (probs, renormalized top-k gates,
    expert indices), all fp32/int32."""
    logits = x2d.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E) fp32
    gate, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)  # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return probs, gate, idx


def _expert_ffn_ragged(cfg, params, xs, sizes, cap, cdt):
    """Per-expert FFN over sorted rows via the ragged grouped matmul.

    ``cap`` (the capacity) is the static per-group bound that lets the
    xla fallback use the E-independent capacity-batched GEMM."""
    gmm = functools.partial(
        ops.grouped_matmul, group_sizes=sizes, impl=cfg.kernel_impl,
        max_group_size=cap,
    )
    h = gmm(xs, params["w_in"].astype(cdt))
    if "w_gate" in params:
        h = _act(cfg.act, gmm(xs, params["w_gate"].astype(cdt))) * h
    else:
        h = _act(cfg.act, h)
    return gmm(h, params["w_out"].astype(cdt))


def _moe_ragged(cfg, params, xf, flat_e, keep, gates, C, cdt):
    """Sort-by-expert → ragged FFN → unsort-and-combine (single shard).

    Dropped slots are re-keyed to the virtual expert E, so the stable
    sort moves them past ``sum(sizes)`` — the kernel's zero tail — and
    they cost no expert FLOPs."""
    T, d = xf.shape
    M = flat_e.shape[0]
    K = cfg.num_experts_per_tok
    E = cfg.num_experts
    key = jnp.where(keep, flat_e, E)
    order = jnp.argsort(key)                        # stable: token order kept
    tok = order // K                                # source token per row
    xs = jnp.take(xf, tok, axis=0)                  # (M, d)
    sizes = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
    ys = _expert_ffn_ragged(cfg, params, xs, sizes, C, cdt)
    gs = jnp.take(gates, order)
    out = jnp.zeros((T, d), jnp.float32)
    return out.at[tok].add(ys.astype(jnp.float32) * gs[:, None])


def _moe_expert_parallel(cfg, ctx, params, xf, flat_e, rank, keep, gates,
                         C, cdt):
    """Expert-parallel FFN: scatter kept slots to a static (E, C, d)
    buffer constrained onto the expert axis (the all-to-all boundary),
    batched per-expert GEMMs local to each shard, gather-and-combine."""
    T, d = xf.shape
    M = flat_e.shape[0]
    K = cfg.num_experts_per_tok
    E = cfg.num_experts
    tok = jnp.arange(M, dtype=jnp.int32) // K
    e_idx = jnp.where(keep, flat_e, E)              # dropped → OOB, dropped
    c_idx = jnp.minimum(rank, C - 1)
    xe = jnp.zeros((E, C, d), cdt).at[e_idx, c_idx].set(
        jnp.take(xf, tok, axis=0), mode="drop"
    )
    xe = ctx.cons(xe, "experts", None, None)
    h = jnp.einsum("ecd,edf->ecf", xe, params["w_in"].astype(cdt))
    if "w_gate" in params:
        g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(cdt))
        h = _act(cfg.act, g) * h
    else:
        h = _act(cfg.act, h)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(cdt))
    ye = ctx.cons(ye, "experts", None, None)
    y_slot = ye[jnp.minimum(flat_e, E - 1), c_idx]  # (M, d)
    out = jnp.zeros((T, d), jnp.float32)
    return out.at[tok].add(y_slot.astype(jnp.float32) * gates[:, None])


@scoped("ffn")
def moe_apply(
    cfg: ModelConfig,
    ctx: ShardingCtx,
    params: Dict[str, Any],
    x: jax.Array,               # (B, S, d)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B,S,d), aux (AUX_BASE+E,) fp32 — see module doc)."""
    B, S, d = x.shape
    cdt = x.dtype
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    M = T * K
    C = capacity(cfg, T)
    xf = x.reshape(T, d)

    probs, gate, idx = _route(cfg, params, xf)

    # load-balance aux loss (Switch/GShard form) + router entropy deficit
    me = probs.mean(axis=0)                                        # (E,)
    ce = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32).mean(axis=0)
    lb = E * jnp.sum(me * ce)
    ent = -jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1).mean()
    ent_def = jnp.log(float(E)) - ent   # ≥ 0, minimized at uniform routing

    # capacity: rank of each slot within its expert (stable sort ⇒ token
    # order), slots at rank ≥ C are dropped
    flat_e = idx.reshape(M)                          # slot s = t·K + k
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    order0 = jnp.argsort(flat_e)
    rank_sorted = jnp.arange(M, dtype=jnp.int32) - starts[flat_e[order0]]
    keep_sorted = rank_sorted < C
    rank = jnp.zeros((M,), jnp.int32).at[order0].set(rank_sorted)
    keep = jnp.zeros((M,), bool).at[order0].set(keep_sorted)
    gates = gate.reshape(M) * keep.astype(jnp.float32)

    if ctx.expert_parallel(E):
        out2d = _moe_expert_parallel(
            cfg, ctx, params, xf, flat_e, rank, keep, gates, C, cdt
        )
    else:
        out2d = _moe_ragged(cfg, params, xf, flat_e, keep, gates, C, cdt)

    out = out2d.astype(cdt).reshape(B, S, d)
    if "shared" in params:
        out = out + mlp_apply(cfg, ctx, params["shared"], x)

    kept = jnp.minimum(counts, C).astype(jnp.float32)              # (E,)
    load = kept / jnp.maximum(kept.sum(), 1.0)
    dropped = jnp.float32(M) - kept.sum()
    stats = jax.lax.stop_gradient(
        jnp.concatenate([jnp.stack([dropped, jnp.float32(M)]), load])
    )
    aux = jnp.concatenate([jnp.stack([lb, ent_def]), stats])
    return out, aux.astype(jnp.float32)


def moe_ref_dense(cfg: ModelConfig, params: Dict[str, Any], x: jax.Array) -> jax.Array:
    """Oracle: route every token to its top-k experts with no capacity limit.

    Used by tests to bound the dispatch error introduced by capacity drops.
    """
    B, S, d = x.shape
    xt = x.reshape(-1, d).astype(jnp.float32)
    logits = xt @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    w_in = params["w_in"].astype(jnp.float32)
    w_out = params["w_out"].astype(jnp.float32)
    w_gate = params.get("w_gate")
    out = jnp.zeros_like(xt)
    for k in range(cfg.num_experts_per_tok):
        idx = expert_idx[:, k]
        wi = w_in[idx]                                   # (T, d, f)
        h = jnp.einsum("td,tdf->tf", xt, wi)
        if w_gate is not None:
            g = jnp.einsum("td,tdf->tf", xt, w_gate.astype(jnp.float32)[idx])
            h = _act(cfg.act, g) * h
        else:
            h = _act(cfg.act, h)
        y = jnp.einsum("tf,tfd->td", h, w_out[idx])
        out = out + gate_vals[:, k:k + 1] * y
    return out.reshape(B, S, d).astype(x.dtype)
