"""Shared layers: norms, rotary embeddings, MLPs, embeddings.

Every layer is a pair (``*_defs`` returning a P-tree, ``*_apply`` pure fn).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core.config import ModelConfig
from repro.core.module import P
from repro.kernels import ops
from repro.obs.profile import scoped
from repro.parallel.sharding import ShardingCtx


# --------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------- #
def norm_defs(cfg: ModelConfig, d: int) -> Dict[str, P]:
    defs = {"scale": P((d,), (None,), init="ones")}
    if cfg.norm_type == "layernorm":
        defs["bias"] = P((d,), (None,), init="zeros")
    return defs


@scoped("norm")
def norm_apply(
    cfg: ModelConfig, ctx: ShardingCtx, params: Dict[str, Any], x: jax.Array
) -> jax.Array:
    norm = ops.rmsnorm if cfg.norm_type == "rmsnorm" else ops.layernorm
    w = tuple(params[k] for k in ("scale", "bias") if k in params)
    # a Pallas kernel on a mesh runs per shard of batch rows
    xs = ctx.fit(x.shape, "batch")
    norm = ctx.per_shard(
        norm, (xs,) + (PartitionSpec(),) * len(w), xs,
        when=ops.is_pallas("auto"),
    )
    return norm(x, *w)


# --------------------------------------------------------------------- #
# rotary position embedding
# --------------------------------------------------------------------- #
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[None, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                       # (1, S, 1, half)
    else:
        ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                       # (B, S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# dense MLP
# --------------------------------------------------------------------- #
def mlp_defs(cfg: ModelConfig, d: int, d_ff: int) -> Dict[str, P]:
    gated = cfg.act in ("swiglu", "geglu")
    defs: Dict[str, P] = {
        "w_in": P((d, d_ff), ("fsdp", "tp"), fan_in=d),
        "w_out": P((d_ff, d), ("tp", "fsdp"), fan_in=d_ff),
    }
    if gated:
        defs["w_gate"] = P((d, d_ff), ("fsdp", "tp"), fan_in=d)
    if cfg.mlp_bias:
        defs["b_in"] = P((d_ff,), ("tp",), init="zeros")
        defs["b_out"] = P((d,), (None,), init="zeros")
    return defs


def _act(name: str, x: jax.Array) -> jax.Array:
    if name in ("swiglu",):
        return jax.nn.silu(x)
    if name in ("gelu", "geglu"):
        return jax.nn.gelu(x)
    return jax.nn.relu(x)


@scoped("ffn")
def mlp_apply(
    cfg: ModelConfig, ctx: ShardingCtx, params: Dict[str, Any], x: jax.Array
) -> jax.Array:
    cdt = x.dtype
    h = x @ params["w_in"].astype(cdt)
    if "b_in" in params:
        h = h + params["b_in"].astype(cdt)
    if "w_gate" in params:
        g = x @ params["w_gate"].astype(cdt)
        h = _act(cfg.act, g) * h
    else:
        h = _act(cfg.act, h)
    if ctx.context_parallel:
        h = ctx.cons(h, "batch", "seq_cp", None)
    else:
        h = ctx.cons(h, "batch", "seq", "tp")
    out = h @ params["w_out"].astype(cdt)
    if "b_out" in params:
        out = out + params["b_out"].astype(cdt)
    return out


# --------------------------------------------------------------------- #
# embeddings & lm head
# --------------------------------------------------------------------- #
def embedding_defs(cfg: ModelConfig) -> Dict[str, P]:
    defs = {
        "tok": P((cfg.padded_vocab, cfg.d_model), ("tp", "fsdp"), init="normal", scale=0.02)
    }
    if not cfg.use_rope and cfg.max_pos:
        defs["pos"] = P((cfg.max_pos, cfg.d_model), (None, "fsdp"), init="normal", scale=0.02)
    return defs


@scoped("embed")
def embed_apply(
    cfg: ModelConfig,
    ctx: ShardingCtx,
    params: Dict[str, Any],
    tokens: jax.Array,           # (B, S) int32
    positions: Optional[jax.Array] = None,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    x = jnp.take(params["tok"], tokens, axis=0).astype(compute_dtype)
    if "pos" in params:
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        pe = jnp.take(params["pos"], positions, axis=0).astype(compute_dtype)
        x = x + (pe if pe.ndim == 3 else pe[None])
    return x


def lm_head_defs(cfg: ModelConfig) -> Dict[str, P]:
    if cfg.tie_embeddings:
        return {}
    return {"w": P((cfg.d_model, cfg.padded_vocab), ("fsdp", "tp"), fan_in=cfg.d_model)}


def lm_head_weight(cfg: ModelConfig, params: Dict[str, Any], embed_params) -> jax.Array:
    if cfg.tie_embeddings:
        return embed_params["tok"].T
    return params["w"]
