"""Transformer stacks: decoder (dense/MoE/SSM/hybrid), encoder, enc-dec.

Layers are grouped into scan *units* so heterogeneous interleaves stay
scannable: unit size = attn_layer_period for hybrids (jamba: 1 attn + 7
mamba), moe_layer_period for MoE (llama4-maverick: dense/MoE alternation),
1 for plain dense.  Unit params are stacked over units and the stack runs
as one ``lax.scan`` (keeps HLO size O(unit), essential for 126-layer
llama3-405b lowering), with per-unit remat.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.core.module import P, stack_tree
from repro.models import layers as L
from repro.models.attention import attention_apply, attention_defs
from repro.models.moe import aux_shape, moe_apply, moe_defs
from repro.models.ssm import init_ssm_cache, ssm_apply, ssm_defs
from repro.parallel.sharding import ShardingCtx


# --------------------------------------------------------------------- #
# scan-unit structure
# --------------------------------------------------------------------- #
def unit_size(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.attn_layer_period
    if cfg.num_experts and cfg.moe_layer_period > 1:
        return cfg.moe_layer_period
    return 1


def num_units(cfg: ModelConfig) -> int:
    u = unit_size(cfg)
    assert cfg.num_layers % u == 0, (cfg.num_layers, u)
    return cfg.num_layers // u


def num_moe_layers(cfg: ModelConfig) -> int:
    """Total MoE layers in the stack (normalizes summed aux statistics)."""
    if not cfg.num_experts:
        return 0
    u = unit_size(cfg)
    return sum(1 for i in range(u) if cfg.is_moe_layer(i)) * num_units(cfg)


def _sublayer_defs(cfg: ModelConfig, li: int, cross: bool) -> Dict[str, Any]:
    """Param defs for global layer index `li` (within a unit)."""
    d = cfg.d_model
    defs: Dict[str, Any] = {"norm1": L.norm_defs(cfg, d)}
    if cfg.is_attn_layer(li):
        defs["attn"] = attention_defs(cfg)
    else:
        defs["ssm"] = ssm_defs(cfg)
    if cross:
        defs["norm_x"] = L.norm_defs(cfg, d)
        defs["xattn"] = attention_defs(cfg, cross=True)
    if cfg.d_ff > 0:
        if not cfg.parallel_residual:
            defs["norm2"] = L.norm_defs(cfg, d)
        if cfg.is_moe_layer(li):
            defs["ffn"] = moe_defs(cfg)
        else:
            defs["ffn"] = L.mlp_defs(cfg, d, cfg.d_ff)
    return defs


def unit_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    u = unit_size(cfg)
    return {f"sub{i}": _sublayer_defs(cfg, i, cross) for i in range(u)}


def stack_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    return stack_tree(unit_defs(cfg, cross), num_units(cfg))


# --------------------------------------------------------------------- #
# sub-layer application
# --------------------------------------------------------------------- #
def _apply_sublayer(
    cfg: ModelConfig,
    ctx: ShardingCtx,
    li: int,
    params: Dict[str, Any],
    x: jax.Array,
    *,
    mode: str,
    positions,
    cache,
    cache_pos,
    cross_kv,
    causal: Optional[bool] = None,
    block_table=None,
    chunk_valid=None,
) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (x, new_cache, aux) — aux is the fixed-shape router stats
    vector for MoE models (``moe.aux_shape``), a scalar zero for dense."""
    aux = jnp.zeros(aux_shape(cfg), jnp.float32)
    new_cache: Dict[str, Any] = {}
    h = L.norm_apply(cfg, ctx, params["norm1"], x)
    is_attn = cfg.is_attn_layer(li)
    if is_attn:
        mix, c = attention_apply(
            cfg, ctx, params["attn"], h,
            positions=positions, mode=mode,
            cache=cache.get("attn") if cache else None,
            cache_pos=cache_pos, causal=causal, block_table=block_table,
            chunk_valid=chunk_valid,
        )
        if c is not None:
            new_cache["attn"] = c
    else:
        if mode == "chunk":
            raise ValueError(
                "chunked prefill requires an attention-only stack (SSM "
                "state cannot be advanced per-chunk with bucket padding)"
            )
        mix, c = ssm_apply(
            cfg, ctx, params["ssm"], h, mode=mode,
            cache=cache.get("ssm") if cache else None,
        )
        if c is not None:
            new_cache["ssm"] = c

    if cfg.parallel_residual and "ffn" in params:
        ff = (
            moe_apply(cfg, ctx, params["ffn"], h)
            if cfg.is_moe_layer(li)
            else (L.mlp_apply(cfg, ctx, params["ffn"], h), None)
        )
        if isinstance(ff, tuple) and ff[1] is not None:
            ff_out, aux = ff
        else:
            ff_out = ff[0] if isinstance(ff, tuple) else ff
        x = x + mix + ff_out
        return x, new_cache, aux

    x = x + mix

    if cross_kv is not None or (cache and "xattn" in cache):
        hx = L.norm_apply(cfg, ctx, params["norm_x"], x)
        xmix, _ = attention_apply(
            cfg, ctx, params["xattn"], hx,
            mode=mode, cross_kv=cross_kv,
            cache=cache.get("xattn") if cache else None,
        )
        x = x + xmix
        if mode == "prefill" and cross_kv is not None:
            # cross KV is static during decode: compute & store once
            from repro.models.attention import _project_qkv

            _, ck, cv = _project_qkv(cfg, params["xattn"], hx, kv_src=cross_kv)
            new_cache["xattn"] = {
                "k": ck, "v": cv,
                "len": jnp.full((x.shape[0],), cross_kv.shape[1], jnp.int32),
            }

    if "ffn" in params:
        h2 = L.norm_apply(cfg, ctx, params["norm2"], x)
        if cfg.is_moe_layer(li):
            ff_out, aux = moe_apply(cfg, ctx, params["ffn"], h2)
        else:
            ff_out = L.mlp_apply(cfg, ctx, params["ffn"], h2)
        x = x + ff_out
    return x, new_cache, aux


# --------------------------------------------------------------------- #
# stacks
# --------------------------------------------------------------------- #
def decoder_stack(
    cfg: ModelConfig,
    ctx: ShardingCtx,
    stacked_params: Dict[str, Any],
    x: jax.Array,
    *,
    mode: str = "train",
    positions=None,
    caches=None,              # stacked cache pytree (prefill out / decode in-out)
    cache_pos=None,
    cross_kv=None,
    causal: Optional[bool] = None,
    block_table=None,         # (B, pages_per_seq): paged decode (all layers)
    chunk_valid=None,         # scalar: valid rows of a prefill chunk
) -> Tuple[jax.Array, Any, jax.Array]:
    """Runs the full layer stack.  Returns (x, new_caches, aux_loss_sum)."""
    u = unit_size(cfg)

    def unit_body(carry, xs):
        x, aux_sum = carry
        uparams, ucache = xs
        new_ucache = {}
        for i in range(u):
            sub = f"sub{i}"
            x, nc, aux = _apply_sublayer(
                cfg, ctx, i, uparams[sub], x,
                mode=mode, positions=positions,
                cache=ucache.get(sub) if ucache else None,
                cache_pos=cache_pos, cross_kv=cross_kv, causal=causal,
                block_table=block_table, chunk_valid=chunk_valid,
            )
            aux_sum = aux_sum + aux
            if nc:
                new_ucache[sub] = nc
        if ctx.context_parallel and mode not in ("decode", "chunk"):
            x = ctx.cons(x, "batch", "seq_cp", None)
        else:
            x = ctx.cons(x, "batch", None, None)
        return (x, aux_sum), new_ucache

    body = unit_body
    if mode == "train":
        # recompute each unit in the backward: only unit boundaries are saved
        body = jax.checkpoint(unit_body)

    aux0 = jnp.zeros(aux_shape(cfg), jnp.float32)

    if caches is None:
        (x, aux_sum), new_caches = jax.lax.scan(
            lambda c, p: body(c, (p, None)), (x, aux0), stacked_params
        )
    else:
        (x, aux_sum), new_caches = jax.lax.scan(
            body, (x, aux0), (stacked_params, caches)
        )
    if mode == "train":
        new_caches = None
    return x, new_caches, aux_sum


def init_stack_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
    cross_len: int = 0, *, layout: str = "dense", page_size: int = 0,
    num_pages: int = 0,
):
    """Preallocated decode cache, stacked over scan units.

    ``layout="paged"`` replaces each attention layer's dense per-slot
    ``(B, T, Hkv, D)`` buffers with a shared ``(num_pages, page, Hkv, D)``
    pool; SSM and cross-attention state stay dense per-slot.
    """
    from repro.models.attention import init_cache as init_attn_cache
    from repro.models.attention import init_paged_cache

    u = unit_size(cfg)
    unit = {}
    for i in range(u):
        sub: Dict[str, Any] = {}
        if cfg.is_attn_layer(i):
            if layout == "paged":
                sub["attn"] = init_paged_cache(cfg, num_pages, page_size, dtype)
            else:
                sub["attn"] = init_attn_cache(cfg, batch, max_len, dtype)
        else:
            sub["ssm"] = init_ssm_cache(cfg, batch, dtype)
        if cfg.is_encoder_decoder and cross_len:
            hd = cfg.resolved_head_dim
            sub["xattn"] = {
                "k": jnp.zeros((batch, cross_len, cfg.num_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, cross_len, cfg.num_kv_heads, hd), dtype),
                "len": jnp.full((batch,), cross_len, jnp.int32),
            }
        unit[f"sub{i}"] = sub
    n = num_units(cfg)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n, *x.shape)), unit)
