"""GQA attention with context-parallel / head-TP activation sharding,
KV caching (prefill + decode), sliding window, and optional cross-attention.

Cache layout: {"k": (B, T, Hkv, D), "v": (B, T, Hkv, D)} with the sequence
dim logically ``cache_seq`` (sharded over `model` — decode then lowers to
flash-decoding-style partial-stat all-reduces, see ops.decode_attention).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core.config import ModelConfig
from repro.core.module import P
from repro.kernels import ops
from repro.obs.profile import scoped
from repro.parallel.sharding import ShardingCtx


def attention_defs(cfg: ModelConfig, cross: bool = False) -> Dict[str, P]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    defs: Dict[str, P] = {
        "wq": P((d, nq * hd), ("fsdp", "tp"), fan_in=d),
        "wk": P((d, nkv * hd), ("fsdp", "tp"), fan_in=d),
        "wv": P((d, nkv * hd), ("fsdp", "tp"), fan_in=d),
        "wo": P((nq * hd, d), ("tp", "fsdp"), fan_in=nq * hd),
    }
    if cfg.qkv_bias:
        defs["bq"] = P((nq * hd,), ("tp",), init="zeros")
        defs["bk"] = P((nkv * hd,), ("tp",), init="zeros")
        defs["bv"] = P((nkv * hd,), ("tp",), init="zeros")
    if cfg.attn_out_bias:
        defs["bo"] = P((d,), (None,), init="zeros")
    return defs


def _project_qkv(cfg, params, x, kv_src=None):
    """Returns q (B,S,H,D), k, v (B,T,Hkv,D)."""
    cdt = x.dtype
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    src = x if kv_src is None else kv_src
    T = src.shape[1]
    q = x @ params["wq"].astype(cdt)
    k = src @ params["wk"].astype(cdt)
    v = src @ params["wv"].astype(cdt)
    if "bq" in params:
        q = q + params["bq"].astype(cdt)
        k = k + params["bk"].astype(cdt)
        v = v + params["bv"].astype(cdt)
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, T, cfg.num_kv_heads, hd)
    v = v.reshape(B, T, cfg.num_kv_heads, hd)
    return q, k, v


def _out_proj(cfg, ctx: ShardingCtx, params, o: jax.Array) -> jax.Array:
    B, S = o.shape[:2]
    cdt = o.dtype
    o = o.reshape(B, S, cfg.num_heads * cfg.resolved_head_dim)
    out = o @ params["wo"].astype(cdt)
    if "bo" in params:
        out = out + params["bo"].astype(cdt)
    return out


@scoped("attention")
def attention_apply(
    cfg: ModelConfig,
    ctx: ShardingCtx,
    params: Dict[str, Any],
    x: jax.Array,                       # (B, S, d_model)
    *,
    positions: Optional[jax.Array] = None,
    mode: str = "train",                # train | prefill | decode
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,   # scalar int32 (decode write idx)
    causal: Optional[bool] = None,
    cross_kv: Optional[jax.Array] = None,    # encoder output for cross-attn
    window: Optional[int] = None,
    block_table: Optional[jax.Array] = None,  # (B, pages_per_seq) paged layout
    chunk_valid: Optional[jax.Array] = None,  # scalar: valid rows of a chunk
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, S, _ = x.shape
    causal = cfg.causal if causal is None else causal
    window = cfg.sliding_window if window is None else window
    is_cross = cross_kv is not None

    if mode == "decode" and (is_cross or (cache is not None and "len" in cache)):
        # cross-attention KV was precomputed at prefill time and lives in cache
        q, _, _ = _project_qkv(cfg, params, x, kv_src=x[:, :0])
        if cfg.use_rope:
            pass  # no rope on cross-attention
        k, v = cache["k"], cache["v"]
        lengths = cache["len"]
        o = ops.decode_attention(
            q, k, v, lengths, softcap=cfg.attn_logit_softcap, impl=cfg.kernel_impl
        )
        return _out_proj(cfg, ctx, params, o), cache

    q, k, v = _project_qkv(cfg, params, x, kv_src=cross_kv)

    if cfg.use_rope and not is_cross:
        if positions is None:
            if mode == "decode":
                if jnp.ndim(cache_pos) == 0:
                    positions = jnp.full((S,), cache_pos, jnp.int32)
                else:
                    positions = cache_pos[:, None].astype(jnp.int32)  # (B,1)
            else:
                positions = jnp.arange(S)
        # the RoPE kernel where q and k go on to the flash kernels, which
        # read the (B, S, H·D) rows it writes; decode and chunked prefill
        # feed the paged kernels and keep the XLA formula.  On a mesh the
        # kernel runs per shard: batch rows, heads over `model` where they
        # divide it
        impl = cfg.kernel_impl if mode in ("train", "prefill") else "xla"
        rot = functools.partial(ops.rope, theta=cfg.rope_theta, impl=impl)
        ps = ctx.fit(positions.shape, "batch") if positions.ndim == 2 else PartitionSpec()

        def rotate(x):
            xs = ctx.fit(x.shape, "batch", None, "tp", None)
            return ctx.per_shard(rot, (xs, ps), xs,
                                 when=ops.is_pallas(impl))(x, positions)

        q, k = rotate(q), rotate(k)

    if mode == "chunk":
        # chunked / suffix prefill over the paged layout (serving engine):
        # the chunk's S rows sit at logical positions cache_pos + [0, S);
        # rows >= chunk_valid are bucket padding (their K/V is routed to
        # the null page and their outputs are discarded by the caller).
        # Writes only ever touch pages the slot owns exclusively — the
        # engine privatizes shared prefix pages (COW) before chunking.
        assert cache is not None and "k_pool" in cache, \
            "mode='chunk' requires the paged cache layout"
        assert block_table is not None and jnp.ndim(cache_pos) == 0
        assert B == 1, "chunked prefill processes one slot at a time"
        page = cache["k_pool"].shape[1]
        n_tables = block_table.shape[1]
        pos = cache_pos + jnp.arange(S, dtype=jnp.int32)           # (S,)
        valid = jnp.arange(S) < chunk_valid
        page_idx = block_table[0, jnp.clip(pos // page, 0, n_tables - 1)]
        page_idx = jnp.where(valid, page_idx, 0)                   # null page
        k_pool, v_pool = ops.paged_kv_update_rows(
            cache["k_pool"], cache["v_pool"], k[0], v[0],
            page_idx, pos % page,
        )
        k_pool = ctx.cons(k_pool, None, None, "kv_tp", None)
        v_pool = ctx.cons(v_pool, None, None, "kv_tp", None)
        starts = jnp.full((B,), cache_pos, jnp.int32)
        lengths = jnp.full((B,), cache_pos + chunk_valid, jnp.int32)
        o = ops.paged_prefill_attention(
            q, k_pool, v_pool, block_table, starts, lengths,
            softcap=cfg.attn_logit_softcap, impl=cfg.kernel_impl,
        )
        new_cache = {"k_pool": k_pool, "v_pool": v_pool}
        return _out_proj(cfg, ctx, params, o), new_cache

    if mode == "decode" and cache is not None and "k_pool" in cache:
        # paged layout (serving engine): per-slot positions, block-table
        # indirection into the shared page pool.  The token insert is an
        # O(B·page) scatter (ops.paged_kv_update) — not the O(B·T) masked
        # select of the dense per-slot path below.
        assert block_table is not None and jnp.ndim(cache_pos) == 1
        page = cache["k_pool"].shape[1]
        capacity = block_table.shape[1] * page
        cp = jnp.minimum(cache_pos.astype(jnp.int32), capacity - 1)
        page_idx = jnp.take_along_axis(
            block_table, (cp // page)[:, None], axis=1
        )[:, 0]
        k_pool, v_pool = ops.paged_kv_update(
            cache["k_pool"], cache["v_pool"], k, v, page_idx, cp % page,
            impl=cfg.kernel_impl,
        )
        # pool sharding: KV heads over `model` (TP serving) — the page axis
        # stays local so block-table gathers never cross devices
        k_pool = ctx.cons(k_pool, None, None, "kv_tp", None)
        v_pool = ctx.cons(v_pool, None, None, "kv_tp", None)
        lengths = jnp.minimum(cache_pos + 1, jnp.int32(capacity))
        o = ops.paged_decode_attention(
            q, k_pool, v_pool, block_table, lengths,
            softcap=cfg.attn_logit_softcap, impl=cfg.kernel_impl,
        )
        new_cache = {"k_pool": k_pool, "v_pool": v_pool}
        return _out_proj(cfg, ctx, params, o), new_cache

    if mode == "decode":
        assert cache is not None and cache_pos is not None
        # window caches are rolling: write at cache_pos % T
        T = cache["k"].shape[1]
        rolling = bool(window) and window <= T
        if jnp.ndim(cache_pos) == 0:
            widx = cache_pos % T if rolling else cache_pos
            k_cache = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, widx, 0, 0)
            )
            v_cache = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, widx, 0, 0)
            )
            lengths = jnp.minimum(
                jnp.full((B,), cache_pos + 1, jnp.int32), jnp.int32(T)
            )
        else:
            # per-slot positions (continuous-batching engine): masked write.
            # O(B·T) traffic — fine at serving batch sizes; a paged cache /
            # Pallas scatter is the production path (see serving/engine.py).
            widx = (cache_pos % T) if rolling else cache_pos     # (B,)
            onehot = (
                jnp.arange(T)[None, :] == widx[:, None]
            )[..., None, None]                                    # (B,T,1,1)
            k_cache = jnp.where(onehot, k.astype(cache["k"].dtype), cache["k"])
            v_cache = jnp.where(onehot, v.astype(cache["v"].dtype), cache["v"])
            lengths = jnp.minimum(cache_pos + 1, jnp.int32(T))
        k_cache = ctx.cons(k_cache, "cache_batch", "cache_seq")
        v_cache = ctx.cons(v_cache, "cache_batch", "cache_seq")
        o = ops.decode_attention(
            q, k_cache, v_cache, lengths, softcap=cfg.attn_logit_softcap,
            impl=cfg.kernel_impl,
        )
        new_cache = {"k": k_cache, "v": v_cache}
        return _out_proj(cfg, ctx, params, o), new_cache

    # train / prefill: blockwise attention over the full (or encoder) sequence
    if ctx.context_parallel and not is_cross:
        q = ctx.cons(q, "batch", "seq_cp")
        # GQA KV is small: gather it fully (llama3-style CP)
        k = ctx.cons(k, "batch", None)
        v = ctx.cons(v, "batch", None)
    # train / prefill hot path: cfg.kernel_impl="auto" hits the fused Pallas
    # kernels (fwd + custom-VJP bwd) on TPU, the blockwise xla path elsewhere
    attn = functools.partial(
        ops.attention,
        causal=causal and not is_cross,
        window=window,
        softcap=cfg.attn_logit_softcap,
        impl=cfg.kernel_impl,
    )
    # on a mesh a Pallas kernel runs per shard: batch rows over the batch
    # axes, heads over `model` when both q and kv heads divide it
    qs = ctx.fit(q.shape, "batch", None, "tp", None)
    ks = ctx.fit(k.shape, "batch", None, "tp", None)
    if qs[2:3] != ks[2:3]:
        qs = ctx.fit(q.shape, "batch")
        ks = ctx.fit(k.shape, "batch")
    attn = ctx.per_shard(attn, (qs, ks, ks), qs,
                         when=ops.is_pallas(cfg.kernel_impl))
    o = attn(q, k, v)
    out = _out_proj(cfg, ctx, params, o)

    new_cache = None
    if mode == "prefill" and not is_cross:
        new_cache = {"k": k, "v": v}
    return out, new_cache


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> Dict[str, jax.Array]:
    hd = cfg.resolved_head_dim
    T = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    return {
        "k": jnp.zeros((batch, T, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, T, cfg.num_kv_heads, hd), dtype),
    }


def init_paged_cache(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16
) -> Dict[str, jax.Array]:
    """Shared K/V page pool for one layer (block table lives with the
    engine cache top-level — it is identical across layers)."""
    if cfg.sliding_window:
        raise ValueError(
            "cache_layout='paged' does not support sliding-window (rolling) "
            "caches — use the dense layout"
        )
    hd = cfg.resolved_head_dim
    return {
        "k_pool": jnp.zeros((num_pages, page_size, cfg.num_kv_heads, hd), dtype),
        "v_pool": jnp.zeros((num_pages, page_size, cfg.num_kv_heads, hd), dtype),
    }
