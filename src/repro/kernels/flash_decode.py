"""Pallas TPU flash-decoding: single-token attention over a long KV cache,
split over cache blocks with running log-sum-exp combine.

This is the kernel behind the decode shapes (decode_32k, long_500k): one
query token per sequence against a 32k–512k cache.  The GPU original
(flash-decoding) splits the cache across thread blocks and combines with a
second kernel; the TPU-native form makes the cache-block dim the innermost
sequential grid axis so the combine state (m, l, acc) lives in VMEM scratch
— no second pass, no HBM round-trips for partials.

Grid (batch, cache_blocks); each step loads a (block_t × Hkv × head_dim)
K/V tile holding every KV head — the TPU compiler takes a block whose
last two dims are the full (Hkv, head_dim), not one head of them — and
walks the heads in-kernel.  Each head's panel is a (group × block_t)
logit block against the `group` query heads that share it (GQA).

``attend_block`` is the per-head online-softmax step shared with the
paged kernels (``paged_attention.py``).

Validated against ``ref.attention_ref`` (q_offset/masked) in interpret
mode; the distributed version shards the cache-seq dim over the `model`
mesh axis and GSPMD reduces the per-shard (m, l, acc) partials — the same
math this kernel does locally.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import tiling

NEG_INF = -1e30


def attend_block(h, q, k, v, valid, m_scr, l_scr, acc_scr, *, scale, softcap):
    """Fold one K/V block into KV head ``h``'s running (m, l, acc).

    ``q`` (rows, D), ``k``/``v`` (cols, D) fp32, ``valid`` (rows, cols)
    bool; the scratch refs are (Hkv, rows, 1|1|D) fp32."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                       # (rows, cols)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[h] = m_new
    l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def finish(h, l_scr, acc_scr):
    """Head ``h``'s normalized output (rows, D) fp32."""
    return acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)


def _fd_kernel(
    len_ref,     # (B,) scalar-prefetch valid lengths
    q_ref,       # (1, Hkv, group, D)
    k_ref,       # (1, block_t, Hkv, D)
    v_ref,
    o_ref,       # (1, Hkv, group, D)
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    block_t: int,
    t_steps: int,
    softcap: float,
):
    b = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        init_scratch(m_scr, l_scr, acc_scr)

    group = q_ref.shape[2]
    pos = ti * block_t + jax.lax.broadcasted_iota(jnp.int32, (group, block_t), 1)
    valid = pos < len_ref[b]
    for h in range(q_ref.shape[1]):
        attend_block(
            h,
            q_ref[0, h].astype(jnp.float32),        # (group, D)
            k_ref[0, :, h].astype(jnp.float32),     # (block_t, D)
            v_ref[0, :, h].astype(jnp.float32),
            valid, m_scr, l_scr, acc_scr, scale=scale, softcap=softcap,
        )

    @pl.when(ti == t_steps - 1)
    def _final():
        for h in range(q_ref.shape[1]):
            o_ref[0, h] = finish(h, l_scr, acc_scr).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,         # (B, 1, H, D)
    k_cache: jax.Array,   # (B, T, Hkv, D)
    v_cache: jax.Array,
    lengths: jax.Array,   # (B,) int32 valid cache length
    *,
    softcap: float = 0.0,
    block_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    assert H % Hkv == 0
    group = H // Hkv
    # non-multiple tails: zero-pad the cache up to a block multiple; the
    # in-kernel `pos < length` mask (length <= T) drops the padded rows
    block_t, Tp = tiling.pick_block(T, block_t)
    k_cache = tiling.pad_dim(k_cache, 1, Tp)
    v_cache = tiling.pad_dim(v_cache, 1, Tp)
    t_steps = Tp // block_t
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, group, D)

    kernel = functools.partial(
        _fd_kernel,
        scale=scale, block_t=block_t, t_steps=t_steps, softcap=softcap,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,      # lengths
            grid=(B, t_steps),
            in_specs=[
                pl.BlockSpec((1, Hkv, group, D), lambda b, ti, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, block_t, Hkv, D), lambda b, ti, ln: (b, ti, 0, 0)),
                pl.BlockSpec((1, block_t, Hkv, D), lambda b, ti, ln: (b, ti, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, Hkv, group, D), lambda b, ti, ln: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((Hkv, group, 1), jnp.float32),
                pltpu.VMEM((Hkv, group, 1), jnp.float32),
                pltpu.VMEM((Hkv, group, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(B, 1, H, D)
