"""Pallas TPU fused RMSNorm / LayerNorm (Apex-class fused norm).

Row-tiled: grid over blocks of tokens; each step loads a (block_rows ×
d_model) VMEM tile, computes the moments and normalizes in one pass (fp32
math), writes the tile back.  d_model up to 16384 → tile ≤ 16384·8·4B =
0.5 MB fp32 at block_rows=8, comfortably inside VMEM; for small d_model the
row block is widened.

Oracles: ``ref.rmsnorm_ref`` / ``ref.layernorm_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = y.astype(o_ref.dtype)


def _layernorm_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float, use_bias: bool):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)[None, :]
    if use_bias:
        y = y + b_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = y.astype(o_ref.dtype)


def _block_rows(n_rows: int, d: int) -> int:
    # target ~1 MB fp32 tiles
    target = max(1, (1 << 18) // max(d, 1))
    b = 1
    while b * 2 <= target and n_rows % (b * 2) == 0:
        b *= 2
    return b


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5, *, interpret: bool = False):
    orig_shape = x.shape
    d = orig_shape[-1]
    xf = x.reshape(-1, d)
    rows = xf.shape[0]
    br = _block_rows(rows, d)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(xf, w)
    return out.reshape(orig_shape)


def layernorm(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    eps: float = 1e-5,
    *,
    interpret: bool = False,
):
    orig_shape = x.shape
    d = orig_shape[-1]
    xf = x.reshape(-1, d)
    rows = xf.shape[0]
    br = _block_rows(rows, d)
    use_bias = b is not None
    bb = b if use_bias else jnp.zeros((d,), x.dtype)
    out = pl.pallas_call(
        functools.partial(_layernorm_kernel, eps=eps, use_bias=use_bias),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
        name="layernorm",
    )(xf, w, bb)
    return out.reshape(orig_shape)
