"""Pallas TPU rotary position embedding over (B, S, H·D) rows.

Rotate-half RoPE: the lane j of a head's first half pairs with lane
j + D/2, and out = x·cos + partner·sin, with sin negated on the first
half.  XLA cannot find a lane's partner in place: the halves, split as
(B, S, H, D/2) arrays, fill a fraction of the 128 lanes, so it lays q
and k out with S minor and copies them back to rows for the attention
kernels (and its rolls or flips of the row write fp32 copies of their
own).  Here a block holds whole rows of the row-major (B, S, H·D) array;
each 128-lane tile of it holds whole heads (D divides 128), and each
lane's partner comes from two lane rotations of the tile in VMEM
(``pltpu.roll``) and a select.  The rows go back to HBM in the layout
they came in.

The cos and sin tables are (…, S, 128) fp32, one 128-lane pattern for
every block of heads, computed in XLA from the positions.  The gradient
is the same rotation by the opposite angle (``sign=-1``).  Oracle:
``ops.rope`` on the ``xla`` path, which this matches to within an ulp
of fp32 (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.tiling import pad_dim, pick_block

LANES = 128


def angles(positions: jax.Array, theta: float, D: int) -> jax.Array:
    """(…, S, D/2) fp32: position times frequency theta^(-2i/D)."""
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * freqs


def tables(positions: jax.Array, theta: float, D: int, sign: float):
    """(cos, sin), each (P, S, 128) fp32 with P = 1 for (S,) positions
    and B for (B, S): lane l holds frequency (l mod D) mod D/2, and sin is
    negated on each head's first half (and all of it times ``sign``)."""
    ang = angles(positions, theta, D)
    if positions.ndim == 1:
        ang = ang[None]
    cos, sin = jnp.cos(ang), sign * jnp.sin(ang)
    reps = LANES // D
    return (jnp.tile(jnp.concatenate([cos, cos], axis=-1), reps),
            jnp.tile(jnp.concatenate([-sin, sin], axis=-1), reps))


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, half: int):
    cos, sin = cos_ref[0], sin_ref[0]                     # (bs, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
    first = lane % (2 * half) < half
    for c in range(x_ref.shape[2] // LANES):
        cols = pl.ds(c * LANES, LANES)
        x = x_ref[0, :, cols].astype(jnp.float32)
        # jnp.roll semantics: a roll by 128 - half brings lane l + half to l
        partner = jnp.where(first, pltpu.roll(x, LANES - half, 1),
                            pltpu.roll(x, half, 1))
        o_ref[0, :, cols] = (x * cos + partner * sin).astype(o_ref.dtype)


def _block_rows(S: int, row_bytes: int):
    """(rows a block, padded S): all S rows where they fit in about 2 MB,
    else the largest power of two of rows that does, S padded to it."""
    fit = max(8, (2 << 20) // row_bytes)
    if S <= fit:
        return S, S
    return pick_block(S, 1 << (fit.bit_length() - 1))


def rope(x: jax.Array, positions: jax.Array, theta: float, *,
         sign: float = 1.0, interpret: bool = False):
    """x: (B, S, H, D) with D dividing 128 and H·D a multiple of 128;
    positions: (S,) or (B, S).  Returns x rotated by ``sign`` × its
    angles, in x's dtype.  A block is (1, block_s, H·D): whole rows
    (``_block_rows``), so that the grid has few steps."""
    B, S, H, D = x.shape
    cos, sin = tables(positions, theta, D, sign)
    bs, Sp = _block_rows(S, H * D * x.dtype.itemsize)
    rows = pad_dim(x.reshape(B, S, H * D), 1, Sp)
    cos, sin = pad_dim(cos, 1, Sp), pad_dim(sin, 1, Sp)
    per_row = cos.shape[0] > 1

    def x_map(b, s):
        return (b, s, 0)

    def table_map(b, s):
        return (b if per_row else 0, s, 0)

    out = pl.pallas_call(
        functools.partial(_rope_kernel, half=D // 2),
        grid=(B, Sp // bs),
        in_specs=[
            pl.BlockSpec((1, bs, H * D), x_map),
            pl.BlockSpec((1, bs, LANES), table_map),
            pl.BlockSpec((1, bs, LANES), table_map),
        ],
        out_specs=pl.BlockSpec((1, bs, H * D), x_map),
        out_shape=jax.ShapeDtypeStruct(rows.shape, x.dtype),
        interpret=interpret,
        name="rope",
    )(rows, cos, sin)
    return out[:, :S].reshape(B, S, H, D)
