"""Pallas TPU paged-attention decode/prefill + paged KV scatter write.

The serving engine's paged KV cache stores tokens in fixed-size pages of a
shared pool (``(num_pages, page, Hkv, D)``); a per-slot block table maps
logical cache positions to physical pages (``serving/paged_cache.py``).
Three kernels make that layout a first-class serving path:

``paged_flash_decode``
    The flash-decoding combine of ``flash_decode.py`` with the contiguous
    cache replaced by block-table indirection: grid
    (batch, pages_per_seq), and the K/V *page* tile (every KV head of
    it: the TPU compiler takes a block whose last two dims are the full
    (Hkv, D)) for grid step ``(b, p)`` is gathered straight out of the
    pool by the BlockSpec index map reading the prefetched block table
    (``PrefetchScalarGridSpec``) — the gather is the DMA, no
    materialized (B, T) cache ever exists.  Per-head combine state
    (m, l, acc) lives in VMEM scratch across the sequential page axis,
    exactly like the contiguous kernel.

``paged_flash_prefill``
    Chunked/suffix prefill attention through the same block table: the
    query block is a whole *chunk* of ``S`` tokens sitting at logical
    positions ``starts[b] + i`` (``starts`` supports prefix-cache skips
    and chunked prefill — the chunk attends to every already-written
    page, including pages shared from the prefix cache, plus itself,
    under a causal mask shifted by the query offset).  Same VMEM
    running-LSE combine as the decode kernel over grid
    (batch, query_blocks, pages_per_seq), with (group·bs) query rows per
    KV head instead of ``group``: queries travel head-major,
    (B, Hkv, group, S, D), so a block of ``bs`` chunk tokens has last two
    dims (bs, D).

``paged_kv_write``
    Per-token decode cache insert: grid (B,), each step rewrites ONE page
    (the page holding ``pos``) with the new token placed at row
    ``pos % page``.  The pool rides through ``input_output_aliases`` so
    the op is an in-place O(B·page) scatter — replacing the O(B·T)
    one-hot masked select the dense per-slot layout needs
    (``models/attention.py``).

Unallocated block-table entries point at the reserved null page 0; slots
with ``length == 0`` read (and may write) only that page, so collisions
there are harmless garbage — page 0 is never attributed to a sequence.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import tiling
from repro.kernels.flash_decode import attend_block, finish, init_scratch

# paged prefill query block: chunk tokens per grid step (a multiple of 16)
_BLOCK_S = 128


# --------------------------------------------------------------------- #
# decode attention through the block table
# --------------------------------------------------------------------- #
def _pa_kernel(
    bt_ref,      # (B, pages_per_seq) scalar-prefetch block table
    len_ref,     # (B,) scalar-prefetch valid lengths
    q_ref,       # (1, Hkv, group, D)
    k_ref,       # (1, page, Hkv, D)  — the page picked by the index map
    v_ref,
    o_ref,       # (1, Hkv, group, D)
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    page: int,
    p_steps: int,
    softcap: float,
):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        init_scratch(m_scr, l_scr, acc_scr)

    # logical position of each page row; pages past the valid length are
    # the null page — masked out entirely (m stays NEG_INF for len==0).
    group = q_ref.shape[2]
    pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (group, page), 1)
    valid = pos < len_ref[b]
    for h in range(q_ref.shape[1]):
        attend_block(
            h,
            q_ref[0, h].astype(jnp.float32),        # (group, D)
            k_ref[0, :, h].astype(jnp.float32),     # (page, D)
            v_ref[0, :, h].astype(jnp.float32),
            valid, m_scr, l_scr, acc_scr, scale=scale, softcap=softcap,
        )

    @pl.when(pi == p_steps - 1)
    def _final():
        for h in range(q_ref.shape[1]):
            o_ref[0, h] = finish(h, l_scr, acc_scr).astype(o_ref.dtype)


def paged_flash_decode(
    q: jax.Array,            # (B, 1, H, D)
    k_pool: jax.Array,       # (num_pages, page, Hkv, D)
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, pages_per_seq) int32 physical page ids
    lengths: jax.Array,      # (B,) int32 valid cache length
    *,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, D = q.shape
    page, Hkv = k_pool.shape[1], k_pool.shape[2]
    pages_per_seq = block_table.shape[1]
    assert H % Hkv == 0
    group = H // Hkv
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, group, D)

    kernel = functools.partial(
        _pa_kernel,
        scale=scale, page=page, p_steps=pages_per_seq, softcap=softcap,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # block_table, lengths
            grid=(B, pages_per_seq),
            in_specs=[
                pl.BlockSpec(
                    (1, Hkv, group, D), lambda b, pi, bt, ln: (b, 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, page, Hkv, D), lambda b, pi, bt, ln: (bt[b, pi], 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, page, Hkv, D), lambda b, pi, bt, ln: (bt[b, pi], 0, 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, Hkv, group, D), lambda b, pi, bt, ln: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((Hkv, group, 1), jnp.float32),
                pltpu.VMEM((Hkv, group, 1), jnp.float32),
                pltpu.VMEM((Hkv, group, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(B, 1, H, D)


# --------------------------------------------------------------------- #
# chunked/suffix prefill attention through the block table
# --------------------------------------------------------------------- #
def _pp_kernel(
    bt_ref,      # (B, pages_per_seq) scalar-prefetch block table
    start_ref,   # (B,) scalar-prefetch query offset (first query's position)
    len_ref,     # (B,) scalar-prefetch total valid context length
    q_ref,       # (1, Hkv, group, bs, D)
    k_ref,       # (1, page, Hkv, D)  — the page picked by the index map
    v_ref,
    o_ref,       # (1, Hkv, group, bs, D)
    m_scr, l_scr, acc_scr,    # (Hkv, group·bs, 1/1/D)
    *,
    scale: float,
    page: int,
    p_steps: int,
    softcap: float,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        init_scratch(m_scr, l_scr, acc_scr)

    _, Hkv, group, bs, D = q_ref.shape
    rows = group * bs
    # causal mask shifted by the query offset: query row r (chunk token
    # qi·bs + r % bs) sits at logical position start + qi·bs + r % bs and
    # may attend to k positions <= its own; pages past the valid length
    # (incl. the null page in unallocated entries) are masked out.
    q_pos = start_ref[b] + qi * bs + (
        jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0) % bs
    )
    k_pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
    valid = (k_pos <= q_pos) & (k_pos < len_ref[b])
    for h in range(Hkv):
        attend_block(
            h,
            q_ref[0, h].astype(jnp.float32).reshape(rows, D),
            k_ref[0, :, h].astype(jnp.float32),     # (page, D)
            v_ref[0, :, h].astype(jnp.float32),
            valid, m_scr, l_scr, acc_scr, scale=scale, softcap=softcap,
        )

    @pl.when(pi == p_steps - 1)
    def _final():
        for h in range(Hkv):
            out = finish(h, l_scr, acc_scr).reshape(group, bs, D)
            o_ref[0, h] = out.astype(o_ref.dtype)


def paged_flash_prefill(
    q: jax.Array,            # (B, S, H, D) chunk queries
    k_pool: jax.Array,       # (num_pages, page, Hkv, D)
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, pages_per_seq) int32 physical page ids
    starts: jax.Array,       # (B,) int32 logical position of query row 0
    lengths: jax.Array,      # (B,) int32 total valid context (start + valid)
    *,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, D = q.shape
    page, Hkv = k_pool.shape[1], k_pool.shape[2]
    pages_per_seq = block_table.shape[1]
    assert H % Hkv == 0
    group = H // Hkv
    scale = 1.0 / math.sqrt(D)
    # query blocks of bs <= _BLOCK_S chunk tokens (a multiple of 16, the
    # bf16 sublane tile, so the in-kernel (group, bs) -> rows merge is
    # layout-free); padded tail rows are computed and sliced off
    bs = min(_BLOCK_S, S + (-S % 16))
    Sp = S + (-S % bs)

    # (B, S, Hkv·group, D) -> (B, Hkv, group, Sp, D): the block's last two
    # dims are (bs, D) and every KV head's queries travel together
    qg = jnp.transpose(q.reshape(B, S, Hkv, group, D), (0, 2, 3, 1, 4))
    qg = tiling.pad_dim(qg, 3, Sp)

    kernel = functools.partial(
        _pp_kernel,
        scale=scale, page=page, p_steps=pages_per_seq, softcap=softcap,
    )
    q_map = lambda b, qi, pi, bt, st, ln: (b, 0, 0, qi, 0)  # noqa: E731
    kv_map = lambda b, qi, pi, bt, st, ln: (bt[b, pi], 0, 0, 0)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # block_table, starts, lengths
            grid=(B, Sp // bs, pages_per_seq),
            in_specs=[
                pl.BlockSpec((1, Hkv, group, bs, D), q_map),
                pl.BlockSpec((1, page, Hkv, D), kv_map),
                pl.BlockSpec((1, page, Hkv, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, Hkv, group, bs, D), q_map),
            scratch_shapes=[
                pltpu.VMEM((Hkv, group * bs, 1), jnp.float32),
                pltpu.VMEM((Hkv, group * bs, 1), jnp.float32),
                pltpu.VMEM((Hkv, group * bs, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Sp, D), q.dtype),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32), starts.astype(jnp.int32),
        lengths.astype(jnp.int32), qg, k_pool, v_pool,
    )
    out = jnp.transpose(out[:, :, :, :S], (0, 3, 1, 2, 4))
    return out.reshape(B, S, H, D)


# --------------------------------------------------------------------- #
# per-token scatter write
# --------------------------------------------------------------------- #
def _kv_write_kernel(
    page_idx_ref,   # (B,) scalar-prefetch physical page per slot
    row_ref,        # (B,) scalar-prefetch row (pos % page) per slot
    kn_ref,         # (1, 1, Hkv, D) new K token for this slot
    vn_ref,
    kin_ref,        # (1, page, Hkv, D) current page content (aliased pool)
    vin_ref,
    kout_ref,       # (1, page, Hkv, D) rewritten page
    vout_ref,
    *,
    page: int,
):
    b = pl.program_id(0)
    r = row_ref[b]
    rows = jax.lax.broadcasted_iota(jnp.int32, (page, 1, 1), 0)
    hit = rows == r
    kout_ref[0] = jnp.where(hit, kn_ref[0].astype(kout_ref.dtype), kin_ref[0])
    vout_ref[0] = jnp.where(hit, vn_ref[0].astype(vout_ref.dtype), vin_ref[0])


def paged_kv_write(
    k_pool: jax.Array,     # (num_pages, page, Hkv, D)
    v_pool: jax.Array,
    k_new: jax.Array,      # (B, 1, Hkv, D)
    v_new: jax.Array,
    page_idx: jax.Array,   # (B,) physical page holding each slot's write pos
    row: jax.Array,        # (B,) row within that page (pos % page)
    *,
    interpret: bool = False,
):
    """In-place O(B·page) decode-token insert; returns the updated pools.

    Each grid step rewrites exactly the page its slot owns at the write
    position; pages of distinct active slots are disjoint by construction
    (the allocator hands a page to one sequence), so steps never race on
    live data.  Inactive slots all target the null page 0 — those writes
    may collide, but page 0 holds no sequence.
    """
    B = k_new.shape[0]
    P, page, Hkv, D = k_pool.shape
    kernel = functools.partial(_kv_write_kernel, page=page)
    new_k, new_v = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # page_idx, row
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, 1, Hkv, D), lambda b, pi, ri: (b, 0, 0, 0)),
                pl.BlockSpec((1, 1, Hkv, D), lambda b, pi, ri: (b, 0, 0, 0)),
                pl.BlockSpec((1, page, Hkv, D), lambda b, pi, ri: (pi[b], 0, 0, 0)),
                pl.BlockSpec((1, page, Hkv, D), lambda b, pi, ri: (pi[b], 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, page, Hkv, D), lambda b, pi, ri: (pi[b], 0, 0, 0)),
                pl.BlockSpec((1, page, Hkv, D), lambda b, pi, ri: (pi[b], 0, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # pools are donated: operand indices count the scalar-prefetch args
        # (page_idx=0, row=1, k_new=2, v_new=3, k_pool=4, v_pool=5)
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(
        page_idx.astype(jnp.int32), row.astype(jnp.int32),
        k_new, v_new, k_pool, v_pool,
    )
    return new_k, new_v
