"""Pallas TPU flash attention (forward + backward).

TPU-native adaptation of TransformerEngine-class fused attention:
  * kv innermost in the grid, so VMEM scratch accumulators (running max /
    denom / out) carry across kv steps, using the sequential-grid
    semantics of TPU Pallas.
  * The caller picks the tile (``ops.attention_blocks``): large for
    bidirectional calls, whose fixed per-grid-step cost would otherwise
    dominate, and 128 × 128 for causal and windowed calls, whose kernels
    skip dead tiles at that granularity.  A step's VMEM holds the
    double-buffered Q, K, V (and dO) tiles plus a few (block_q × block_k)
    fp32 score-sized temporaries — 4 MB each at 1024 × 1024, which the
    TPU compiler places in VMEM without a raised limit
    (tests/test_tpu_compile.py compiles the ESM-2 cell's shape).
  * tiles are upcast to fp32 in the kernel and the matmuls run at default
    precision, which the MXU takes in one bf16 pass.  Feeding it bf16
    operands instead (Q·Kᵀ, dO·Vᵀ as they are, P and dS cast down) gave
    the same results bit for bit on a TPU v5e, and was no faster: 1%
    slower at 1024 × 1024 tiles and 3% slower on causal 128 × 128 tiles.
  * the forward of a call that is bidirectional, unwindowed and whose keys
    fill whole tiles builds no per-element mask (a static condition): at
    the ESM-2 cell's 1024 × 1024 tiles the mask was 13% of the forward's
    time.  The backward kernels build it always; there it costs nothing
    measurable.
  * online softmax in fp32; GQA handled in the K/V index_map (no
    jnp.repeat — each kv tile is re-fetched per group member by the DMA
    engine, the natural TPU analogue of TE's GQA kernels).
  * supports causal masking, sliding window, logit softcap, and a q-position
    offset for decode.

Layout.  The kernels read q, k, v, o and dO, and write o, dQ, dK and dV,
in the model's own row-major layout: (B, S, H, D) reshaped to (B, S, H·D),
which is a bitcast.  A block is (1, block, 128) over (B, S, H·D), so it
holds whole heads: one at D = 128, a pair at D = 64.  A pair is worked
without lane shuffles: one head's 64 lanes of K (and V) are zeroed and
the contraction runs over all 128, which a 128-deep MXU takes in the same
passes as a 64-deep one; the heads' results are put back together with
lane masks.  Per-row statistics cross the kernel boundary lane-dense, as
(B, H/hb, hb, S) fp32 for hb heads a block, each block (hb, block_q):
  * the forward writes LSE so, transposing its (block_q, 1) columns once
    per q block in VMEM;
  * the dQ kernel computes Δ = rowsum(dO ⊙ O) per head from the O and dO
    tiles it loads, once per q block, and writes it out so too;
  * the dK/dV kernel works in the transposed orientation (Sᵀ = K·Qᵀ, one
    (block_k, block_q) tile), where LSE and Δ are (1, block_q) rows that
    broadcast as they are, and dK += dSᵀ·Q, dV += Pᵀ·dO are plain
    matmuls.
So XLA does no transpose, cast, slice or broadcast around the calls.  The
layout applies where whole heads of q and of k/v sit in 128-lane blocks
(``heads_per_block``): D = 128 with any GQA group, or D = 64 with an even
head count and no GQA.  Any other shape takes the head-major kernels
below (``*_head_major``), which transpose to (B·H, S, D) and pass per-row
statistics lane-replicated as (B·H, S, 128).  A causal call with no
backward takes the head-major forward too (``flash_attention``).

Backward pass (FlashAttention-2 style):
  * the dQ kernel — grid (B, H/hb, q_blocks, kv_blocks); recomputes
    block probabilities from the saved per-row LSE and accumulates dQ in
    VMEM scratch across kv steps.
  * the dK/dV kernel — grid (B, Hkv/hb, kv_blocks, group·q_blocks); the
    innermost dim sweeps every q block of every query head in the GQA
    group so dK/dV accumulate directly in grouped-head form — the dK/dV
    tensors never materialize at (B, H, T, D).
  All passes recompute S = QKᵀ on the MXU instead of saving the (S × T)
  probability matrix — O(S) residuals (LSE, Δ), exactly like the fwd.

Validated against ``ref.attention_ref`` (values) and its jax.grad
(cotangents) in interpret mode; see tests/test_kernels.py and
tests/test_grads.py.  The custom-VJP dispatch lives in ``ops.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.tiling import pad_dim, pick_block

NEG_INF = -1e30
LANES = 128  # a block's lane width; the head-major path's statistics too


def heads_per_block(H: int, Hkv: int, D: int) -> int:
    """Query heads one 128-lane block holds on the lane-dense path: 1 at
    D = 128, 2 at D = 64 with an even head count and no GQA (so a block
    of k/v holds the same pair).  0 where the path does not apply: the
    call then takes the head-major kernels."""
    if D == LANES:
        return 1
    if 2 * D == LANES and H % 2 == 0 and Hkv == H:
        return 2
    return 0


def _needs_mask(*, causal, window, kv_len, kv_padded):
    """Static: can any entry of any tile be masked?  A bidirectional,
    unwindowed call over keys that fill whole tiles has every key live,
    so its forward kernel builds no per-element mask."""
    return causal or window > 0 or kv_len != kv_padded


def _block_mask(qi, ki, *, block_q, block_k, causal, window, q_offset, kv_len,
                transposed=False):
    """(block_q, block_k) validity mask for the (qi, ki) tile; with
    ``transposed``, the (block_k, block_q) mask of its transpose."""
    shape, q_ax = ((block_k, block_q), 1) if transposed else ((block_q, block_k), 0)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_ax) + q_offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_ax)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)
    return mask


def _block_live(qi, ki, *, block_q, block_k, causal, window, q_offset):
    """Scalar predicate: does tile (qi, ki) contain any unmasked entry?

    Used to skip recompute work for tiles that are fully masked under
    causal/window structure (the DMA still runs; the MXU work doesn't).
    """
    conds = []
    if causal:
        # last q row of the tile must reach the first k column
        conds.append(ki * block_k <= qi * block_q + block_q - 1 + q_offset)
    if window > 0:
        # last k column must be inside the window of the last q row
        conds.append(ki * block_k + block_k - 1 > qi * block_q + q_offset - window)
    if not conds:
        return None
    live = conds[0]
    for c in conds[1:]:
        live = jnp.logical_and(live, c)
    return live


def _when_live(live, fn):
    """Run ``fn`` now, or under ``pl.when(live)`` where some tiles are dead."""
    if live is None:
        fn()
    else:
        pl.when(live)(fn)


def _recompute_p_ds(q, k, v, do, lse, delta, mask, *, scale, softcap):
    """Shared bwd math: recompute probabilities + pre-softcap score grads.

    Returns (p, ds) both (bq, bk) fp32; ds already includes the logit
    scale so dq = ds @ k and dk = dsᵀ @ q need no further scaling.
    Called with (k, q, dO, v) in place of (q, k, v, dO), row statistics
    and the transposed mask, it returns (pᵀ, dsᵀ).
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # pre-softcap scores
    if softcap > 0.0:
        t = jnp.tanh(s / softcap)
        z = softcap * t                         # logits
    else:
        z = s
    # p = exp(z - lse) on valid entries.  The mask (not the NEG_INF trick)
    # must gate this: a fully-masked row has lse ≈ NEG_INF and exp(z - lse)
    # would be exp(0) = 1 at its masked entries.  The exponent is clamped at
    # 0 (p <= 1 mathematically) so garbage lse rows — fully-masked or
    # padded q rows, whose dO is zero — stay finite instead of overflowing.
    p = jnp.where(mask, jnp.exp(jnp.minimum(jnp.where(mask, z, 0.0) - lse, 0.0)), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # (bq, bk)
    dz = p * (dp - delta)
    if softcap > 0.0:
        dz = dz * (1.0 - t * t)                  # through the softcap tanh
    return p, dz * scale


def _matmul(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# --------------------------------------------------------------------- #
# lane-dense kernels: blocks of whole heads over (B, S, H·D)
# --------------------------------------------------------------------- #
def _sum(xs):
    """xs[0] + xs[1] + …, with no zero added first."""
    return sum(xs[1:], xs[0])


def _head_lanes(x, h, heads):
    """``x`` (rows, 128) with every lane outside head ``h`` zeroed; ``x``
    itself when a block holds one head."""
    if heads == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane // (LANES // heads) == h, x, 0.0)


def _per_head_lanes(cols, heads):
    """(rows, 128): each lane holds its head's entry of the (rows, 1)
    columns ``cols``, one per head."""
    out = jnp.broadcast_to(cols[-1], (cols[-1].shape[0], LANES))
    if heads > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for h in range(heads - 1):
            out = jnp.where(lane // (LANES // heads) == h, cols[h], out)
    return out


def _to_rows(cols):
    """(heads, rows): the (rows, 1) columns ``cols`` as rows, by one
    (rows, 128) → (128, rows) transpose in VMEM."""
    rows = cols[0].shape[0]
    out = jnp.broadcast_to(cols[-1], (rows, LANES))
    lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    for h in range(len(cols) - 1):
        out = jnp.where(lane == h, cols[h], out)
    return out.T[: len(cols)]


def _to_col(row):
    """(rows, 1): the (1, rows) row ``row`` as a column."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _fa_lane_kernel(
    q_ref, k_ref, v_ref,       # VMEM input tiles (1, b, 128)
    o_ref, lse_ref,            # (1, bq, 128), (1, 1, heads, bq)
    m_scr, l_scr, acc_scr,     # (heads, bq, 1) ×2, (bq, 128)
    *,
    heads: int,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_k: int,
    kv_steps: int,
    q_offset: int,
    kv_len: int,
    masked: bool,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)           # (bq, 128)
    k = k_ref[0].astype(jnp.float32)           # (bk, 128)
    v = v_ref[0].astype(jnp.float32)
    mask = None
    if masked:
        mask = _block_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, q_offset=q_offset, kv_len=kv_len,
        )

    alphas, pvs = [], []
    for h in range(heads):
        kh = _head_lanes(k, h, heads)
        s = jax.lax.dot_general(
            q, kh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                               # (bq, bk): head h's scores
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        if masked:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[h]                       # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            # fully-masked rows (can happen under causal/window): keep them inert
            p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[h] = m_new
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
        alphas.append(alpha)
        pvs.append(_matmul(p, _head_lanes(v, h, heads)))  # head h's lanes only
    acc_scr[...] = acc_scr[...] * _per_head_lanes(alphas, heads) + _sum(pvs)

    @pl.when(ki == kv_steps - 1)
    def _final():
        denom = [jnp.maximum(l_scr[h], 1e-30) for h in range(heads)]
        o_ref[0] = (acc_scr[...] / _per_head_lanes(denom, heads)).astype(o_ref.dtype)
        # per-row logsumexp residual for the backward pass (fully-masked
        # rows get lse ≈ NEG_INF, which the bwd kernels treat as inert)
        lse_ref[0, 0] = _to_rows(
            [m_scr[h] + jnp.log(denom[h]) for h in range(heads)]
        )


def _fa_lane_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
    dq_ref, delta_ref,
    dq_scr, lse_scr, delta_scr,
    *,
    heads: int,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_k: int,
    kv_steps: int,
    q_offset: int,
    kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # Δ = rowsum(dO ⊙ O) per head, written out for the dK/dV kernel;
        # it and LSE kept as (bq, 1) columns across the kv steps
        prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        delta = [jnp.sum(_head_lanes(prod, h, heads), axis=-1, keepdims=True)
                 for h in range(heads)]
        delta_ref[0, 0] = _to_rows(delta)
        for h in range(heads):
            delta_scr[h] = delta[h]
            lse_scr[h] = _to_col(lse_ref[0, 0, h:h + 1, :])

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        mask = _block_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, q_offset=q_offset, kv_len=kv_len,
        )
        dq = []
        for h in range(heads):
            kh = _head_lanes(k, h, heads)
            _, ds = _recompute_p_ds(
                q, kh, _head_lanes(v, h, heads), do, lse_scr[h], delta_scr[h],
                mask, scale=scale, softcap=softcap,
            )
            dq.append(_matmul(ds, kh))             # head h's lanes only
        dq_scr[...] += _sum(dq)

    _when_live(_block_live(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, q_offset=q_offset,
    ), _accumulate)

    @pl.when(ki == kv_steps - 1)
    def _final():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_lane_dkv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *,
    heads: int,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_k: int,
    q_steps: int,
    inner_steps: int,     # group * q_steps
    q_offset: int,
    kv_len: int,
):
    ki = pl.program_id(2)
    j = pl.program_id(3)
    qi = j % q_steps

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        mask = _block_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, q_offset=q_offset, kv_len=kv_len, transposed=True,
        )
        dk, dv = [], []
        for h in range(heads):
            qh = _head_lanes(q, h, heads)
            doh = _head_lanes(do, h, heads)
            pt, dst = _recompute_p_ds(               # (bk, bq) each
                k, qh, doh, v, lse_ref[0, 0, h:h + 1, :],
                delta_ref[0, 0, h:h + 1, :], mask, scale=scale, softcap=softcap,
            )
            dv.append(_matmul(pt, doh))              # head h's lanes only
            dk.append(_matmul(dst, qh))
        dv_scr[...] += _sum(dv)
        dk_scr[...] += _sum(dk)

    _when_live(_block_live(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, q_offset=q_offset,
    ), _accumulate)

    @pl.when(j == inner_steps - 1)
    def _final():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# two heads' (block_q × block_k) fp32 temporaries at 1024² tiles: the dQ
# and dK/dV kernels need more than the 16 MiB of scoped VMEM by default
_LANE_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 << 20)


def _lane_dense(x, n):
    """(B, L, H, D) -> (B, n, H·D): a bitcast, then zero rows to ``n``."""
    B, L, H, D = x.shape
    return pad_dim(x.reshape(B, L, H * D), 1, n)


def _fwd_lane_dense(q, k, v, *, heads, causal, window, softcap, q_offset,
                    block_q, block_k, interpret):
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    block_q, Sp = pick_block(S, block_q)
    block_k, Tp = pick_block(T, block_k)
    kv_steps = Tp // block_k

    def q_map(b, c, qi, ki):
        return (b, qi, c)

    def kv_map(b, c, qi, ki):
        return (b, ki, c // group)

    def row_map(b, c, qi, ki):
        return (b, c, 0, qi)

    kernel = functools.partial(
        _fa_lane_kernel, heads=heads, scale=1.0 / math.sqrt(D), causal=causal,
        window=window, softcap=softcap, block_q=block_q, block_k=block_k,
        kv_steps=kv_steps, q_offset=q_offset, kv_len=T,
        masked=_needs_mask(causal=causal, window=window, kv_len=T, kv_padded=Tp),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H // heads, Sp // block_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, LANES), q_map),
            pl.BlockSpec((1, block_k, LANES), kv_map),
            pl.BlockSpec((1, block_k, LANES), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, LANES), q_map),
            pl.BlockSpec((1, 1, heads, block_q), row_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, H * D), q.dtype),
            jax.ShapeDtypeStruct((B, H // heads, heads, Sp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_LANE_PARAMS,
        name="flash_attention_fwd",
    )(_lane_dense(q, Sp), _lane_dense(k, Tp), _lane_dense(v, Tp))
    return out[:, :S].reshape(B, S, H, D), lse


def _bwd_lane_dense(q, k, v, out, lse, do, *, heads, causal, window, softcap,
                    q_offset, block_q, block_k, interpret):
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    # padded q rows carry zero dO (and so zero Δ): they contribute exactly
    # nothing to dK/dV; padded kv rows are masked in-kernel via kv_len
    block_q, Sp = pick_block(S, block_q)
    block_k, Tp = pick_block(T, block_k)
    q_steps = Sp // block_q
    kv_steps = Tp // block_k
    qd, od, dod = (_lane_dense(x, Sp) for x in (q, out, do))
    kd, vd = _lane_dense(k, Tp), _lane_dense(v, Tp)
    static = dict(
        heads=heads, scale=1.0 / math.sqrt(D), causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, q_offset=q_offset,
        kv_len=T,
    )

    # ---- dQ (and Δ): grid (B, H/hb, q, kv), kv innermost ----
    def q_map(b, c, qi, ki):
        return (b, qi, c)

    def kv_map(b, c, qi, ki):
        return (b, ki, c // group)

    def row_map(b, c, qi, ki):
        return (b, c, 0, qi)

    q_spec = pl.BlockSpec((1, block_q, LANES), q_map)
    kv_spec = pl.BlockSpec((1, block_k, LANES), kv_map)
    row_spec = pl.BlockSpec((1, 1, heads, block_q), row_map)
    dq, delta = pl.pallas_call(
        functools.partial(_fa_lane_dq_kernel, kv_steps=kv_steps, **static),
        grid=(B, H // heads, q_steps, kv_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, H * D), q.dtype),
            jax.ShapeDtypeStruct(lse.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
            pltpu.VMEM((heads, block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_LANE_PARAMS,
        name="flash_attention_dq",
    )(qd, kd, vd, dod, od, lse)

    # ---- dK/dV: grid (B, Hkv/hb, kv, group·q) — the innermost dim walks
    # every q block of every head in the GQA group ----
    inner_steps = group * q_steps

    def q_map2(b, c, ki, j):
        return (b, j % q_steps, c * group + j // q_steps)

    def row_map2(b, c, ki, j):
        return (b, c * group + j // q_steps, 0, j % q_steps)

    def kv_map2(b, c, ki, j):
        return (b, ki, c)

    q_spec2 = pl.BlockSpec((1, block_q, LANES), q_map2)
    row_spec2 = pl.BlockSpec((1, 1, heads, block_q), row_map2)
    kv_spec2 = pl.BlockSpec((1, block_k, LANES), kv_map2)
    dk, dv = pl.pallas_call(
        functools.partial(_fa_lane_dkv_kernel, q_steps=q_steps,
                          inner_steps=inner_steps, **static),
        grid=(B, Hkv // heads, kv_steps, inner_steps),
        in_specs=[q_spec2, q_spec2, row_spec2, row_spec2, kv_spec2, kv_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Hkv * D), k.dtype),
            jax.ShapeDtypeStruct((B, Tp, Hkv * D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, LANES), jnp.float32),
            pltpu.VMEM((block_k, LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_LANE_PARAMS,
        name="flash_attention_dkv",
    )(qd, dod, lse, delta, kd, vd)

    return (dq[:, :S].reshape(B, S, H, D), dk[:, :T].reshape(B, T, Hkv, D),
            dv[:, :T].reshape(B, T, Hkv, D))


# --------------------------------------------------------------------- #
# head-major kernels: the fallback for every other shape
# --------------------------------------------------------------------- #
def _fa_kernel(
    q_ref, k_ref, v_ref,       # VMEM input tiles
    o_ref, lse_ref,            # VMEM output tiles
    m_scr, l_scr, acc_scr,     # VMEM scratch (carried across kv grid steps)
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_k: int,
    kv_steps: int,
    q_offset: int,
    kv_len: int,
    masked: bool,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)           # (bq, D)
    k = k_ref[0].astype(jnp.float32)           # (bk, D)
    v = v_ref[0].astype(jnp.float32)           # (bk, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # (bq, bk)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    if masked:
        mask = _block_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, q_offset=q_offset, kv_len=kv_len,
        )
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                         # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    if masked:
        # fully-masked rows (can happen under causal/window): keep them inert
        p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + _matmul(p, v)

    @pl.when(ki == kv_steps - 1)
    def _final():
        l = l_scr[...]
        denom = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        # per-row logsumexp residual, replicated over a full lane tile: a
        # (block_q,) row vector is not a block shape the TPU compiler accepts
        lse_ref[0] = jnp.broadcast_to(m_scr[...] + jnp.log(denom), lse_ref.shape[1:])


def _head_major(x):
    """(B, S, H, D) -> (B*H, S, D)."""
    B, S, H, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, S, D)


def _fwd_head_major(q, k, v, *, causal, window, softcap, q_offset,
                    block_q, block_k, interpret):
    """Returns (out (B,S,H,D), lse (B*H, S) fp32)."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    # non-multiple dims: zero-pad q rows (outputs sliced below) and kv rows
    # (masked in-kernel via kv_len) rather than shrinking the block
    block_q, Sp = pick_block(S, block_q)
    block_k, Tp = pick_block(T, block_k)
    kv_steps = Tp // block_k
    scale = 1.0 / math.sqrt(D)

    # (B, H) collapsed into the leading grid dim; head-major layout
    qh = pad_dim(_head_major(q), 1, Sp)
    kh = pad_dim(_head_major(k), 1, Tp)
    vh = pad_dim(_head_major(v), 1, Tp)

    def q_map(b, qi, ki):
        return (b, qi, 0)

    def kv_map(b, qi, ki):
        batch = b // H
        head = b % H
        return (batch * Hkv + head // group, ki, 0)

    def lse_map(b, qi, ki):
        return (b, qi, 0)

    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        kv_steps=kv_steps,
        q_offset=q_offset,
        kv_len=T,
        masked=_needs_mask(causal=causal, window=window, kv_len=T, kv_padded=Tp),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Sp // block_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_q, LANES), lse_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sp, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd_head_major",
    )(qh, kh, vh)
    out, lse = out[:, :S], lse[:, :S, 0]
    return jnp.transpose(out.reshape(B, H, S, D), (0, 2, 1, 3)), lse


def _fa_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_k: int,
    kv_steps: int,
    q_offset: int,
    kv_len: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]                  # (bq, 1)
        delta = delta_ref[0][:, :1]
        mask = _block_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, q_offset=q_offset, kv_len=kv_len,
        )
        _, ds = _recompute_p_ds(
            q, k, v, do, lse, delta, mask, scale=scale, softcap=softcap,
        )
        dq_scr[...] += _matmul(ds, k)

    _when_live(_block_live(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, q_offset=q_offset,
    ), _accumulate)

    @pl.when(ki == kv_steps - 1)
    def _final():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_dkv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_k: int,
    q_steps: int,
    inner_steps: int,     # group * q_steps
    q_offset: int,
    kv_len: int,
):
    ki = pl.program_id(1)
    j = pl.program_id(2)
    qi = j % q_steps

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        mask = _block_mask(
            qi, ki, block_q=block_q, block_k=block_k, causal=causal,
            window=window, q_offset=q_offset, kv_len=kv_len,
        )
        p, ds = _recompute_p_ds(
            q, k, v, do, lse, delta, mask, scale=scale, softcap=softcap,
        )
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                        # (bk, D)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    _when_live(_block_live(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, q_offset=q_offset,
    ), _accumulate)

    @pl.when(j == inner_steps - 1)
    def _final():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_head_major(q, k, v, out, lse, do, *, causal, window, softcap,
                    q_offset, block_q, block_k, interpret):
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    # padded q rows carry zero dO (and zero Δ), so they contribute exactly
    # nothing to dK/dV; padded kv rows are masked in-kernel via kv_len
    block_q, Sp = pick_block(S, block_q)
    block_k, Tp = pick_block(T, block_k)
    q_steps = Sp // block_q
    kv_steps = Tp // block_k
    scale = 1.0 / math.sqrt(D)

    qh = pad_dim(_head_major(q), 1, Sp)
    kh = pad_dim(_head_major(k), 1, Tp)
    vh = pad_dim(_head_major(v), 1, Tp)
    oh = pad_dim(_head_major(out), 1, Sp)
    doh = pad_dim(_head_major(do), 1, Sp)

    # per-row statistics, replicated over one lane tile like the forward's
    # LSE output: lse and Δ = rowsum(dO ⊙ O) (one fused XLA reduction)
    def rows(x):
        return jnp.broadcast_to(pad_dim(x, 1, Sp)[..., None], (B * H, Sp, LANES))

    lse = rows(lse)
    delta = rows(jnp.sum(
        oh.astype(jnp.float32) * doh.astype(jnp.float32), axis=-1
    ))

    # ---- dQ: grid (B·H, q, kv), kv innermost accumulates into scratch ----
    def q_map(b, qi, ki):
        return (b, qi, 0)

    def kv_map(b, qi, ki):
        batch = b // H
        head = b % H
        return (batch * Hkv + head // group, ki, 0)

    def row_map(b, qi, ki):
        return (b, qi, 0)

    dq_kernel = functools.partial(
        _fa_dq_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, kv_steps=kv_steps,
        q_offset=q_offset, kv_len=T,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_q, LANES), row_map),
            pl.BlockSpec((1, block_q, LANES), row_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq_head_major",
    )(qh, kh, vh, doh, lse, delta)

    # ---- dK/dV: grid (B·Hkv, kv, group·q) — the innermost dim walks every
    # q block of every head in the GQA group, so dK/dV accumulate directly
    # in grouped-head form (never materializing (B, H, T, D)). ----
    inner_steps = group * q_steps

    def q_map2(b, ki, j):
        batch = b // Hkv
        kvh = b % Hkv
        g = j // q_steps
        qi = j % q_steps
        return (batch * H + kvh * group + g, qi, 0)

    def kv_map2(b, ki, j):
        return (b, ki, 0)

    dkv_kernel = functools.partial(
        _fa_dkv_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, q_steps=q_steps,
        inner_steps=inner_steps, q_offset=q_offset, kv_len=T,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * Hkv, kv_steps, inner_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map2),
            pl.BlockSpec((1, block_q, D), q_map2),
            pl.BlockSpec((1, block_q, LANES), q_map2),
            pl.BlockSpec((1, block_q, LANES), q_map2),
            pl.BlockSpec((1, block_k, D), kv_map2),
            pl.BlockSpec((1, block_k, D), kv_map2),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), kv_map2),
            pl.BlockSpec((1, block_k, D), kv_map2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, Tp, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Tp, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv_head_major",
    )(qh, doh, lse, delta, kh, vh)

    dq = jnp.transpose(dq[:, :S].reshape(B, H, S, D), (0, 2, 1, 3))
    dk = jnp.transpose(dk[:, :T].reshape(B, Hkv, T, D), (0, 2, 1, 3))
    dv = jnp.transpose(dv[:, :T].reshape(B, Hkv, T, D), (0, 2, 1, 3))
    return dq, dk, dv


# --------------------------------------------------------------------- #
# entry points: the layout is chosen by shape (``heads_per_block``)
# --------------------------------------------------------------------- #
def flash_attention_fwd(
    q: jax.Array,              # (B, S, H, D)
    k: jax.Array,              # (B, T, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Forward kernel returning (out (B,S,H,D), lse fp32): lse is
    (B, H/hb, hb, S') on the lane-dense path (S' the padded length), and
    (B*H, S) on the head-major one; ``flash_attention_bwd`` takes it as
    it is."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    kw = dict(causal=causal, window=window, softcap=softcap, q_offset=q_offset,
              block_q=block_q, block_k=block_k, interpret=interpret)
    heads = heads_per_block(H, Hkv, D)
    if heads:
        return _fwd_lane_dense(q, k, v, heads=heads, **kw)
    return _fwd_head_major(q, k, v, **kw)


def flash_attention(
    q: jax.Array,              # (B, S, H, D)
    k: jax.Array,              # (B, T, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """The output alone, for a call with no backward (a prefill).  A
    causal call runs the head-major forward whatever its shape: at its
    128 × 128 tiles the lane-dense blocks' DMAs, 128 rows of 256 bytes
    each, cost more than the transposes around the head-major kernel
    (qwen2-7b widths cut to 4 layers, 1 × 4096 prefill on a TPU v5e:
    104.0 ms against 108.5), and without a backward nothing reads the
    lane-dense statistics."""
    fwd = _fwd_head_major if causal else flash_attention_fwd
    out, _ = fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def flash_attention_bwd(
    q: jax.Array,              # (B, S, H, D)
    k: jax.Array,              # (B, T, Hkv, D)
    v: jax.Array,
    out: jax.Array,            # (B, S, H, D) forward output
    lse: jax.Array,            # the forward's lse, as it returned it
    do: jax.Array,             # (B, S, H, D) output cotangent
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Returns (dq, dk, dv) in the input dtypes."""
    H, D = q.shape[2:]
    kw = dict(causal=causal, window=window, softcap=softcap, q_offset=q_offset,
              block_q=block_q, block_k=block_k, interpret=interpret)
    heads = heads_per_block(H, k.shape[2], D)
    if heads:
        return _bwd_lane_dense(q, k, v, out, lse, do, heads=heads, **kw)
    return _bwd_head_major(q, k, v, out, lse, do, **kw)
