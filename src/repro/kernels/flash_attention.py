"""Pallas TPU flash attention (forward + backward).

TPU-native adaptation of TransformerEngine-class fused attention:
  * grid (batch·heads, q_blocks, kv_blocks) — kv innermost so VMEM scratch
    accumulators (running max / denom / out) carry across kv steps, using
    the sequential-grid semantics of TPU Pallas.
  * BlockSpec tiles: (block_q × head_dim) for Q/out, (block_k × head_dim)
    for K/V.  The caller picks the tile (``ops.attention_blocks``): large
    for bidirectional calls, whose fixed per-grid-step cost would
    otherwise dominate, and 128 × 128 for causal and windowed calls,
    whose kernels skip dead tiles at that granularity.  A step's VMEM
    holds the double-buffered Q, K, V (and dO) tiles plus a few
    (block_q × block_k) fp32 score-sized temporaries — 4 MB each at
    1024 × 1024, which the TPU compiler places in VMEM without a raised
    limit (tests/test_tpu_compile.py compiles the ESM-2 cell's shape).
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

Per-row statistics (the forward's LSE, the backward's LSE and Δ) cross
the kernel boundary as (B·H, S, 128) arrays replicated over one lane tile,
so every block's last two dims are (block_q, 128): a (block_q,) row vector
is not a block shape the TPU compiler accepts.  The saved residual is the
compact (B·H, S) LSE.

Backward pass (FlashAttention-2 style):
  * Δ_i = Σ_d dO_id·O_id per q row — one fused XLA reduction.
  * ``_fa_dq_kernel``     — grid (B·H, q_blocks, kv_blocks); recomputes
    block probabilities from the saved per-row LSE and accumulates dQ in
    VMEM scratch across kv steps.
  * ``_fa_dkv_kernel``    — grid (B·Hkv, kv_blocks, group·q_blocks); the
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
LANES = 128  # per-row statistics travel replicated over one lane tile


def _needs_mask(*, causal, window, kv_len, kv_padded):
    """Static: can any entry of any tile be masked?  A bidirectional,
    unwindowed call over keys that fill whole tiles has every key live,
    so its forward kernel builds no per-element mask."""
    return causal or window > 0 or kv_len != kv_padded


def _block_mask(qi, ki, *, block_q, block_k, causal, window, q_offset, kv_len):
    """(block_q, block_k) validity mask for the (qi, ki) tile."""
    q_pos = (
        qi * block_q
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        + q_offset
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
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


# --------------------------------------------------------------------- #
# forward
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
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ki == kv_steps - 1)
    def _final():
        l = l_scr[...]
        denom = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        # per-row logsumexp residual for the backward pass (fully-masked
        # rows get lse ≈ NEG_INF, which the bwd kernels treat as inert),
        # replicated over a full lane tile: a (block_q,) row vector is not
        # a block shape the TPU compiler accepts
        lse_ref[0] = jnp.broadcast_to(m_scr[...] + jnp.log(denom), lse_ref.shape[1:])


def _head_major(x):
    """(B, S, H, D) -> (B*H, S, D)."""
    B, S, H, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, S, D)


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
    """Forward kernel returning (out (B,S,H,D), lse (B*H, S) fp32)."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
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
        name="flash_attention_fwd",
    )(qh, kh, vh)
    out, lse = out[:, :S], lse[:, :S, 0]
    return jnp.transpose(out.reshape(B, H, S, D), (0, 2, 1, 3)), lse


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
    out, _ = flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


# --------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------- #
def _recompute_p_ds(
    q, k, v, do, lse, delta, qi, ki, *,
    scale, causal, window, softcap, block_q, block_k, q_offset, kv_len,
):
    """Shared bwd math: recompute probabilities + pre-softcap score grads.

    Returns (p, ds) both (bq, bk) fp32; ds already includes the logit
    scale so dq = ds @ k and dk = dsᵀ @ q need no further scaling.
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # pre-softcap scores
    if softcap > 0.0:
        t = jnp.tanh(s / softcap)
        z = softcap * t                         # logits
    else:
        z = s
    mask = _block_mask(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, q_offset=q_offset, kv_len=kv_len,
    )
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
        _, ds = _recompute_p_ds(
            q, k, v, do, lse, delta, qi, ki,
            scale=scale, causal=causal, window=window, softcap=softcap,
            block_q=block_q, block_k=block_k, q_offset=q_offset, kv_len=kv_len,
        )
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    live = _block_live(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, q_offset=q_offset,
    )
    if live is None:
        _accumulate()
    else:
        pl.when(live)(_accumulate)

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
        p, ds = _recompute_p_ds(
            q, k, v, do, lse, delta, qi, ki,
            scale=scale, causal=causal, window=window, softcap=softcap,
            block_q=block_q, block_k=block_k, q_offset=q_offset, kv_len=kv_len,
        )
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                        # (bk, D)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    live = _block_live(
        qi, ki, block_q=block_q, block_k=block_k, causal=causal,
        window=window, q_offset=q_offset,
    )
    if live is None:
        _accumulate()
    else:
        pl.when(live)(_accumulate)

    @pl.when(j == inner_steps - 1)
    def _final():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q: jax.Array,              # (B, S, H, D)
    k: jax.Array,              # (B, T, Hkv, D)
    v: jax.Array,
    out: jax.Array,            # (B, S, H, D) forward output
    lse: jax.Array,            # (B*H, S) fp32 forward residual
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
        name="flash_attention_dq",
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

    def row_map2(b, ki, j):
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
            pl.BlockSpec((1, block_q, LANES), row_map2),
            pl.BlockSpec((1, block_q, LANES), row_map2),
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
        name="flash_attention_dkv",
    )(qh, doh, lse, delta, kh, vh)

    dq = jnp.transpose(dq[:, :S].reshape(B, H, S, D), (0, 2, 1, 3))
    dk = jnp.transpose(dk[:, :T].reshape(B, Hkv, T, D), (0, 2, 1, 3))
    dv = jnp.transpose(dv[:, :T].reshape(B, Hkv, T, D), (0, 2, 1, 3))
    return dq, dk, dv
