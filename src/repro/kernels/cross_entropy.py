"""Pallas TPU fused vocab-softmax cross-entropy (forward + backward).

For 128k–256k vocabularies the (tokens × vocab) logits tensor is the single
largest training activation (llama3-405b train_4k: 1M × 128k fp32 = 0.5 TB
globally).  This kernel fuses the output projection with an online
log-sum-exp so full logits never reach HBM:

  grid (token_blocks, vocab_blocks) — vocab innermost; per step:
    logits_blk = h_blk @ W_blk            (bt × bv on the MXU)
    online max / sumexp update            (VMEM scratch, fp32)
    gather target logit if it falls in this vocab block
  final step emits per-token  loss = lse - logit[target].

The MXU takes the hidden and weight tiles in their own dtype (bf16 in
training) with fp32 accumulation; softmax statistics stay fp32.  The
vocab block halves for wide models (``_fit_block_v``) so the (D × bv)
weight tile, double-buffered, and the backward's fp32 (D × bv)
accumulator fit the default 16 MiB of scoped VMEM: bv = 512 at
D = 1280, 256 at D = 3584.  Per-token vectors (targets, loss, LSE and
their cotangents) cross the kernel boundary as (T, 1) columns: a 1-D
(block_t,) block does not match the layout XLA gives a 1-D array.

Backward: the O(T) residual is the per-token LSE; block logits are
recomputed on the MXU and the softmax gradient

  dlogits = (g_loss + g_lse)·softmax − g_loss·onehot(target)

is contracted immediately, so the (tokens × vocab) gradient never
materializes alongside full logits.  Two kernels (TPU grids revisit an
output block only along the innermost dim, so each contraction gets the
loop order that makes its accumulator VMEM-resident):

  * ``_ce_dh_kernel``  — grid (token_blocks, vocab_blocks): dH += dlogits Wᵀ
  * ``_ce_dw_kernel``  — grid (vocab_blocks, token_blocks): dW += Hᵀ dlogits

The custom-VJP dispatch wiring lives in ``ops.py``; the jnp blockwise
implementation there remains the CPU/fallback training path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.tiling import pad_dim, pick_block

NEG_INF = -1e30


def _fit_block_v(d: int, block_v: int) -> int:
    """Halve the vocab block (down to one lane tile) until the (D, bv)
    weight tile and the backward's (D, bv) fp32 accumulator fit the
    compiler's default 16 MiB of scoped VMEM with double buffering."""
    while block_v > 128 and d * block_v * 12 > 12 << 20:
        block_v //= 2
    return block_v


def _mxu(a, b, contract):
    """fp32-accumulated matmul of two tiles in their own dtype.  bf16 x bf16
    products are exact in the fp32 accumulator, so a caller's ``highest``
    matmul precision has nothing to add there — and the TPU compiler has no
    fp32-precision matmul of bf16 operands: pin the default for them."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32,
        precision=(
            jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
        ),
    )


def _ce_kernel(
    h_ref, w_ref, tgt_ref,
    loss_ref, lse_ref,
    m_scr, l_scr, t_scr,
    *,
    block_t: int,
    block_v: int,
    v_steps: int,
    vocab: int,
):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        t_scr[...] = jnp.full_like(t_scr, NEG_INF)

    h = h_ref[...]                                  # (bt, D)
    w = w_ref[...]                                  # (D, bv)
    logits = _mxu(h, w, ((1,), (0,)))              # (bt, bv)
    # mask vocab padding (last block may cover padded ids)
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    logits = jnp.where(col < vocab, logits, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    l_scr[...] = jnp.exp(m_prev - m_new) * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new

    hit = col == tgt_ref[...]                        # tgt: (bt, 1)
    t_here = jnp.max(jnp.where(hit, logits, NEG_INF), axis=-1, keepdims=True)
    t_scr[...] = jnp.maximum(t_scr[...], t_here)

    @pl.when(vi == v_steps - 1)
    def _final():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        loss_ref[...] = lse - t_scr[...]
        lse_ref[...] = lse


def fused_cross_entropy(
    hidden: jax.Array,     # (T, D)
    w_out: jax.Array,      # (D, Vpad)
    targets: jax.Array,    # (T,) int32
    *,
    vocab: int = 0,        # true vocab (<= Vpad); 0 -> Vpad
    block_t: int = 128,
    block_v: int = 512,
    interpret: bool = False,
):
    T, D = hidden.shape
    Vp = w_out.shape[1]
    vocab = vocab or Vp
    # non-multiple dims: zero-pad token rows (outputs sliced below) and
    # vocab columns (masked in-kernel via col < vocab)
    block_t, Tp = pick_block(T, block_t)
    block_v, Vpp = pick_block(Vp, _fit_block_v(D, block_v))
    v_steps = Vpp // block_v
    hidden_p = pad_dim(hidden, 0, Tp)
    w_p = pad_dim(w_out, 1, Vpp)
    tgt_p = pad_dim(targets, 0, Tp)[:, None]
    kernel = functools.partial(
        _ce_kernel,
        block_t=block_t,
        block_v=block_v,
        v_steps=v_steps,
        vocab=vocab,
    )
    loss, lse = pl.pallas_call(
        kernel,
        grid=(Tp // block_t, v_steps),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((D, block_v), lambda ti, vi: (0, vi)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        interpret=interpret,
        name="cross_entropy_fwd",
    )(hidden_p, w_p, tgt_p)
    return loss[:T, 0], lse[:T, 0]


# --------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------- #
def _block_dlogits(h, w, tgt, lse, gl, glse, vi, *, block_t, block_v, vocab):
    """Recompute one (bt, bv) logits block from the saved LSE and form the
    fused softmax gradient  (g_loss + g_lse)·p − g_loss·onehot  (fp32)."""
    logits = _mxu(h, w, ((1,), (0,)))
    col = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    valid = col < vocab
    # exponent clamped at 0 (p <= 1 mathematically) so padded token rows —
    # whose lse slot is zero-padded but whose g_loss/g_lse are zero — stay
    # finite instead of overflowing
    p = jnp.where(
        valid, jnp.exp(jnp.minimum(jnp.where(valid, logits, 0.0) - lse, 0.0)), 0.0
    )
    onehot = jnp.where(valid & (col == tgt), 1.0, 0.0)
    return (gl + glse) * p - gl * onehot


def _ce_dh_kernel(
    h_ref, w_ref, tgt_ref, lse_ref, gl_ref, glse_ref,
    dh_ref,
    acc_scr,
    *,
    block_t: int,
    block_v: int,
    v_steps: int,
    vocab: int,
):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    h = h_ref[...]
    w = w_ref[...]
    dlogits = _block_dlogits(
        h, w, tgt_ref[...], lse_ref[...], gl_ref[...], glse_ref[...], vi,
        block_t=block_t, block_v=block_v, vocab=vocab,
    )
    acc_scr[...] += _mxu(dlogits.astype(w.dtype), w, ((1,), (1,)))  # (bt, D)

    @pl.when(vi == v_steps - 1)
    def _final():
        dh_ref[...] = acc_scr[...].astype(dh_ref.dtype)


def _ce_dw_kernel(
    h_ref, w_ref, tgt_ref, lse_ref, gl_ref, glse_ref,
    dw_ref,
    acc_scr,
    *,
    block_t: int,
    block_v: int,
    t_steps: int,
    vocab: int,
):
    vi = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    h = h_ref[...]
    w = w_ref[...]
    dlogits = _block_dlogits(
        h, w, tgt_ref[...], lse_ref[...], gl_ref[...], glse_ref[...], vi,
        block_t=block_t, block_v=block_v, vocab=vocab,
    )
    acc_scr[...] += _mxu(h, dlogits.astype(h.dtype), ((0,), (0,)))  # (D, bv)

    @pl.when(ti == t_steps - 1)
    def _final():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


def fused_cross_entropy_bwd(
    hidden: jax.Array,     # (T, D)
    w_out: jax.Array,      # (D, Vpad)
    targets: jax.Array,    # (T,) int32
    lse: jax.Array,        # (T,) fp32 forward residual
    g_loss: jax.Array,     # (T,) cotangent of per-token loss
    g_lse: jax.Array,      # (T,) cotangent of the lse output
    *,
    vocab: int = 0,
    block_t: int = 128,
    block_v: int = 512,
    interpret: bool = False,
):
    """Returns (dh (T, D), dw (D, Vpad)) in the input dtypes."""
    T, D = hidden.shape
    Vp = w_out.shape[1]
    vocab = vocab or Vp
    block_t, Tp = pick_block(T, block_t)
    block_v, Vpp = pick_block(Vp, _fit_block_v(D, block_v))
    t_steps = Tp // block_t
    v_steps = Vpp // block_v
    # padded token rows carry zero loss/lse cotangents -> zero dlogits;
    # padded vocab columns are masked via col < vocab
    hidden = pad_dim(hidden, 0, Tp)
    w_pad = pad_dim(w_out, 1, Vpp)
    targets = pad_dim(targets, 0, Tp)[:, None]
    lse = pad_dim(lse, 0, Tp)[:, None]
    gl = pad_dim(g_loss.astype(jnp.float32), 0, Tp)[:, None]
    glse = pad_dim(g_lse.astype(jnp.float32), 0, Tp)[:, None]

    dh_kernel = functools.partial(
        _ce_dh_kernel,
        block_t=block_t, block_v=block_v, v_steps=v_steps, vocab=vocab,
    )
    dh = pl.pallas_call(
        dh_kernel,
        grid=(t_steps, v_steps),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((D, block_v), lambda ti, vi: (0, vi)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((Tp, D), hidden.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, D), jnp.float32)],
        interpret=interpret,
        name="cross_entropy_dh",
    )(hidden, w_pad, targets, lse, gl, glse)

    dw_kernel = functools.partial(
        _ce_dw_kernel,
        block_t=block_t, block_v=block_v, t_steps=t_steps, vocab=vocab,
    )
    dw = pl.pallas_call(
        dw_kernel,
        grid=(v_steps, t_steps),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((D, block_v), lambda vi, ti: (0, vi)),
            pl.BlockSpec((block_t, 1), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda vi, ti: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((D, block_v), lambda vi, ti: (0, vi)),
        out_shape=jax.ShapeDtypeStruct((D, Vpp), w_out.dtype),
        scratch_shapes=[pltpu.VMEM((D, block_v), jnp.float32)],
        interpret=interpret,
        name="cross_entropy_dw",
    )(hidden, w_pad, targets, lse, gl, glse)
    return dh[:T], dw[:, :Vp]
