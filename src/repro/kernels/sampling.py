"""Pallas TPU fused sampling: per-slot top-k/top-p filter + categorical.

One kernel call samples the next token for every serving slot from a
(B, V) logit panel, with *heterogeneous* per-slot sampling params —
temperature, top-k, top-p and PRNG state are (B,) vectors, so a batch can
mix greedy protein-embedding traffic with high-temperature molecule
sampling (the MolMIM workload) in a single jitted decode step.  Grid is
(B,); each step owns one slot's full (padded) vocab row in VMEM and
writes two scalars: the sampled token id and its log-probability.

Three design points make this a single fused pass with no sort and no
host involvement:

* **Dual bisection thresholds.**  Top-k and top-p both reduce to "keep
  ``z >= tau``" for a per-row threshold.  Instead of sorting the vocab
  (no Mosaic lowering, O(V log V)), ``tau_k`` / ``tau_p`` are found by a
  fixed 32-iteration bisection over the logit range, maintaining the
  invariants ``count(z >= lo_k) >= k`` and ``mass(z >= lo_p) >= p·Z``
  — each iteration is two masked VMEM reductions over the row.  32
  f32 halvings exhaust float resolution, so the kept set matches the
  sort-based oracle (``ref.sample_ref``) except for values within one
  ulp of the k-th/top-p boundary.

* **Counter-based hash PRNG.**  Noise for slot ``b`` at generation step
  ``t`` is ``fmix32(fmix32(seed_b + C0) ^ t·C1) ^ i·C2`` pushed through
  the murmur3 finalizer — a pure function of (request seed, token index,
  vocab id).  No carried PRNG state, no dependence on batch composition
  or slot index: the same request sampled in any slot of any batch mix
  reproduces the same tokens, and the identical integer math runs in the
  XLA fallback, so ``xla`` and ``pallas`` agree token-for-token.

* **Gumbel-max selection.**  ``argmax(z + g)`` over the kept set samples
  the renormalized categorical without ever normalizing — one more VMEM
  reduction.  Greedy rows (``temperature <= 0``) take the same path with
  zero noise and no filter, which degrades exactly to first-index
  ``argmax`` (bit-identical to ``jnp.argmax`` greedy decoding).

The row math lives in ``_sample_rows`` and is shared verbatim by the
kernel body (rows=1) and the batched XLA fallback (rows=B), keeping the
two implementations in lockstep by construction.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
_LANES = 128
_BISECT_ITERS = 32


# --------------------------------------------------------------------- #
# counter-based noise (murmur3 fmix32 stream)
# --------------------------------------------------------------------- #
def _fmix32(h: jax.Array) -> jax.Array:
    """murmur3 finalizer: full-avalanche 32-bit mix (uint32 in/out)."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def gumbel_noise(seed: jax.Array, step: jax.Array, idx: jax.Array) -> jax.Array:
    """Gumbel(0,1) noise as a pure function of (seed, step, vocab idx).

    ``seed``/``step``: (R, 1) uint32; ``idx``: (R, V) uint32.  The same
    (seed, step, idx) triple yields the same noise on every backend and
    in every batch composition — this is what makes fixed-seed sampling
    reproducible regardless of which slots share the decode step.
    """
    h = _fmix32(seed + jnp.uint32(0x9E3779B9))
    h = _fmix32(h ^ (step * jnp.uint32(0x85EBCA77)))
    u = _fmix32(h ^ (idx * jnp.uint32(0x9E3779B1)))
    # top 24 bits -> uniform strictly inside (0, 1); +0.5 keeps log finite
    # (through int32: the 24-bit value is the same, and the TPU compiler
    # converts signed integers to float, not unsigned ones)
    top = jax.lax.bitcast_convert_type(u >> jnp.uint32(8), jnp.int32)
    uf = (top.astype(jnp.float32) + 0.5) * (1.0 / (1 << 24))
    return -jnp.log(-jnp.log(uf))


# --------------------------------------------------------------------- #
# shared row math (kernel body with rows=1, XLA fallback with rows=B)
# --------------------------------------------------------------------- #
def _sample_rows(x, temp, top_k, top_p, seed, step, idx, *,
                 iters: int = _BISECT_ITERS):
    """Sample one token per row of ``x``.

    ``x``: (R, *V) f32 raw logits (padded / masked-vocab entries at
    ``NEG_INF``), the vocab spread over one or more trailing axes;
    ``temp``/``top_p`` f32, ``top_k`` i32 (``0`` disables),
    ``seed``/``step`` uint32, each (R, 1, ...) with x's rank; ``idx`` i32
    vocab ids shaped like x.  Returns ``(tok i32, logp f32)``, each
    (R, 1, ...), where
    ``logp`` is the log-probability of the chosen token under the
    filtered, temperature-scaled, renormalized distribution (for greedy
    rows: under the full T=1 softmax).
    """
    V = math.prod(x.shape[1:])
    ax = tuple(range(1, x.ndim))          # the vocab axes
    valid = x > NEG_INF / 2
    greedy = temp <= 0.0
    t = jnp.where(greedy, 1.0, temp)
    z = jnp.where(valid, x / t, NEG_INF)
    m = jnp.max(z, axis=ax, keepdims=True)
    mn = jnp.min(jnp.where(valid, z, m), axis=ax, keepdims=True)
    e = jnp.where(valid, jnp.exp(z - m), 0.0)
    Z = jnp.sum(e, axis=ax, keepdims=True)

    k = jnp.where(top_k <= 0, jnp.int32(V), jnp.clip(top_k, 1, V))
    k = k.astype(jnp.float32)
    p = jnp.clip(top_p, 1e-9, 1.0)
    pZ = p * Z
    hi0 = m + 1.0

    def body(_, c):
        lo_k, hi_k, lo_p, hi_p = c
        mid = 0.5 * (lo_k + hi_k)
        cnt = jnp.sum(jnp.where(z >= mid, 1.0, 0.0), axis=ax, keepdims=True)
        ok = cnt >= k
        lo_k = jnp.where(ok, mid, lo_k)
        hi_k = jnp.where(ok, hi_k, mid)
        mid = 0.5 * (lo_p + hi_p)
        mass = jnp.sum(jnp.where(z >= mid, e, 0.0), axis=ax, keepdims=True)
        ok = mass >= pZ
        lo_p = jnp.where(ok, mid, lo_p)
        hi_p = jnp.where(ok, hi_p, mid)
        return lo_k, hi_k, lo_p, hi_p

    def _filtered(_):
        lo_k, _, lo_p, _ = jax.lax.fori_loop(0, iters, body, (mn, hi0, mn, hi0))
        # the intersection of both filters; never excludes the argmax token
        tau = jnp.minimum(jnp.maximum(lo_k, lo_p), m)
        return tau, gumbel_noise(seed, step, idx.astype(jnp.uint32))

    def _argmax_only(_):
        return mn, jnp.zeros_like(x)

    # all-greedy rows (the Pallas kernel sees one row per grid step, the
    # XLA path a whole batch): skip the bisection sweeps and the noise
    # hash entirely — greedy decode costs what argmax costs
    tau, g = jax.lax.cond(jnp.all(greedy), _argmax_only, _filtered, None)
    tau = jnp.where(greedy, mn, tau)
    g = jnp.where(greedy, 0.0, g)
    keep = valid & (z >= tau)
    y = jnp.where(keep, z + g, NEG_INF)
    ymax = jnp.max(y, axis=ax, keepdims=True)
    # first index attaining the max — jnp.argmax's tie-break, so the
    # greedy path is bit-identical to argmax decoding
    tok = jnp.min(
        jnp.where(y == ymax, idx, jnp.int32(V)), axis=ax, keepdims=True
    )
    z_tok = jnp.max(jnp.where(idx == tok, z, NEG_INF), axis=ax, keepdims=True)
    Zf = jnp.sum(jnp.where(keep, e, 0.0), axis=ax, keepdims=True)
    logp = z_tok - m - jnp.log(jnp.maximum(Zf, 1e-30))
    return tok.astype(jnp.int32), logp


def sample_xla(logits, temperature, top_k, top_p, seed, step):
    """Batched XLA fallback: the shared row math over all rows at once."""
    B, V = logits.shape
    idx = jnp.broadcast_to(jnp.arange(V, dtype=jnp.int32)[None], (B, V))
    tok, logp = _sample_rows(
        logits.astype(jnp.float32),
        temperature.astype(jnp.float32)[:, None],
        top_k.astype(jnp.int32)[:, None],
        top_p.astype(jnp.float32)[:, None],
        seed.astype(jnp.uint32)[:, None],
        step.astype(jnp.uint32)[:, None],
        idx,
    )
    return tok[:, 0], logp[:, 0]


# --------------------------------------------------------------------- #
# Pallas kernel
# --------------------------------------------------------------------- #
def _sample_kernel(temp_ref, topk_ref, topp_ref, seed_ref, step_ref,
                   x_ref, tok_ref, logp_ref):
    b = pl.program_id(0)
    x = x_ref[...]                                        # (1, Vp/128, 128)
    idx = (
        jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
    )

    def param(ref, dtype):
        return jnp.full((1, 1, 1), ref[b], dtype)

    tok, logp = _sample_rows(
        x,
        param(temp_ref, jnp.float32),
        param(topk_ref, jnp.int32),
        param(topp_ref, jnp.float32),
        jax.lax.bitcast_convert_type(param(seed_ref, jnp.int32), jnp.uint32),
        jax.lax.bitcast_convert_type(param(step_ref, jnp.int32), jnp.uint32),
        idx,
    )
    tok_ref[...] = jnp.broadcast_to(tok, tok_ref.shape)
    logp_ref[...] = jnp.broadcast_to(logp, logp_ref.shape)


def fused_sample(
    logits: jax.Array,       # (B, V) — any float dtype
    temperature: jax.Array,  # (B,) f32; <= 0 means greedy argmax
    top_k: jax.Array,        # (B,) i32; 0 disables
    top_p: jax.Array,        # (B,) f32; 1.0 disables
    seed: jax.Array,         # (B,) per-request PRNG seed
    step: jax.Array,         # (B,) generation index (tokens emitted so far)
    *,
    interpret: bool = False,
):
    """Fused per-slot filter + categorical: one kernel, (B,) heterogeneous
    params, returns ``(tok (B,) i32, logp (B,) f32)``.

    Each grid step holds one slot's (padded) vocab row in VMEM as a dense
    (Vp/128, 128) tile — 0.6 MB at a 152k vocab — so whole-row
    reductions run over full vregs.  Padding columns are ``NEG_INF`` so
    they are invisible to the filter, the softmax mass and the gumbel
    argmax.  The per-slot params ride in SMEM (scalar prefetch); the two
    results come back replicated over one lane tile.
    """
    B, V = logits.shape
    Vp = max(_LANES, V + (-V % _LANES))
    x = logits.astype(jnp.float32)
    if Vp != V:
        x = jnp.pad(x, ((0, 0), (0, Vp - V)), constant_values=NEG_INF)
    x = x.reshape(B, Vp // _LANES, _LANES)

    row = lambda b, *_: (b, 0, 0)  # noqa: E731
    tok, logp = pl.pallas_call(
        _sample_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,  # temperature, top_k, top_p, seed, step
            grid=(B,),
            in_specs=[pl.BlockSpec((1, Vp // _LANES, _LANES), row)],
            out_specs=[
                pl.BlockSpec((1, 1, _LANES), row),
                pl.BlockSpec((1, 1, _LANES), row),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(
        temperature.astype(jnp.float32),
        top_k.astype(jnp.int32),
        top_p.astype(jnp.float32),
        jax.lax.bitcast_convert_type(seed.astype(jnp.uint32), jnp.int32),
        jax.lax.bitcast_convert_type(step.astype(jnp.uint32), jnp.int32),
        x,
    )
    return tok[:, 0, 0], logp[:, 0, 0]
