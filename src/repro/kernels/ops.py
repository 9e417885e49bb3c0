"""Kernel dispatch + memory-bounded jnp implementations.

Three implementations exist for each hot-spot:
  * ``pallas``  — the TPU kernel (``flash_attention.py`` etc.), used on TPU.
                  Attention and cross-entropy are differentiable end-to-end:
                  ``jax.custom_vjp`` wrappers here pair the forward kernels
                  with their Pallas backward kernels, so ``impl="pallas"``
                  (and ``auto`` on TPU) is trainable.
  * ``xla``     — blockwise/scanned jnp with the same O(block) memory
                  behavior, autodiff-able; the CPU and fallback path.
  * ``naive``   — the oracle in ``ref.py`` (tests only).

``impl="auto"`` resolves to pallas on TPU, xla elsewhere.
``impl="pallas_interpret"`` runs the Pallas kernels (fwd + bwd) in
interpret mode on any backend — the CPU-verifiable training path used by
the gradient test sweeps.  See kernels/README.md for the dispatch table.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import tiling
from repro.kernels import flash_attention as _fa
from repro.kernels import cross_entropy as _ce
from repro.kernels import rope as _rope
from repro.kernels.rmsnorm import layernorm as _ln_pallas
from repro.kernels.rmsnorm import rmsnorm as _rms_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

NEG_INF = -1e30

# kv block of the xla blockwise attention scan: the fp32 (m, l, acc) carries
# round-trip HBM once per block, so carry traffic scales 1/block
XLA_ATTN_BLOCK_K = 2048

_IMPLS = ("auto", "pallas", "pallas_interpret", "xla", "naive")


def _resolve(impl: str, interpret: bool) -> Tuple[str, bool]:
    if impl not in _IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; expected one of {_IMPLS}")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas_interpret":
        return "pallas", True
    return impl, interpret


def is_pallas(impl: str) -> bool:
    """Whether ``impl`` runs the Pallas kernels here: a mesh must then run
    them per shard (``ShardingCtx.per_shard``)."""
    return _resolve(impl, False)[0] == "pallas"


# --------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------- #
def _blockwise_attention_xla(q, k, v, *, causal, window, softcap, q_offset):
    """Flash-attention semantics as a lax.scan over kv blocks (O(S·block) mem).

    What bounds its HBM traffic:
      * kv blocks of ``XLA_ATTN_BLOCK_K`` rows;
      * probability blocks are cast to the input dtype (bf16) before the
        PV matmul with fp32 accumulation — halves the largest per-block
        buffer, mirroring what the MXU kernel does;
      * GQA K/V are NOT repeated — the einsum runs in grouped-head form.
    """
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    # zero-pad the kv tail block; masked below via k_pos < T
    block_k, Tp = tiling.pick_block(T, XLA_ATTN_BLOCK_K)
    k = tiling.pad_dim(k, 1, Tp)
    v = tiling.pad_dim(v, 1, Tp)
    nblk = Tp // block_k
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qg = q.reshape(B, S, Hkv, group, D)
    q_pos = jnp.arange(S) + q_offset

    kb = k.reshape(B, nblk, block_k, Hkv, D)
    vb = v.reshape(B, nblk, block_k, Hkv, D)

    def body(carry, blk):
        m, l, acc = carry                          # (B,Hkv,g,S), ..., (B,Hkv,g,S,D)
        kblk, vblk, ki = blk                       # (B,bk,Hkv,D)
        s = jnp.einsum(
            "bshgd,bthd->bhgst", qg, kblk, preferred_element_type=jnp.float32
        ) * scale                                   # (B,Hkv,g,S,bk) fp32
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * block_k + jnp.arange(block_k)
        mask = jnp.broadcast_to(k_pos[None, :] < T, (S, block_k))
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(m_new[..., None] <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgst,bthd->bhgsd", p.astype(q.dtype), vblk,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, group, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, S), jnp.float32)
    a0 = jnp.zeros((B, Hkv, group, S, D), jnp.float32)
    xs = (
        jnp.moveaxis(kb, 1, 0),
        jnp.moveaxis(vb, 1, 0),
        jnp.arange(nblk),
    )
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,Hkv,g,S,D)
    out = out.reshape(B, H, S, D)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


# (block_q, block_k) of a bidirectional, unwindowed Pallas attention call
# (the ESM-2/BERT encoders): large tiles amortise each grid step's fixed
# cost; see kernels/README.md "Tiles".
BIDIRECTIONAL_BLOCKS = (1024, 1024)


def _bidirectional_block(n: int, block: int) -> int:
    """The largest of block, block/2, ... 128 that is at most ``n`` and
    tiles ``n`` rounded up to 128, so every tile is 128-aligned and no
    length pads further than 128-row tiles would.  A length of at most
    128, or a multiple of 128 up to ``block``, is one tile."""
    padded = n + (-n % 128)
    if n <= 128 or (n == padded and n <= block):
        return n
    while padded % block or block > n:
        block //= 2
    return block


def attention_blocks(
    S: int, T: int, *, causal: bool, window: int
) -> Tuple[int, int]:
    """(block_q, block_k) of the Pallas kernels for queries of length S
    over T keys.  Causal and windowed calls (decoder prefill and training)
    keep 128 × 128, the granularity at which their kernels skip dead
    tiles; bidirectional calls take ``BIDIRECTIONAL_BLOCKS``.  A tile is
    capped at its length, as ``tiling.pick_block`` does."""
    if causal or window > 0:
        return tiling.pick_block(S, 128)[0], tiling.pick_block(T, 128)[0]
    bq, bk = BIDIRECTIONAL_BLOCKS
    return _bidirectional_block(S, bq), _bidirectional_block(T, bk)


class _AttnCfg(NamedTuple):
    """Hashable static config for the pallas attention custom-VJP."""

    causal: bool
    window: int
    softcap: float
    q_offset: int
    block_q: int
    block_k: int
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attention_pallas(cfg: _AttnCfg, q, k, v):
    return _fa.flash_attention(
        q, k, v, causal=cfg.causal, window=cfg.window, softcap=cfg.softcap,
        q_offset=cfg.q_offset, block_q=cfg.block_q, block_k=cfg.block_k,
        interpret=cfg.interpret,
    )


def _attention_pallas_fwd(cfg: _AttnCfg, q, k, v):
    out, lse = _fa.flash_attention_fwd(
        q, k, v, causal=cfg.causal, window=cfg.window, softcap=cfg.softcap,
        q_offset=cfg.q_offset, block_q=cfg.block_q, block_k=cfg.block_k,
        interpret=cfg.interpret,
    )
    return out, (q, k, v, out, lse)


def _attention_pallas_bwd(cfg: _AttnCfg, res, do):
    q, k, v, out, lse = res
    return _fa.flash_attention_bwd(
        q, k, v, out, lse, do,
        causal=cfg.causal, window=cfg.window, softcap=cfg.softcap,
        q_offset=cfg.q_offset, block_q=cfg.block_q, block_k=cfg.block_k,
        interpret=cfg.interpret,
    )


_attention_pallas.defvjp(_attention_pallas_fwd, _attention_pallas_bwd)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        block_q, block_k = attention_blocks(
            q.shape[1], k.shape[1], causal=causal, window=window
        )
        cfg = _AttnCfg(
            causal=causal, window=window, softcap=softcap, q_offset=q_offset,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        return _attention_pallas(cfg, q, k, v)
    if impl == "naive":
        return ref.attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap, q_offset=q_offset
        )
    return _blockwise_attention_xla(
        q, k, v, causal=causal, window=window, softcap=softcap, q_offset=q_offset
    )


# --------------------------------------------------------------------- #
# rotary position embedding
# --------------------------------------------------------------------- #
def _rope_xla(x, positions, theta):
    half = x.shape[-1] // 2
    ang = _rope.angles(positions, theta, x.shape[-1])[..., None, :]  # (…, S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rope_pallas(x, positions, theta, interpret):
    return _rope.rope(x, positions, theta, interpret=interpret)


def _rope_pallas_fwd(x, positions, theta, interpret):
    return _rope_pallas(x, positions, theta, interpret), positions


def _rope_pallas_bwd(theta, interpret, positions, dy):
    # a rotation's transpose is the rotation by the opposite angle
    return _rope.rope(dy, positions, theta, sign=-1.0, interpret=interpret), None


_rope_pallas.defvjp(_rope_pallas_fwd, _rope_pallas_bwd)


def rope(x: jax.Array, positions: jax.Array, theta: float, *,
         impl: str = "auto", interpret: bool = False) -> jax.Array:
    """Rotate-half rotary embedding of x (B, S, H, D) at ``positions``
    (S,) or (B, S).  ``pallas`` works on the (B, S, H·D) rows the
    projections write and the attention kernels read (``rope.py``) where
    whole heads fill 128-lane blocks; elsewhere the XLA formula runs."""
    impl, interpret = _resolve(impl, interpret)
    H, D = x.shape[2:]
    if impl == "pallas" and _rope.LANES % D == 0 and H * D % _rope.LANES == 0:
        return _rope_pallas(x, positions, theta, interpret)
    return _rope_xla(x, positions, theta)


def decode_attention(
    q: jax.Array,         # (B, 1, H, D)
    k_cache: jax.Array,   # (B, T, Hkv, D)  — seq dim may be mesh-sharded
    v_cache: jax.Array,
    length: jax.Array,    # (B,) valid cache length per sequence
    *,
    softcap: float = 0.0,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Single-token attention over a (possibly sequence-sharded) KV cache.

    Written as plain reductions over the cache sequence dim: under GSPMD a
    `model`-sharded cache turns max/sum into small all-reduces of per-shard
    statistics — the collective structure of flash-decoding, for free.
    """
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        from repro.kernels.flash_decode import flash_decode

        return flash_decode(
            q, k_cache, v_cache, length.astype(jnp.int32),
            softcap=softcap, interpret=interpret,
        )
    return _decode_attention_xla(q, k_cache, v_cache, length, softcap=softcap)


def _decode_attention_xla(q, k_cache, v_cache, length, *, softcap=0.0):
    B, _, H, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    # grouped-head form: never jnp.repeat the cache (repeating reads the
    # 32k/500k cache `group`× in fp32 — found via decode traffic analysis)
    qg = q[:, 0].reshape(B, Hkv, group, D)
    s = jnp.einsum(
        "bhgd,bthd->bhgt", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale                                          # (B, Hkv, g, T) fp32
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    valid = jnp.arange(T)[None, :] < length[:, None]   # (B, T)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    # empty caches (length 0, e.g. an idle serving slot) yield zeros, the
    # same semantics as the Pallas decode kernels' masked-row guard
    p = jnp.where(m <= NEG_INF / 2, 0.0, p)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhgt,bthd->bhgd",
        (p / jnp.maximum(denom, 1e-30)).astype(q.dtype),
        v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, D).astype(q.dtype)


# --------------------------------------------------------------------- #
# paged KV cache (serving): block-table attention + per-token scatter
# --------------------------------------------------------------------- #
def _gather_pages(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """(num_pages, page, Hkv, D) + (B, n) table -> dense (B, n·page, Hkv, D)."""
    B, n = block_table.shape
    page, Hkv, D = pool.shape[1:]
    flat = jnp.take(pool, block_table.reshape(-1), axis=0)
    return flat.reshape(B, n * page, Hkv, D)


def paged_decode_attention(
    q: jax.Array,            # (B, 1, H, D)
    k_pool: jax.Array,       # (num_pages, page, Hkv, D)
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, pages_per_seq) int32
    length: jax.Array,       # (B,) valid cache length per sequence
    *,
    softcap: float = 0.0,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Single-token attention through a block-table paged KV pool.

    ``pallas`` gathers K/V page tiles by indexing the pool through the
    prefetched block table inside the kernel grid — the (B, T) dense
    cache never materializes.  The ``xla``/``naive`` fallback gathers
    pages into a dense cache and reuses the blockwise decode math
    (correct everywhere, O(B·T) gather — the CPU/testing path).
    """
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        from repro.kernels.paged_attention import paged_flash_decode

        return paged_flash_decode(
            q, k_pool, v_pool, block_table, length.astype(jnp.int32),
            softcap=softcap, interpret=interpret,
        )
    k_cache = _gather_pages(k_pool, block_table)
    v_cache = _gather_pages(v_pool, block_table)
    return _decode_attention_xla(q, k_cache, v_cache, length, softcap=softcap)


def paged_prefill_attention(
    q: jax.Array,            # (B, S, H, D) chunk queries
    k_pool: jax.Array,       # (num_pages, page, Hkv, D)
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, pages_per_seq) int32
    starts: jax.Array,       # (B,) logical position of each chunk's row 0
    lengths: jax.Array,      # (B,) total valid context length (start+valid)
    *,
    softcap: float = 0.0,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Chunk/suffix prefill attention through a block-table paged KV pool.

    Query row ``i`` of batch ``b`` sits at logical position
    ``starts[b] + i`` and attends causally to every cache position
    ``<= starts[b] + i`` (and ``< lengths[b]``) through the block table —
    this is the read side of prefix caching (the chunk attends straight
    into pages shared from the hash index) and of chunked prefill (each
    chunk attends to all previously written chunks plus itself; the
    chunk's own K/V must already be scattered into the pool, see
    ``paged_kv_update_rows``).

    ``pallas`` gathers K/V page tiles through the prefetched block table
    inside the kernel grid; the ``xla``/``naive`` fallback gathers pages
    into a dense cache and applies the shifted causal mask explicitly
    (O(B·S·T) scores — the CPU/testing path; chunks are short).
    """
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        from repro.kernels.paged_attention import paged_flash_prefill

        return paged_flash_prefill(
            q, k_pool, v_pool, block_table,
            starts.astype(jnp.int32), lengths.astype(jnp.int32),
            softcap=softcap, interpret=interpret,
        )
    B, S, H, D = q.shape
    Hkv = k_pool.shape[2]
    group = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    k_cache = _gather_pages(k_pool, block_table)       # (B, T, Hkv, D)
    v_cache = _gather_pages(v_pool, block_table)
    T = k_cache.shape[1]
    qg = q.reshape(B, S, Hkv, group, D)
    s = jnp.einsum(
        "bshgd,bthd->bhgst", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale                                          # (B, Hkv, g, S, T)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = starts[:, None] + jnp.arange(S)[None, :]   # (B, S)
    k_pos = jnp.arange(T)
    mask = (
        (k_pos[None, None, :] <= q_pos[:, :, None])
        & (k_pos[None, None, :] < lengths[:, None, None])
    )                                                  # (B, S, T)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(m <= NEG_INF / 2, 0.0, p)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum(
        "bhgst,bthd->bhgsd", (p / denom).astype(q.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )                                                  # (B, Hkv, g, S, D)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, S, H, D)
    return out.astype(q.dtype)


def paged_kv_update_rows(
    k_pool: jax.Array,     # (num_pages, page, Hkv, D)
    v_pool: jax.Array,
    k_new: jax.Array,      # (S, Hkv, D) chunk K rows (batch-1 chunk)
    v_new: jax.Array,
    page_idx: jax.Array,   # (S,) physical page per row (null page = masked)
    row: jax.Array,        # (S,) row within each page
) -> Tuple[jax.Array, jax.Array]:
    """Scatter a prefill chunk's K/V rows into the page pool.

    O(S) rows of data move regardless of impl, so the jnp scatter IS the
    efficient form on every backend (unlike the per-token decode write,
    where the dense layout's masked select touches O(B·T) and the Pallas
    page rewrite wins).  Masked rows target the null page 0; collisions
    there are harmless garbage.
    """
    k_pool = k_pool.at[page_idx, row].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[page_idx, row].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def paged_kv_update(
    k_pool: jax.Array,     # (num_pages, page, Hkv, D)
    v_pool: jax.Array,
    k_new: jax.Array,      # (B, 1, Hkv, D) decode-token K per slot
    v_new: jax.Array,
    page_idx: jax.Array,   # (B,) physical page holding each slot's write pos
    row: jax.Array,        # (B,) row within the page (pos % page)
    *,
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Insert one decode token per slot at (page_idx, row): O(B·page).

    Replaces the dense layout's O(B·T) one-hot masked select
    (``models/attention.py``).  ``pallas`` rewrites exactly one pool page
    per slot in place (donated pools); ``xla``/``naive`` is the
    equivalent jnp scatter.
    """
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        from repro.kernels.paged_attention import paged_kv_write

        return paged_kv_write(
            k_pool, v_pool, k_new, v_new, page_idx, row, interpret=interpret
        )
    k_pool = k_pool.at[page_idx, row].set(k_new[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[page_idx, row].set(v_new[:, 0].astype(v_pool.dtype))
    return k_pool, v_pool


# --------------------------------------------------------------------- #
# fused sampling (serving): per-slot top-k/top-p filter + categorical
# --------------------------------------------------------------------- #
def sample_tokens(
    logits: jax.Array,       # (B, V) last-position logits
    temperature: jax.Array,  # (B,) f32; <= 0 means greedy argmax
    top_k: jax.Array,        # (B,) i32; 0 disables the top-k filter
    top_p: jax.Array,        # (B,) f32; 1.0 disables the top-p filter
    seed: jax.Array,         # (B,) per-request PRNG seed
    step: jax.Array,         # (B,) generation index (tokens emitted so far)
    *,
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Sample one token per row with heterogeneous per-row params.

    Returns ``(tok (B,) i32, logp (B,) f32)`` — the chosen token and its
    log-probability under the filtered, temperature-scaled, renormalized
    distribution (greedy rows: under the full T=1 softmax).

    Selection runs entirely on device: ``pallas`` is the fused VMEM
    kernel (dual-bisection thresholds + counter-based gumbel-max,
    ``sampling.py``), ``xla`` is the same row math batched over B (the
    two agree token-for-token — the noise stream is a pure integer hash
    of (seed, step, vocab id), not backend PRNG state).  ``naive`` is the
    sort-based oracle in ``ref.py``.  Called inside the serving engine's
    jitted decode step so token selection adds zero host syncs.
    """
    impl, interpret = _resolve(impl, interpret)
    from repro.kernels import sampling as _sp

    if impl == "pallas":
        return _sp.fused_sample(
            logits, temperature, top_k, top_p, seed, step, interpret=interpret
        )
    if impl == "naive":
        return ref.sample_ref(logits, temperature, top_k, top_p, seed, step)
    return _sp.sample_xla(logits, temperature, top_k, top_p, seed, step)


# --------------------------------------------------------------------- #
# norms
#
# The xla paths use custom VJPs engineered so every FULL-SIZE fusion output
# stays in the input dtype (bf16); only per-row statistics are fp32.  The
# autodiff'd fp32-math norm materializes fp32 residual-stream buffers in
# fwd+bwd+remat (in llama3-405b's compiled step, 48% of its HBM traffic).
# This mirrors what the fused Pallas/Apex norm kernels do on real hardware.
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_xla(x, w, eps):
    return ref.rmsnorm_ref(x, w, eps)


def _rms_fwd(x, w, eps):
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)  # (..,1)
    y = (xf * rstd * w.astype(jnp.float32)).astype(x.dtype)
    return y, (x, w, rstd)


def _rms_bwd(eps, res, dy):
    x, w, rstd = res
    D = x.shape[-1]
    dyw = (dy * w).astype(jnp.float32)          # fused: read dy,w -> temp
    xf = x.astype(jnp.float32)
    # per-row scalar: (dy.w . xhat) / D
    c = jnp.sum(dyw * xf, axis=-1, keepdims=True) * (rstd * rstd) / D   # (..,1)
    dx = ((dyw - xf * c) * rstd).astype(x.dtype)
    dw = jnp.sum((dy.astype(jnp.float32)) * xf * rstd, axis=tuple(range(x.ndim - 1)))
    return dx, dw.astype(w.dtype)


_rmsnorm_xla.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layernorm_xla(x, w, b, eps):
    return ref.layernorm_ref(x, w, b, eps)


def _ln_fwd(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    rstd = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    y = xc * rstd * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype), (x, w, mu, rstd)


def _ln_bwd(eps, res, dy):
    x, w, mu, rstd = res
    D = x.shape[-1]
    xhat_f = (x.astype(jnp.float32) - mu) * rstd
    dyw = (dy * w).astype(jnp.float32)
    c1 = jnp.mean(dyw, axis=-1, keepdims=True)
    c2 = jnp.mean(dyw * xhat_f, axis=-1, keepdims=True)
    dx = ((dyw - c1 - xhat_f * c2) * rstd).astype(x.dtype)
    dyf = dy.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    dw = jnp.sum(dyf * xhat_f, axis=axes).astype(w.dtype)
    db = jnp.sum(dyf, axis=axes).astype(w.dtype)
    return dx, dw, db


_layernorm_xla.defvjp(_ln_fwd, _ln_bwd)


# pallas norm kernels are forward-only; pair them with the hand-written
# xla backward formulas above so the pallas paths stay trainable (per-row
# statistics are recomputed in bwd — cheaper than saving them from VMEM)
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm_pallas(x, w, eps, interpret):
    return _rms_pallas(x, w, eps, interpret=interpret)


def _rms_pallas_fwd(x, w, eps, interpret):
    return _rmsnorm_pallas(x, w, eps, interpret), (x, w)


def _rms_pallas_bwd(eps, interpret, res, dy):
    x, w = res
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return _rms_bwd(eps, (x, w, rstd), dy)


_rmsnorm_pallas.defvjp(_rms_pallas_fwd, _rms_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layernorm_pallas(x, w, b, eps, interpret):
    return _ln_pallas(x, w, b, eps, interpret=interpret)


def _ln_pallas_fwd(x, w, b, eps, interpret):
    return _layernorm_pallas(x, w, b, eps, interpret), (x, w, b)


def _ln_pallas_bwd(eps, interpret, res, dy):
    x, w, b = res
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    rstd = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    dx, dw, db = _ln_bwd(eps, (x, w, mu, rstd), dy)
    return dx, dw, (None if b is None else db)


_layernorm_pallas.defvjp(_ln_pallas_fwd, _ln_pallas_bwd)


def rmsnorm(x, w, eps: float = 1e-5, *, impl: str = "auto", interpret: bool = False):
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        return _rmsnorm_pallas(x, w, eps, interpret)
    if impl == "naive":
        return ref.rmsnorm_ref(x, w, eps)
    return _rmsnorm_xla(x, w, eps)


def layernorm(x, w, b=None, eps: float = 1e-5, *, impl: str = "auto", interpret: bool = False):
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        return _layernorm_pallas(x, w, b, eps, interpret)
    if impl == "naive":
        return ref.layernorm_ref(x, w, b, eps)
    if b is None:
        # reuse the 3-arg vjp with a zero bias to keep one code path
        return _layernorm_xla(x, w, jnp.zeros_like(w), eps)
    return _layernorm_xla(x, w, b, eps)


# --------------------------------------------------------------------- #
# fused cross-entropy
# --------------------------------------------------------------------- #
def _blockwise_ce_xla(hidden, w_out, targets, *, vocab, block_v=2048):
    """lse via checkpointed scan over vocab blocks; logits never materialize.

    The matmuls run in the input dtype with fp32 ACCUMULATION
    (preferred_element_type) instead of upcasting `hidden` to fp32 — an
    upfront fp32 cast makes the hidden cotangent fp32 and cascades fp32
    residual-stream buffers through the entire backward pass."""
    T, D = hidden.shape
    Vp = w_out.shape[1]
    # zero-pad the vocab tail; masked below via col < vocab
    block_v, Vpp = tiling.pick_block(Vp, block_v)
    w_pad = tiling.pad_dim(w_out, 1, Vpp)
    nblk = Vpp // block_v
    wb = jnp.moveaxis(w_pad.reshape(D, nblk, block_v), 1, 0)  # (nblk, D, bv)

    def body(_, blk):
        wblk, vi = blk
        logits = jax.lax.dot_general(
            hidden, wblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # (T, bv) fp32
        col = vi * block_v + jnp.arange(block_v)
        logits = jnp.where(col[None, :] < vocab, logits, NEG_INF)
        blk_lse = jax.scipy.special.logsumexp(logits, axis=-1)  # (T,)
        return None, blk_lse

    _, blk_lses = jax.lax.scan(jax.checkpoint(body), None, (wb, jnp.arange(nblk)))
    lse = jax.scipy.special.logsumexp(blk_lses, axis=0)         # (T,)
    w_tgt = jnp.take(w_out, targets, axis=1)                    # (D, T)
    tgt_logit = jnp.einsum(
        "td,dt->t", hidden, w_tgt, preferred_element_type=jnp.float32
    )
    return lse - tgt_logit, lse


class _CECfg(NamedTuple):
    """Hashable static config for the pallas cross-entropy custom-VJP."""

    vocab: int
    block_t: int
    block_v: int
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cross_entropy_pallas(cfg: _CECfg, hidden, w_out, targets):
    return _ce.fused_cross_entropy(
        hidden, w_out, targets, vocab=cfg.vocab,
        block_t=cfg.block_t, block_v=cfg.block_v, interpret=cfg.interpret,
    )


def _cross_entropy_pallas_fwd(cfg: _CECfg, hidden, w_out, targets):
    loss, lse = _cross_entropy_pallas(cfg, hidden, w_out, targets)
    return (loss, lse), (hidden, w_out, targets, lse)


def _cross_entropy_pallas_bwd(cfg: _CECfg, res, g):
    hidden, w_out, targets, lse = res
    g_loss, g_lse = g
    dh, dw = _ce.fused_cross_entropy_bwd(
        hidden, w_out, targets, lse, g_loss, g_lse, vocab=cfg.vocab,
        block_t=cfg.block_t, block_v=cfg.block_v, interpret=cfg.interpret,
    )
    return dh, dw, None  # targets are integer — no cotangent


_cross_entropy_pallas.defvjp(_cross_entropy_pallas_fwd, _cross_entropy_pallas_bwd)


def cross_entropy(
    hidden: jax.Array,
    w_out: jax.Array,
    targets: jax.Array,
    *,
    vocab: int = 0,
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    vocab = vocab or w_out.shape[1]
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        cfg = _CECfg(vocab=vocab, block_t=128, block_v=512, interpret=interpret)
        return _cross_entropy_pallas(cfg, hidden, w_out, targets)
    if impl == "naive":
        return ref.cross_entropy_ref(hidden, w_out[:, :vocab], targets)
    return _blockwise_ce_xla(hidden, w_out, targets, vocab=vocab)


# --------------------------------------------------------------------- #
# Mamba-2 SSD
# --------------------------------------------------------------------- #
def _ssd_chunked_xla(x, dt, A, Bm, Cm, D, *, chunk=64, init_state=None):
    """Chunked dual form as jnp (mirrors the kernel math), scan over chunks."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    group = H // G
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    nc = S // chunk

    xf = x.astype(jnp.float32).reshape(Bsz, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
    Bf = jnp.repeat(Bm.astype(jnp.float32), group, axis=2).reshape(Bsz, nc, chunk, H, N)
    Cf = jnp.repeat(Cm.astype(jnp.float32), group, axis=2).reshape(Bsz, nc, chunk, H, N)
    Af = A.astype(jnp.float32)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(h, blk):
        xc, dtc, bc, cc = blk  # (B,chunk,H,*)
        da = dtc * Af[None, None, :]                 # (B,L,H)
        cum = jnp.cumsum(da, axis=1)
        seg = cum[:, -1]                             # (B,H)
        diff = cum[:, :, None, :] - cum[:, None, :, :]   # (B,L,L,H)
        decay = jnp.where(causal[None, :, :, None], jnp.exp(diff), 0.0)
        scores = jnp.einsum("blhn,bshn->blsh", cc, bc)
        # the (L × L) attention-like weights feed an MXU matmul: store them
        # in the input dtype with fp32 accumulation — decay statistics
        # stay fp32.
        att = (scores * decay * dtc[:, None, :, :]).astype(x.dtype)
        y = jnp.einsum(
            "blsh,bshp->blhp", att, xc.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        decay_in = jnp.exp(cum)                      # (B,L,H)
        y += jnp.einsum("blhn,bhpn,blh->blhp", cc, h, decay_in)
        decay_out = jnp.exp(seg[:, None, :] - cum)   # (B,L,H)
        xw = xc * (dtc * decay_out)[..., None]
        h = h * jnp.exp(seg)[..., None, None] + jnp.einsum("blhp,blhn->bhpn", xw, bc)
        return h, y

    h0 = (
        jnp.zeros((Bsz, H, P, N), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )
    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bf, 1, 0),
        jnp.moveaxis(Cf, 1, 0),
    )
    hT, ys = jax.lax.scan(jax.checkpoint(body), h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, H, P)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), hT


def ssd(
    x, dt, A, Bm, Cm, D, *, chunk: int = 64, impl: str = "auto", interpret: bool = False
):
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        return _ssd_pallas(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=interpret)
    if impl == "naive":
        return ref.ssd_ref(x, dt, A, Bm, Cm, D)
    return _ssd_chunked_xla(x, dt, A, Bm, Cm, D, chunk=chunk)


def ssd_decode_step(
    x: jax.Array,      # (B, 1, H, P)
    dt: jax.Array,     # (B, 1, H)
    A: jax.Array,      # (H,)
    Bm: jax.Array,     # (B, 1, G, N)
    Cm: jax.Array,     # (B, 1, G, N)
    D: jax.Array,      # (H,)
    state: jax.Array,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """One recurrent SSD step (serving).  Returns (y (B,1,H,P), new_state)."""
    H = x.shape[2]
    G = Bm.shape[2]
    group = H // G
    xf = x[:, 0].astype(jnp.float32)               # (B,H,P)
    dtf = dt[:, 0].astype(jnp.float32)             # (B,H)
    bf = jnp.repeat(Bm[:, 0].astype(jnp.float32), group, axis=1)  # (B,H,N)
    cf = jnp.repeat(Cm[:, 0].astype(jnp.float32), group, axis=1)
    decay = jnp.exp(dtf * A.astype(jnp.float32)[None, :])[..., None, None]
    upd = (dtf[..., None] * xf)[..., :, None] * bf[..., None, :]
    new_state = state.astype(jnp.float32) * decay + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, cf)
    y = y + xf * D.astype(jnp.float32)[None, :, None]
    return y[:, None].astype(x.dtype), new_state.astype(state.dtype)


# --------------------------------------------------------------------- #
# ragged grouped matmul (MoE expert GEMMs)
# --------------------------------------------------------------------- #
class _GmmCfg(NamedTuple):
    """Hashable static config for the pallas grouped-matmul custom-VJP."""

    block_m: int
    block_n: int
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gmm_pallas(cfg: _GmmCfg, x, w, group_sizes):
    from repro.kernels import grouped_matmul as _gm

    return _gm.gmm(
        x, w, group_sizes,
        block_m=cfg.block_m, block_n=cfg.block_n, interpret=cfg.interpret,
    )


def _gmm_pallas_fwd(cfg: _GmmCfg, x, w, group_sizes):
    return _gmm_pallas(cfg, x, w, group_sizes), (x, w, group_sizes)


def _gmm_pallas_bwd(cfg: _GmmCfg, res, dy):
    from repro.kernels import grouped_matmul as _gm

    x, w, gs = res
    dx = _gm.gmm(
        dy, jnp.swapaxes(w, 1, 2), gs,
        block_m=cfg.block_m, block_n=cfg.block_n, interpret=cfg.interpret,
    ).astype(x.dtype)
    dw = _gm.gmm_dw(
        x, dy, gs,
        block_m=cfg.block_m, block_n=cfg.block_n, interpret=cfg.interpret,
    ).astype(w.dtype)
    return dx, dw, None  # group sizes are integer — no cotangent


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


def _gmm_xla(x, w, group_sizes):
    """XLA fallback: ``lax.ragged_dot`` (differentiable, CPU/GPU/TPU).

    Rows past ``sum(group_sizes)`` are masked to zero to match the
    kernel contract (the dropped-token tail in ``models/moe.py``)."""
    y = jax.lax.ragged_dot(
        x, w, group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32,
    )
    rows = jnp.arange(x.shape[0], dtype=jnp.int32)
    total = jnp.sum(group_sizes.astype(jnp.int32))
    y = jnp.where((rows < total)[:, None], y, 0.0)
    return y.astype(x.dtype)


def _gmm_xla_bounded(x, w, group_sizes, max_size: int):
    """XLA fallback when a static per-group row bound is known (MoE always
    has one: the capacity).  Scatters rows into a static ``(E, max_size,
    K)`` buffer and runs ONE batched GEMM — ``O(E·max_size·K·N)`` FLOPs,
    independent of E for fixed total capacity, where ``lax.ragged_dot``
    lowers to a dense masked loop (``O(M·E·K·N)``) on CPU/GPU.  Rows of a
    group beyond ``max_size`` (contract violation) come back zero, as do
    rows past ``sum(group_sizes)``.  Natively differentiable."""
    E = w.shape[0]
    m = jnp.arange(x.shape[0], dtype=jnp.int32)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    gid = jnp.searchsorted(ends, m, side="right")
    g = jnp.minimum(gid, E - 1)
    rank = m - (ends - sizes)[g]
    valid = (m < ends[-1]) & (rank < max_size)
    xe = jnp.zeros((E, max_size, x.shape[1]), x.dtype)
    xe = xe.at[jnp.where(valid, g, E), rank].set(x, mode="drop")
    ye = jnp.einsum(
        "eck,ekn->ecn", xe, w, preferred_element_type=jnp.float32
    )
    y = jnp.where(valid[:, None], ye[g, rank], 0.0)
    return y.astype(x.dtype)


def grouped_matmul(
    x: jax.Array,            # (M, K) rows sorted by group
    w: jax.Array,            # (E, K, N) per-group (expert) weights
    group_sizes: jax.Array,  # (E,) int32 contiguous row counts (dynamic)
    *,
    impl: str = "auto",
    interpret: bool = False,
    max_group_size: Optional[int] = None,
) -> jax.Array:
    """Ragged grouped matmul ``y[i] = x[i] @ w[g(i)]`` — the MoE expert
    FFN after sort-by-expert dispatch.  Differentiable end-to-end on
    every impl: ``pallas`` pairs the ragged forward kernel with the
    ragged dX/dW backward kernels via ``jax.custom_vjp``
    (``grouped_matmul.py``), ``xla`` is ``lax.ragged_dot`` — or, when
    the caller supplies ``max_group_size`` (a static upper bound on every
    group, e.g. the MoE capacity), the capacity-batched GEMM
    ``_gmm_xla_bounded`` whose cost does not grow with E — and ``naive``
    the (M, K, N) gather oracle in ``ref.py``.  Rows past
    ``sum(group_sizes)`` (capacity-dropped slots) come back zero."""
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        cfg = _GmmCfg(block_m=128, block_n=128, interpret=interpret)
        return _gmm_pallas(cfg, x, w, group_sizes)
    if impl == "naive":
        return ref.grouped_matmul_ref(x, w, group_sizes)
    if max_group_size is not None:
        return _gmm_xla_bounded(x, w, group_sizes, int(max_group_size))
    return _gmm_xla(x, w, group_sizes)
