"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.cross_entropy import fused_cross_entropy
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import layernorm, rmsnorm
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(42)


def tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("B,S,T,H,Hkv,D", [
    (2, 128, 128, 4, 2, 64),
    (1, 256, 256, 8, 8, 128),
    (2, 64, 192, 6, 1, 64),
    (1, 128, 128, 4, 4, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (False, 0, 0.0), (True, 64, 0.0), (True, 0, 30.0),
])
def test_flash_attention_sweep(B, S, T, H, Hkv, D, dtype, causal, window, softcap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    off = T - S
    out = flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=off, block_q=64, block_k=64, interpret=True,
    )
    want = ref.attention_ref(
        q, k, v, causal=causal, window=window, softcap=softcap, q_offset=off
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **tol(dtype)
    )


@pytest.mark.parametrize("S,T,causal,window,want", [
    # the ESM-2 cell's shape: bidirectional, 1024 x 1024
    (1024, 1024, False, 0, ops.BIDIRECTIONAL_BLOCKS),
    # causal and windowed calls keep 128 x 128
    (1024, 1024, True, 0, (128, 128)),
    (4096, 4096, True, 0, (128, 128)),
    (1024, 1024, False, 256, (128, 128)),
    (1024, 1024, True, 512, (128, 128)),
    # lengths under the tile are capped, as pick_block does
    (64, 64, True, 0, (64, 64)),
    (37, 53, True, 0, (37, 53)),
    (1, 300, True, 0, (1, 128)),
    (64, 192, False, 0, (64, 128)),
    (200, 200, False, 0, (128, 128)),
    # a multiple of 128 up to the tile is one tile
    (384, 384, False, 0, (384, 384)),
    # otherwise never padded further than 128-row tiles, and 128-aligned
    (1000, 1000, False, 0, (512, 512)),
    (2048, 2048, False, 0, ops.BIDIRECTIONAL_BLOCKS),
    (1536, 1536, False, 0, (512, 512)),
    (1500, 2000, False, 0, (512, 1024)),
])
def test_attention_blocks_rule(S, T, causal, window, want):
    got = ops.attention_blocks(S, T, causal=causal, window=window)
    assert got == tuple(want)
    for n, b in zip((S, T), got):
        assert b <= n
        assert b % 128 == 0 or b == n <= 128
        assert -n % b <= -n % 128 or n <= 128


def test_flash_attention_large_tiles_bf16():
    """A bidirectional bf16 call on which the tile rule picks tiles above
    128 (here one 512 x 512 tile, so the forward builds no per-element
    mask), against the fp32 oracle."""
    B, S, H, D = 1, 512, 2, 64
    assert min(ops.attention_blocks(S, S, causal=False, window=0)) > 128
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16) for kk in ks)
    out = ops.attention(q, k, v, causal=False, impl="pallas_interpret")
    want = ref.attention_ref(q, k, v, causal=False)
    got, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, **tol(jnp.bfloat16))
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-2


@pytest.mark.parametrize("rows,d", [(32, 128), (64, 256), (128, 512), (8, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rows, d, dtype):
    x = jax.random.normal(KEY, (rows, d), dtype) * 3
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (d,), dtype) * 0.2 + 1
    out = rmsnorm(x, w, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref.rmsnorm_ref(x, w), np.float32),
        **tol(dtype),
    )


@pytest.mark.parametrize("rows,d,bias", [(32, 128, True), (64, 256, False), (16, 768, True)])
def test_layernorm_sweep(rows, d, bias):
    x = jax.random.normal(KEY, (rows, d)) * 2 + 1
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (d,)) * 0.1 + 1
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (d,)) * 0.1 if bias else None
    out = layernorm(x, w, b, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.layernorm_ref(x, w, b)), atol=3e-5, rtol=1e-4
    )


@pytest.mark.parametrize("T,D,V,Vp,bv", [
    (64, 32, 500, 512, 128),
    (128, 64, 1000, 1024, 256),
    (256, 128, 2048, 2048, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_cross_entropy_sweep(T, D, V, Vp, bv, dtype):
    h = jax.random.normal(KEY, (T, D), dtype)
    W = (jax.random.normal(jax.random.fold_in(KEY, 1), (D, Vp)) * 0.05).astype(dtype)
    tgt = jax.random.randint(jax.random.fold_in(KEY, 2), (T,), 0, V)
    loss, lse = fused_cross_entropy(h, W, tgt, vocab=V, block_v=bv, interpret=True)
    want_loss, want_lse = ref.cross_entropy_ref(
        h.astype(jnp.float32), W.astype(jnp.float32)[:, :V], tgt
    )
    np.testing.assert_allclose(np.asarray(loss), np.asarray(want_loss), **tol(dtype))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), **tol(dtype))


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (2, 64, 4, 16, 2, 8, 16),
    (1, 128, 8, 32, 1, 16, 32),
    (2, 96, 2, 64, 2, 32, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, S, H, P, G, N, chunk, dtype):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, G, N), dtype)
    Dv = jax.random.normal(ks[5], (H,))
    y, hT = ssd_scan(x, dt, A, Bm, Cm, Dv, chunk=chunk, interpret=True)
    want_y, want_h = ref.ssd_ref(x, dt, A, Bm, Cm, Dv)
    t = dict(atol=2e-1, rtol=1e-1) if dtype == jnp.bfloat16 else dict(atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(want_y, np.float32), **t
    )
    np.testing.assert_allclose(np.asarray(hT), np.asarray(want_h), **t)


def test_flash_attention_decode_shape():
    """S=1 decode-style call with large cache offset."""
    q = jax.random.normal(KEY, (2, 1, 8, 64))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 256, 2, 64))
    out = flash_attention(q, k, v, causal=True, q_offset=255, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, q_offset=255)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("B,T,H,Hkv,D,bt", [
    (2, 256, 8, 2, 64, 64),
    (3, 512, 4, 4, 128, 128),
    (1, 1024, 16, 2, 64, 256),
])
def test_flash_decode_sweep(B, T, H, Hkv, D, bt):
    from repro.kernels.flash_decode import flash_decode

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    lens = (jnp.arange(B) * 37 % (T - 40) + 33).astype(jnp.int32)
    out = flash_decode(q, k, v, lens, block_t=bt, interpret=True)
    for b in range(B):
        L = int(lens[b])
        want = ref.attention_ref(q[b:b+1], k[b:b+1, :L], v[b:b+1, :L], causal=False)
        np.testing.assert_allclose(
            np.asarray(out[b]), np.asarray(want[0]), atol=3e-5, rtol=1e-4
        )
