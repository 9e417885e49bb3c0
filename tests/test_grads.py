"""VJP allclose sweeps: the trainable kernel paths vs naive autodiff.

``impl="pallas"`` with ``interpret=True`` runs the Pallas forward AND the
Pallas backward kernels (custom VJP) through the interpreter — the same
code that compiles on TPU — so the fused training path is verifiable on
CPU.  ``impl="xla"`` checks the blockwise fallback's autodiff.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(11)

GRAD_TOL = dict(atol=2e-4, rtol=2e-3)


def _attn_inputs(B, S, T, H, Hkv, D):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    do = jax.random.normal(ks[3], (B, S, H, D))
    return q, k, v, do


# causal / bidirectional (the ESM-2/BERT MLM case) / window / softcap combos
ATTN_VARIANTS = [
    (True, 0, 0.0),
    (False, 0, 0.0),
    (True, 32, 0.0),
    (True, 0, 20.0),
    (False, 24, 15.0),
]
# MHA, GQA, MQA; square and offset (T > S, decode-style); odd lengths;
# prime lengths exercise the pallas pad+mask tiling path
ATTN_SHAPES = [
    (2, 64, 64, 4, 4, 32),
    (1, 64, 64, 4, 2, 16),
    (1, 48, 80, 4, 1, 16),
    (1, 40, 40, 2, 2, 16),
    (1, 37, 53, 2, 1, 16),
]


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("causal,window,softcap", ATTN_VARIANTS)
@pytest.mark.parametrize("B,S,T,H,Hkv,D", ATTN_SHAPES)
def test_attention_vjp_sweep(impl, causal, window, softcap, B, S, T, H, Hkv, D):
    q, k, v, do = _attn_inputs(B, S, T, H, Hkv, D)
    off = T - S

    def loss(which):
        def f(q, k, v):
            out = ops.attention(
                q, k, v, causal=causal, window=window, softcap=softcap,
                q_offset=off, impl=which, interpret=True,
            )
            return (out * do).sum()
        return f

    got = jax.grad(loss(impl), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("naive"), argnums=(0, 1, 2))(q, k, v)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), err_msg=f"{impl}:{name}", **GRAD_TOL
        )


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_attention_vjp_bf16(impl):
    q, k, v, do = _attn_inputs(1, 64, 64, 4, 2, 32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(which):
        def f(q, k, v):
            out = ops.attention(q, k, v, causal=True, impl=which, interpret=True)
            return (out.astype(jnp.float32) * do).sum()
        return f

    got = jax.grad(loss(impl), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("naive"), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=5e-2, rtol=5e-2,
        )


def test_attention_vjp_large_tiles_bf16():
    """The trainable path at a bidirectional bf16 shape on which the tile
    rule picks tiles above 128 (512 x 512, so the forward builds no
    per-element mask). Forward and all three cotangents against the fp32
    oracle and its jax.grad."""
    B, S, H, D = 1, 512, 2, 64
    assert min(ops.attention_blocks(S, S, causal=False, window=0)) > 128
    q, k, v, do = _attn_inputs(B, S, S, H, H, D)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def f(which):
        def loss(q, k, v):
            out = ops.attention(q, k, v, causal=False, impl=which, interpret=True)
            return (out.astype(jnp.float32) * do).sum(), out
        return loss

    (_, out), got = jax.value_and_grad(f("pallas"), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, o_ref), want = jax.value_and_grad(f("naive"), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    for name, g, w in zip(("out", "dq", "dk", "dv"), (out,) + got, (o_ref,) + want):
        assert g.dtype == jnp.bfloat16, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, atol=5e-2, rtol=5e-2, err_msg=name)
        assert np.linalg.norm(g - w) / np.linalg.norm(w) < 1e-2, name


def _ce_inputs(T, D, V, Vp):
    ks = jax.random.split(KEY, 4)
    h = jax.random.normal(ks[0], (T, D))
    W = jax.random.normal(ks[1], (D, Vp)) * 0.1
    tgt = jax.random.randint(ks[2], (T,), 0, V)
    gl = jax.random.normal(ks[3], (T,))
    return h, W, tgt, gl


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("T,D,V,Vp", [
    (64, 32, 500, 512),
    (128, 64, 1000, 1024),
    (48, 24, 300, 384),    # odd token count, tail vocab block
    (37, 16, 600, 700),    # prime T, non-multiple Vp -> pad+mask tiling
])
def test_cross_entropy_vjp_sweep(impl, T, D, V, Vp):
    h, W, tgt, gl = _ce_inputs(T, D, V, Vp)

    def loss(which):
        def f(h, W):
            losses, lse = ops.cross_entropy(
                h, W, tgt, vocab=V, impl=which, interpret=True
            )
            # weighted loss + an lse term so both output cotangents are live
            return (losses * gl).sum() + 0.3 * lse.sum()
        return f

    got = jax.grad(loss(impl), argnums=(0, 1))(h, W)
    want = jax.grad(loss("naive"), argnums=(0, 1))(h, W)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), **GRAD_TOL)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), **GRAD_TOL)
    # vocab padding never receives gradient
    if Vp > V:
        assert np.abs(np.asarray(got[1][:, V:])).max() == 0.0


def test_kernel_padded_tiling_fwd_bwd():
    """Explicit small blocks over prime dims force the zero-pad + mask
    tiling path (grid covers padded rows/cols) in fwd AND bwd kernels."""
    from repro.kernels import flash_attention as fa
    from repro.kernels import cross_entropy as ce

    B, S, T, H, Hkv, D = 1, 37, 53, 2, 1, 16
    q, k, v, do = _attn_inputs(B, S, T, H, Hkv, D)
    kw = dict(causal=True, window=16, softcap=10.0, q_offset=T - S,
              block_q=16, block_k=16, interpret=True)
    out, lse = fa.flash_attention_fwd(q, k, v, **kw)
    want = ref.attention_ref(q, k, v, causal=True, window=16, softcap=10.0,
                             q_offset=T - S)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=1e-4)
    dq, dk, dv = fa.flash_attention_bwd(q, k, v, out, lse, do, **kw)
    f = lambda q, k, v: (ref.attention_ref(
        q, k, v, causal=True, window=16, softcap=10.0, q_offset=T - S) * do).sum()
    for g, w in zip((dq, dk, dv), jax.grad(f, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **GRAD_TOL)

    Tt, Dd, V, Vp = 37, 16, 600, 700
    h, W, tgt, gl = _ce_inputs(Tt, Dd, V, Vp)
    loss, lse = ce.fused_cross_entropy(
        h, W, tgt, vocab=V, block_t=16, block_v=128, interpret=True
    )
    wl, wlse = ref.cross_entropy_ref(h, W[:, :V], tgt)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(wl),
                               atol=3e-5, rtol=1e-4)
    dh, dw = ce.fused_cross_entropy_bwd(
        h, W, tgt, lse, gl, jnp.zeros_like(gl), vocab=V,
        block_t=16, block_v=128, interpret=True,
    )
    fce = lambda h, W: (ref.cross_entropy_ref(h, W[:, :V], tgt)[0] * gl).sum()
    wh, ww = jax.grad(fce, argnums=(0, 1))(h, W)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(wh), **GRAD_TOL)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ww), **GRAD_TOL)


def test_cross_entropy_vjp_under_jit():
    h, W, tgt, gl = _ce_inputs(64, 32, 500, 512)

    @jax.jit
    def g(h, W):
        return jax.grad(
            lambda h, W: (
                ops.cross_entropy(h, W, tgt, vocab=500, impl="pallas",
                                  interpret=True)[0] * gl
            ).sum(),
            argnums=(0, 1),
        )(h, W)

    got = g(h, W)
    want = jax.grad(
        lambda h, W: (ref.cross_entropy_ref(h, W[:, :500], tgt)[0] * gl).sum(),
        argnums=(0, 1),
    )(h, W)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), **GRAD_TOL)
    np.testing.assert_allclose(np.asarray(got[1][:, :500]),
                               np.asarray(want[1][:, :500]), **GRAD_TOL)


def test_train_step_gradients_pallas_vs_xla():
    """End-to-end: Model.loss_fn grads with kernel_impl="pallas_interpret"
    (fused Pallas fwd+bwd kernels) match the xla blockwise path — the MLM
    training configuration the paper's ESM-2 recipe uses."""
    import dataclasses

    from repro.core.config import ModelConfig
    from repro.models.model import build_model

    base = ModelConfig(
        name="t", family="bio_bert", num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=1, d_ff=64, vocab_size=60, causal=False,
        objective="mlm", norm_type="layernorm", dtype="float32",
        param_dtype="float32",
    )
    B, S = 2, 16
    ks = jax.random.split(KEY, 3)
    tokens = jax.random.randint(ks[0], (B, S), 0, 60)
    targets = jax.random.randint(ks[1], (B, S), 0, 60)
    mask = (jax.random.uniform(ks[2], (B, S)) < 0.3).astype(jnp.float32)
    batch = {"tokens": tokens, "targets": targets, "loss_mask": mask}

    grads = {}
    losses = {}
    for impl in ("pallas_interpret", "xla"):
        cfg = dataclasses.replace(base, kernel_impl=impl)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        (loss, _), g = jax.value_and_grad(
            lambda p: model.loss_fn(p, batch), has_aux=True
        )(params)
        grads[impl], losses[impl] = g, loss

    np.testing.assert_allclose(
        float(losses["pallas_interpret"]), float(losses["xla"]), rtol=1e-5
    )
    flat_p = jax.tree_util.tree_leaves_with_path(grads["pallas_interpret"])
    flat_x = jax.tree_util.tree_leaves_with_path(grads["xla"])
    for (path, gp), (_, gx) in zip(flat_p, flat_x):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gx), atol=5e-4, rtol=5e-3,
            err_msg=jax.tree_util.keystr(path),
        )
