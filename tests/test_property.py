"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip cleanly where absent
from hypothesis import given, settings, strategies as st

from repro.core.config import ModelConfig, TrainConfig
from repro.kernels import ops, ref
from repro.kernels.ops import rope
from repro.optim import adamw
from repro.optim.schedule import lr_at

# "ci" is registered in conftest.py (derandomized, no deadline) so the
# --hypothesis-profile=ci CLI flag resolves before module import; loading
# it here keeps plain local `pytest` runs on the same deterministic seed
settings.load_profile("ci")

dims = st.sampled_from([16, 32, 64])


@given(B=st.integers(1, 3), S=dims, seed=st.integers(0, 2**16))
def test_rope_preserves_norm(B, S, seed):
    """Rotary embedding is a rotation: per-pair norms are preserved."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, S, 2, 32))
    y = rope(x, jnp.arange(S), 10000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5, atol=1e-5,
    )


@given(S=dims, seed=st.integers(0, 2**16))
def test_causality(S, seed):
    """Changing a future token never changes past attention outputs."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (1, S, 2, 16))
    k = jax.random.normal(ks[1], (1, S, 2, 16))
    v = jax.random.normal(ks[2], (1, S, 2, 16))
    out1 = ops.attention(q, k, v, causal=True, impl="xla")
    t = S // 2
    k2 = k.at[:, t:].add(jax.random.normal(ks[3], (1, S - t, 2, 16)))
    v2 = v.at[:, t:].add(1.0)
    out2 = ops.attention(q, k2, v2, causal=True, impl="xla")
    np.testing.assert_allclose(
        np.asarray(out1[:, :t]), np.asarray(out2[:, :t]), atol=1e-5, rtol=1e-5
    )


@given(S=dims, window=st.sampled_from([4, 8, 16]), seed=st.integers(0, 2**16))
def test_sliding_window_locality(S, window, seed):
    """Tokens beyond the window cannot influence the output."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (1, S, 1, 8))
    k = jax.random.normal(ks[1], (1, S, 1, 8))
    v = jax.random.normal(ks[2], (1, S, 1, 8))
    out1 = ref.attention_ref(q, k, v, causal=True, window=window)
    # perturb everything older than (S-1) - window + 1
    cut = max(S - 1 - window + 1, 0)
    if cut == 0:
        return
    k2 = k.at[:, :cut].set(jax.random.normal(ks[3], (1, cut, 1, 8)))
    out2 = ref.attention_ref(q, k2, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out1[:, -1]), np.asarray(out2[:, -1]), atol=1e-5, rtol=1e-5
    )


@given(
    T=st.sampled_from([8, 32]), V=st.sampled_from([64, 300]),
    seed=st.integers(0, 2**16),
)
def test_cross_entropy_nonnegative_and_shift_invariant(T, V, seed):
    key = jax.random.PRNGKey(seed)
    D = 16
    h = jax.random.normal(key, (T, D))
    W = jax.random.normal(jax.random.fold_in(key, 1), (D, V)) * 0.1
    tgt = jax.random.randint(jax.random.fold_in(key, 2), (T,), 0, V)
    loss, lse = ops.cross_entropy(h, W, tgt, impl="xla")
    assert (np.asarray(loss) >= -1e-5).all()
    # adding a constant column shift b to all logits leaves loss unchanged:
    # implemented by shifting W with a rank-1 update along a constant direction
    # (softmax shift invariance holds per-row only for constant shifts, so we
    # verify via explicit logits here)
    logits = np.asarray(h @ W)
    loss2 = np.asarray(
        jax.nn.logsumexp(jnp.asarray(logits + 3.7), -1)
        - jnp.take_along_axis(jnp.asarray(logits + 3.7), tgt[:, None], 1)[:, 0]
    )
    np.testing.assert_allclose(np.asarray(loss), loss2, atol=2e-4, rtol=1e-4)


@given(seed=st.integers(0, 2**16), steps=st.integers(1, 5))
def test_adamw_descends_quadratic(seed, steps):
    """AdamW must reduce a convex quadratic within a few steps."""
    key = jax.random.PRNGKey(seed)
    x0 = {"w": jax.random.normal(key, (8,)) * 3}
    tc = TrainConfig(learning_rate=0.1, weight_decay=0.0)
    state = adamw.init_state(x0)

    def f(p):
        return jnp.sum(p["w"] ** 2)

    params = x0
    for _ in range(steps * 10):
        g = jax.grad(f)(params)
        params, state = adamw.apply_updates(params, g, state, jnp.float32(0.1), tc)
    assert float(f(params)) < float(f(x0))


@given(step=st.integers(0, 2000))
def test_wsd_schedule_bounds(step):
    tc = TrainConfig(learning_rate=1e-3, min_lr=1e-5, warmup_steps=100,
                     decay_steps=200, total_steps=1000, schedule="wsd")
    lr = float(lr_at(tc, step))
    assert 0.0 <= lr <= tc.learning_rate * (1 + 1e-6)  # fp32 rounding headroom


@given(
    B=st.integers(1, 2), S=st.sampled_from([16, 48]),
    gqa=st.sampled_from([(4, 1), (4, 2), (4, 4)]), seed=st.integers(0, 2**16),
)
def test_gqa_equals_repeated_mha(B, S, gqa, seed):
    """GQA == MHA with kv heads explicitly repeated."""
    H, Hkv = gqa
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, 16))
    k = jax.random.normal(ks[1], (B, S, Hkv, 16))
    v = jax.random.normal(ks[2], (B, S, Hkv, 16))
    out = ops.attention(q, k, v, impl="xla")
    krep = jnp.repeat(k, H // Hkv, axis=2)
    vrep = jnp.repeat(v, H // Hkv, axis=2)
    want = ops.attention(q, krep, vrep, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


@given(seed=st.integers(0, 2**16))
def test_ssd_state_linearity_in_x(seed):
    """The SSD output is linear in x for fixed (dt, A, B, C) with D=0."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    B_, S, H, P, G, N = 1, 16, 2, 4, 1, 4
    x = jax.random.normal(ks[0], (B_, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B_, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B_, S, G, N))
    Cm = jax.random.normal(ks[4], (B_, S, G, N))
    Dv = jnp.zeros((H,))
    y1, _ = ops.ssd(x, dt, A, Bm, Cm, Dv, chunk=8, impl="xla")
    y2, _ = ops.ssd(2.0 * x, dt, A, Bm, Cm, Dv, chunk=8, impl="xla")
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1), atol=1e-4, rtol=1e-4)
