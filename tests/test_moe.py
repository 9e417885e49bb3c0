"""MoE acceptance suite for the ragged (megablocks-style) dispatch path.

  * grouped-matmul parity: every impl (xla ragged_dot, xla capacity-batched,
    pallas interpret) against the (M, K, N) gather oracle — forward AND VJP —
    across expert counts and ragged edge cases (empty experts, all rows in
    one expert, dropped tail, non-tile-multiple M)
  * moe_apply vs the dense no-capacity oracle across capacity factors and
    top-1/top-2 routing
  * fp32 routing regression: a bf16 softmax/top-k would flip the routing
    decision on near-tied logits; the fp32 router must not
  * router stats vector (aux) semantics: drop fraction, per-expert load
  * Trainer integration: router metrics reach history + the obs registry
  * 8-virtual-device expert-parallel parity vs single device (subprocess)
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import ModelConfig, TrainConfig
from repro.core.module import materialize
from repro.kernels import ops, ref
from repro.models.moe import (
    AUX_BASE, aux_shape, capacity, moe_apply, moe_defs, moe_ref_dense,
)
from repro.models.model import build_model
from repro.parallel.sharding import null_ctx
from repro.training import train_step as TS

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
KEY = jax.random.PRNGKey(0)


def moe_cfg(**kw):
    base = dict(
        name="m", family="moe", num_layers=1, d_model=32, num_heads=2,
        num_kv_heads=2, d_ff=64, vocab_size=64, num_experts=4,
        num_experts_per_tok=2, capacity_factor=4.0, dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


# --------------------------------------------------------------------- #
# ragged grouped-matmul kernel parity (fwd + VJP)
# --------------------------------------------------------------------- #
def _size_cases(E, M):
    """Ragged edge cases for E groups over at most M rows."""
    rng = np.random.default_rng(E)
    even = [M // E] * E
    uneven = rng.multinomial(M, rng.dirichlet(np.ones(E))).tolist()
    cases = [
        even,
        uneven,
        [0] * E,                          # all experts empty
        [M] + [0] * (E - 1),              # everything in one expert
        [M // 2] + [0] * (E - 1),         # dropped tail (sum < M)
    ]
    if E >= 3:
        # interior empties + dropped tail (sum stays <= M, the contract)
        cases.append([0, M // 4, 0] + [(M // 2) // (E - 3)] * (E - 3))
    return cases


def _impl_calls(max_group_size):
    return [
        ("xla_ragged", dict(impl="xla")),
        ("xla_bounded", dict(impl="xla", max_group_size=max_group_size)),
        ("pallas_interpret", dict(impl="pallas", interpret=True)),
    ]


@pytest.mark.parametrize("E", [2, 8, 16])
def test_grouped_matmul_parity_fwd_and_vjp(E):
    M, K, N = 64, 16, 24
    x = jax.random.normal(KEY, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (E, K, N)) * 0.3
    for sizes in _size_cases(E, M):
        gs = jnp.asarray(sizes, jnp.int32)
        want = ref.grouped_matmul_ref(x, w, gs)
        cot = jax.random.normal(jax.random.fold_in(KEY, 2), want.shape)

        def loss_ref(x, w):
            return (ref.grouped_matmul_ref(x, w, gs) * cot).sum()

        gx_ref, gw_ref = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        for name, kw in _impl_calls(max(sizes) or 1):
            y = ops.grouped_matmul(x, w, gs, **kw)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(want), atol=1e-4, rtol=1e-4,
                err_msg=f"{name} fwd sizes={sizes}",
            )

            def loss(x, w, kw=kw):
                return (
                    ops.grouped_matmul(x, w, gs, **kw).astype(jnp.float32)
                    * cot
                ).sum()

            gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
            np.testing.assert_allclose(
                np.asarray(gx), np.asarray(gx_ref), atol=1e-3, rtol=1e-3,
                err_msg=f"{name} dX sizes={sizes}",
            )
            np.testing.assert_allclose(
                np.asarray(gw), np.asarray(gw_ref), atol=1e-3, rtol=1e-3,
                err_msg=f"{name} dW sizes={sizes}",
            )


def test_grouped_matmul_non_tile_multiple_rows():
    """M that is not a multiple of any tile size exercises the padded-tail
    masking in the pallas kernel and the bounded fallback."""
    M, K, N, E = 50, 16, 24, 3
    x = jax.random.normal(KEY, (M, K))
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (E, K, N)) * 0.3
    gs = jnp.asarray([17, 0, 26], jnp.int32)      # sum=43 < 50: zero tail
    want = ref.grouped_matmul_ref(x, w, gs)
    for name, kw in _impl_calls(26):
        y = ops.grouped_matmul(x, w, gs, **kw)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(want), atol=1e-4, rtol=1e-4,
            err_msg=name,
        )
        assert np.abs(np.asarray(y[43:])).max() == 0.0, name


# --------------------------------------------------------------------- #
# moe_apply vs dense oracle
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("E,topk", [(4, 1), (4, 2), (8, 2)])
def test_moe_apply_matches_dense_oracle_generous_capacity(E, topk):
    cfg = moe_cfg(num_experts=E, num_experts_per_tok=topk,
                  capacity_factor=float(2 * E))
    params = materialize(moe_defs(cfg), KEY, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 24, cfg.d_model))
    out, aux = moe_apply(cfg, null_ctx(), params, x)
    want = moe_ref_dense(cfg, params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-3
    )
    assert aux.shape == aux_shape(cfg)
    assert float(aux[2]) == 0.0                       # nothing dropped
    np.testing.assert_allclose(float(aux[AUX_BASE:].sum()), 1.0, atol=1e-5)


@pytest.mark.parametrize("cf", [0.25, 0.5, 1.0])
def test_moe_apply_tight_capacity_drops_and_reports(cf):
    cfg = moe_cfg(num_experts=4, num_experts_per_tok=1, capacity_factor=cf)
    params = materialize(moe_defs(cfg), KEY, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (1, 64, cfg.d_model))
    out, aux = moe_apply(cfg, null_ctx(), params, x)
    T = 64
    C = capacity(cfg, T)
    dropped, total = float(aux[2]), float(aux[3])
    assert total == T * cfg.num_experts_per_tok
    assert 0.0 <= dropped <= total
    # per-expert kept counts are capacity-clipped: load * kept_total <= C
    kept_total = total - dropped
    load = np.asarray(aux[AUX_BASE:])
    assert (load * kept_total <= C + 1e-3).all()
    if dropped:
        # dropped tokens contribute nothing: with top-1 routing their
        # output row is exactly zero (before the shared expert, absent here)
        norms = np.linalg.norm(np.asarray(out[0]), axis=-1)
        assert (norms < 1e-6).sum() >= 1


def test_moe_apply_consistent_across_impls():
    """The xla ragged path and the pallas interpret path produce the same
    moe output end-to-end (same routing, same combine)."""
    x = jax.random.normal(jax.random.fold_in(KEY, 6), (2, 16, 32))
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        cfg = moe_cfg(capacity_factor=1.0, kernel_impl=impl)
        params = materialize(moe_defs(cfg), KEY, jnp.float32)
        outs[impl], _ = moe_apply(cfg, null_ctx(), params, x)
    np.testing.assert_allclose(
        np.asarray(outs["xla"]), np.asarray(outs["pallas_interpret"]),
        atol=1e-4, rtol=1e-3,
    )


# --------------------------------------------------------------------- #
# fp32 routing regression (bf16 softmax/top-k would flip the decision)
# --------------------------------------------------------------------- #
def test_router_routes_in_fp32_under_bf16_compute():
    """Construct logits e0=1.0, e1=1.0+2^-12 from exactly-bf16-representable
    weights.  fp32 routing picks expert 1; a bf16 softmax/top-k collapses
    the pair to a tie and top_k's index order picks expert 0 instead."""
    cfg = moe_cfg(num_experts=2, num_experts_per_tok=1, d_model=2,
                  capacity_factor=8.0, dtype="bfloat16")
    params = materialize(moe_defs(cfg), KEY, jnp.bfloat16)
    router = jnp.asarray([[1.0, 1.0], [0.0, 2.0 ** -12]], jnp.float32)
    assert (router.astype(jnp.bfloat16).astype(jnp.float32) == router).all()
    params = dict(params, router=router)
    x = jnp.asarray([[[1.0, 1.0]]], jnp.bfloat16)    # (B=1, S=1, d=2)

    # the buggy path this guards against: bf16 logits tie at 1.0
    logits_bf16 = (x.reshape(1, 2) @ router.astype(jnp.bfloat16))
    bad_choice = int(jnp.argmax(logits_bf16, -1)[0])
    assert bad_choice == 0  # tie -> lower index

    _, aux = moe_apply(cfg, null_ctx(), params, x)
    load = np.asarray(aux[AUX_BASE:])
    assert load[1] == 1.0 and load[0] == 0.0, load  # fp32 picked expert 1


# --------------------------------------------------------------------- #
# Trainer integration: router metrics reach history + the obs registry
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("accum", [1, 2])
def test_train_step_emits_router_metrics(accum):
    cfg = moe_cfg(num_experts=4, capacity_factor=1.0)
    model = build_model(cfg)
    tc = TrainConfig(total_steps=1, warmup_steps=1, accum_steps=accum)
    state = TS.init_train_state(model, KEY, tc)
    batch = {
        "tokens": np.random.default_rng(0)
        .integers(0, 64, size=(4, 32))
        .astype(np.int32)
    }
    _, m = jax.jit(TS.make_train_step(model, tc))(state, batch)
    assert np.isfinite(float(m["loss"]))
    for k in ("aux_loss", "router_entropy", "router_drop_frac"):
        v = float(m[k])
        assert np.isfinite(v) and v >= 0.0, (k, v)
    load = np.asarray(m["router_load"])
    assert load.shape == (4,)
    np.testing.assert_allclose(load.sum(), 1.0, atol=1e-4)
    assert float(m["router_entropy"]) <= np.log(4) + 1e-5


def test_trainer_feeds_router_gauges(tmp_path):
    from repro.data.dataset import build_synthetic_protein_memmap
    from repro.data.pipeline import CLMBatches
    from repro.obs.metrics import MetricsRegistry
    from repro.training.loop import Trainer

    cfg = moe_cfg(num_experts=4, vocab_size=64, capacity_factor=1.0)
    tc = TrainConfig(global_batch=4, seq_len=32, total_steps=2, log_every=1,
                     warmup_steps=1, decay_steps=1)
    ds, _ = build_synthetic_protein_memmap(str(tmp_path / "p"), n=64, seed=0)
    reg = MetricsRegistry()
    tr = Trainer(build_model(cfg), tc, verbose=False, metrics=reg)
    _, hist = tr.run(CLMBatches(ds, 4, 32, seed=0))
    # scalar history rows carry the router scalars, never the load vector
    assert "router_drop_frac" in hist[-1] and "router_load" not in hist[-1]
    for name in ("train_router_drop_frac", "train_aux_loss",
                 "train_router_entropy"):
        fam = reg.get(name)
        assert fam is not None and np.isfinite(fam.value), name
    loads = reg.get("train_router_load")
    assert loads is not None
    assert set(loads.children) == {("0",), ("1",), ("2",), ("3",)}
    total = sum(c.value for c in loads.children.values())
    np.testing.assert_allclose(total, 1.0, atol=1e-4)


# --------------------------------------------------------------------- #
# 8-virtual-device expert parallelism (subprocess)
# --------------------------------------------------------------------- #
EP_CODE = textwrap.dedent("""
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.core.config import ModelConfig, ParallelConfig
    from repro.models.model import build_model
    from repro.launch.mesh import make_mesh

    assert jax.device_count() == 8, jax.device_count()
    cfg = ModelConfig(
        name="m", family="moe", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=128, num_experts=8,
        num_experts_per_tok=2, capacity_factor=2.0, dtype="float32",
    )
    ref_model = build_model(cfg)
    params = ref_model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, 128, size=(4, 32)).astype(np.int32)}

    loss_ref, m_ref = jax.jit(ref_model.loss_fn)(params, batch)
    logits_ref, cache = jax.jit(
        lambda p, b: ref_model.prefill(p, b, 48))(params, batch)
    toks_ref = [int(t) for t in jnp.argmax(logits_ref[:, -1], -1)]

    for shape in ((1, 8), (2, 4)):
        mesh = make_mesh(shape, ("data", "model"))
        m_sh = build_model(cfg, ParallelConfig(), mesh)
        assert m_sh.ctx.expert_parallel(cfg.num_experts) == (shape[1] in (4, 8))
        sh_params = jax.device_put(
            params, jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s),
                m_sh.param_specs()))
        loss_sh, m_sh_metrics = jax.jit(m_sh.loss_fn)(sh_params, batch)
        assert abs(float(loss_sh) - float(loss_ref)) < 1e-4, (
            shape, float(loss_sh), float(loss_ref))
        np.testing.assert_allclose(
            np.asarray(m_sh_metrics["router_load"]),
            np.asarray(m_ref["router_load"]), atol=1e-5)
        lg, _ = jax.jit(lambda p, b: m_sh.prefill(p, b, 48))(sh_params, batch)
        toks = [int(t) for t in jnp.argmax(lg[:, -1], -1)]
        assert toks == toks_ref, (shape, toks, toks_ref)
        print("mesh", shape, "ok")
    print("EP_OK")
""")


def test_expert_parallel_matches_single_device_8dev_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", EP_CODE], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "EP_OK" in out.stdout
