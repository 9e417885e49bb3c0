"""Sharded execution must be numerically equivalent to single-device:
head-TP and context-parallel losses/grad-norms match the mesh-free run,
and so do the smoke configs' sharded train step and decode on a (2, 4)
mesh and the pod-axis FSDP rules on a (2, 2, 2) one.
(Subprocess: needs 8 placeholder devices.)"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CODE = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding
    from repro.core.config import ModelConfig, ParallelConfig, TrainConfig
    from repro.models.model import build_model
    from repro.training.train_step import init_train_state, make_train_step
    from repro.launch.mesh import make_mesh

    cfg = ModelConfig(
        name="t", family="dense", num_layers=2, d_model=64, num_heads=8,
        num_kv_heads=2, d_ff=128, vocab_size=128, dtype="float32",
    )
    tc = TrainConfig(total_steps=1)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 128)
    batch = {"tokens": tokens}

    def loss_with(mesh, pc):
        model = build_model(cfg, pc, mesh)
        state = init_train_state(model, jax.random.PRNGKey(0), tc)
        step = make_train_step(model, tc)
        if mesh is not None:
            with mesh:
                _, m = jax.jit(step)(state, batch)
        else:
            _, m = jax.jit(step)(state, batch)
        return float(m["loss"]), float(m["grad_norm"])

    ref = loss_with(None, ParallelConfig())
    mesh = make_mesh((2, 4), ("data", "model"))
    tp = loss_with(mesh, ParallelConfig(attention_parallelism="head_tp"))
    cp = loss_with(mesh, ParallelConfig(attention_parallelism="context"))
    print("ref", ref); print("tp", tp); print("cp", cp)
    for name, got in (("tp", tp), ("cp", cp)):
        assert abs(got[0] - ref[0]) < 1e-4, (name, got, ref)
        assert abs(got[1] - ref[1]) / max(ref[1], 1) < 1e-3, (name, got, ref)
    # SSM family under CP (SP boundaries inside the mamba block)
    scfg = ModelConfig(
        name="s", family="ssm", num_layers=2, d_model=64, num_heads=8,
        num_kv_heads=8, d_ff=0, vocab_size=128, ssm_state=16, ssm_headdim=16,
        ssm_chunk=8, dtype="float32",
    )
    def loss_ssm(mesh, pc):
        model = build_model(scfg, pc, mesh)
        state = init_train_state(model, jax.random.PRNGKey(0), tc)
        step = make_train_step(model, tc)
        ctx = mesh if mesh is not None else None
        if ctx is not None:
            with ctx:
                _, m = jax.jit(step)(state, batch)
        else:
            _, m = jax.jit(step)(state, batch)
        return float(m["loss"])
    r = loss_ssm(None, ParallelConfig())
    c = loss_ssm(mesh, ParallelConfig(attention_parallelism="context"))
    assert abs(r - c) < 1e-4, (r, c)
    print("ssm ok", r, c)
    print("ALL_OK")
""")


def run_py(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_equals_single_device():
    assert "ALL_OK" in run_py(CODE)


# A smoke config's sharded train step (``make_sharded_train_step``) or its
# prefill + one decode step, on MESH, against the same config off-mesh.
SMOKE = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.configs import get_smoke_config
    from repro.core.config import ParallelConfig, TrainConfig
    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model
    from repro.parallel.sharding import fit_spec
    from repro.training import train_step as TS

    cfg = get_smoke_config(ARCH)
    mesh = make_mesh(MESH, AXES)
    pc = ParallelConfig(**PC)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)

    def train(mesh):
        model = build_model(cfg, pc, mesh)
        tc = TrainConfig(global_batch=8, seq_len=32, total_steps=1)
        state = TS.init_sharded_train_state(model, jax.random.PRNGKey(0), tc)
        step = TS.make_sharded_train_step(model, tc)
        batch = {"tokens": tokens}
        if mesh is None:
            _, m = step(state, batch)
        else:
            batch = jax.device_put(batch, TS.host_batch_sharding(model))
            with mesh:
                _, m = step(state, batch)
        return float(m["loss"]), float(m["grad_norm"])

    def decode(mesh):
        # prefill 16 rows into a 32-row cache, then one greedy decode step
        model = build_model(cfg, pc, mesh)
        params = model.init(jax.random.PRNGKey(0))
        prompt = {"tokens": tokens[:4, :16]}
        prefill = lambda p, b: model.prefill(p, b, 32)
        greedy = lambda lg: jnp.argmax(lg[:, -1], axis=-1)[:, None]
        if mesh is None:
            lg0, cache = jax.jit(prefill)(params, prompt)
            lg1, _ = jax.jit(model.decode_step)(params, cache, greedy(lg0))
        else:
            rep = NamedSharding(mesh, PartitionSpec())
            psh = jax.tree.map(
                lambda p, s: NamedSharding(mesh, fit_spec(p.shape, mesh, s)),
                params, model.param_specs())
            params = jax.device_put(params, psh)
            with mesh:
                lg0, cache = jax.jit(prefill, in_shardings=(psh, rep))(
                    params, prompt)
                csh = model.cache_shardings(cache)
                step = jax.jit(model.decode_step,
                               in_shardings=(psh, csh, rep),
                               out_shardings=(rep, csh))
                lg1, _ = step(params, jax.device_put(cache, csh),
                              jax.device_put(greedy(lg0), rep))
        return [np.asarray(lg0), np.asarray(lg1)]

    if KIND == "train":
        ref, got = train(None), train(mesh)
        print("ref", ref, "mesh", got)
        assert abs(got[0] - ref[0]) < 1e-4, (got, ref)
        assert abs(got[1] - ref[1]) / max(ref[1], 1) < 1e-3, (got, ref)
    else:
        ref, got = decode(None), decode(mesh)
        for r, g in zip(ref, got):
            print("max |dlogit|", float(np.abs(r - g).max()))
            np.testing.assert_array_equal(r.argmax(-1), g.argmax(-1))
            np.testing.assert_allclose(g, r, atol=1e-5, rtol=1e-4)
    print("SMOKE_OK")
""")


def _smoke(arch, kind, mesh, axes, pc):
    head = (f"ARCH, KIND, MESH, AXES, PC = "
            f"{arch!r}, {kind!r}, {mesh!r}, {axes!r}, {pc!r}\n")
    assert "SMOKE_OK" in run_py(head + SMOKE)


@pytest.mark.parametrize("kind", ["train", "decode"])
@pytest.mark.parametrize(
    "arch", ["qwen2-7b", "mamba2-2.7b", "jamba-1.5-large-398b"])
def test_smoke_config_sharded_equals_single_device(arch, kind):
    _smoke(arch, kind, (2, 4), ("data", "model"), {})


def test_pod_mesh_fsdp_equals_single_device():
    """The pod rules of ``axis_rules``: batch over (pod, data), weights
    FSDP-sharded over both."""
    _smoke("qwen2-7b", "train", (2, 2, 2), ("pod", "data", "model"),
           {"fsdp_axes": ("pod", "data")})
