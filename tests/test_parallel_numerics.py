"""Sharded execution must be numerically equivalent to single-device:
head-TP and context-parallel losses/grad-norms match the mesh-free run.
(Subprocess: needs 8 placeholder devices.)"""
import os
import subprocess
import sys
import textwrap

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


def test_sharded_equals_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", CODE], capture_output=True, text=True, env=env,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL_OK" in out.stdout
