"""Dry-run machinery on a small placeholder-device mesh (subprocess: the
XLA device-count flag must be set before jax initializes — we keep the main
pytest process at 1 device per the project rules).

Also validates the scan-aware HLO cost analyzer against XLA's own
cost_analysis on unrolled modules.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_hlo_analyzer_matches_xla_on_unrolled():
    from repro.launch.hlo_cost import analyze
    import jax, jax.numpy as jnp

    def f(w, x):
        for _ in range(6):
            x = jnp.tanh(x @ w)
        return x.sum()

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32),
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
    ).compile()
    a = analyze(comp.as_text())
    ca = comp.cost_analysis()
    if isinstance(ca, list):  # older jax wrapped the dict in a 1-elem list
        ca = ca[0]
    assert abs(a["flops"] - ca["flops"]) / ca["flops"] < 0.05
    assert abs(a["hbm_bytes"] - ca["bytes accessed"]) / ca["bytes accessed"] < 0.25


def test_hlo_analyzer_scan_equals_unroll():
    from repro.launch.hlo_cost import analyze
    import jax, jax.numpy as jnp

    def f_scan(w, x):
        y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None, length=6)
        return y.sum()

    def f_unroll(w, x):
        for _ in range(6):
            x = jnp.tanh(x @ w)
        return x.sum()

    shapes = (
        jax.ShapeDtypeStruct((128, 128), jnp.float32),
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
    )
    a_s = analyze(jax.jit(f_scan).lower(*shapes).compile().as_text())
    a_u = analyze(jax.jit(f_unroll).lower(*shapes).compile().as_text())
    assert a_s["flops"] == a_u["flops"]
    assert abs(a_s["hbm_bytes"] - a_u["hbm_bytes"]) / a_u["hbm_bytes"] < 0.2


@pytest.mark.parametrize("arch", ["qwen2-7b", "mamba2-2.7b", "jamba-1.5-large-398b"])
def test_dryrun_bundle_small_mesh(arch):
    code = textwrap.dedent(f"""
        import jax, json
        from repro.core.config import ParallelConfig
        from repro.configs import get_smoke_config
        from repro.launch.shapes import InputShape, dryrun_bundle
        from repro.launch.hlo_cost import analyze
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke_config("{arch}")
        for shp in [InputShape("t", 64, 8, "train"), InputShape("d", 64, 8, "decode")]:
            fn, args, in_sh, meta = dryrun_bundle(cfg, shp, mesh, ParallelConfig())
            with mesh:
                comp = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
            a = analyze(comp.as_text())
            assert a["flops"] > 0
            print(json.dumps({{"kind": shp.kind, "flops": a["flops"],
                               "colls": sorted(a["collectives"]) }}))
    """)
    out = run_py(code)
    lines = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    assert len(lines) == 2
    assert all(l["flops"] > 0 for l in lines)


def test_multipod_mini_mesh():
    """(pod, data, model) 3-axis mesh lowers and shards the pod axis."""
    code = textwrap.dedent("""
        import jax, json
        from repro.core.config import ParallelConfig
        from repro.configs import get_smoke_config
        from repro.launch.shapes import InputShape, dryrun_bundle
        from repro.launch.hlo_cost import analyze
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_smoke_config("qwen2-7b")
        shp = InputShape("t", 64, 8, "train")
        pc = ParallelConfig(fsdp_axes=("pod", "data"))
        fn, args, in_sh, meta = dryrun_bundle(cfg, shp, mesh, pc)
        with mesh:
            comp = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        a = analyze(comp.as_text())
        print(json.dumps({"flops": a["flops"], "ncolls": len(a["collectives"])}))
    """)
    out = run_py(code)
    rec = json.loads([l for l in out.splitlines() if l.startswith("{")][0])
    assert rec["flops"] > 0 and rec["ncolls"] >= 1


def test_production_mesh_shapes():
    code = textwrap.dedent("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        print(m1.devices.shape, m1.axis_names)
        print(m2.devices.shape, m2.axis_names)
    """)
    out = run_py(code, devices=512)
    assert "(16, 16) ('data', 'model')" in out
    assert "(2, 16, 16) ('pod', 'data', 'model')" in out


def test_hlo_analyzer_nested_scans_multiply():
    """scan-inside-scan (layer scan × attention kv scan): flops must equal
    the fully unrolled program — multipliers compose across while nesting."""
    from repro.launch.hlo_cost import analyze
    import jax, jax.numpy as jnp

    def inner(x, w):  # kv-block-style scan
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=4)
        return y

    def f_nested(w, x):
        def layer(c, _):
            return inner(c, w), None
        y, _ = jax.lax.scan(layer, x, None, length=3)
        return y.sum()

    def f_unrolled(w, x):
        for _ in range(3):
            for _ in range(4):
                x = jnp.tanh(x @ w)
        return x.sum()

    shapes = (
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((32, 64), jnp.float32),
    )
    a_n = analyze(jax.jit(f_nested).lower(*shapes).compile().as_text())
    a_u = analyze(jax.jit(f_unrolled).lower(*shapes).compile().as_text())
    assert a_n["flops"] == a_u["flops"] == 2 * 32 * 64 * 64 * 12
