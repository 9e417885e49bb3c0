"""Wiring of ``chip_smoke.py``, run on the CPU at a reduced size.

The phases run here with reduced configs and the Pallas kernels in
interpret mode; the reference comparisons and the contract line are the
real ones.  This checks the script, not the chip: only a run on a TPU
says the main paths work there.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402


def test_train_matches_reference_in_interpret_mode(tmp_path):
    cfg = dataclasses.replace(
        get_smoke_config("esm2-650m"), kernel_impl="pallas_interpret"
    )
    tc = dataclasses.replace(
        chip_smoke._train_config(3), global_batch=2, seq_len=32
    )
    r = chip_smoke.train(cfg, tc, str(tmp_path), ref_impl="xla")
    assert len(r["losses"]) == 3
    assert chip_smoke._close(r["losses"][0], r["ref_loss0"])
    assert r["grad_gap"][0] <= chip_smoke.GRAD_RTOL
    assert r["compile_s"] > 0


def _fault_dk_zeroed(monkeypatch):
    """Flash-attention backward returns dK = 0."""
    from repro.kernels import ops

    bwd = ops._fa.flash_attention_bwd

    def broken(*args, **kw):
        dq, dk, dv = bwd(*args, **kw)
        return dq, jnp.zeros_like(dk), dv

    monkeypatch.setattr(ops._fa, "flash_attention_bwd", broken)


def _fault_ce_skips_vocab_block(monkeypatch):
    """Fused cross-entropy leaves the last 16 vocab entries out."""
    from repro.kernels import ops

    fwd = ops._ce.fused_cross_entropy

    def broken(hidden, w_out, targets, *, vocab, **kw):
        return fwd(hidden, w_out, targets, vocab=vocab - 16, **kw)

    monkeypatch.setattr(ops._ce, "fused_cross_entropy", broken)


@pytest.mark.parametrize("plant", [_fault_dk_zeroed,
                                   _fault_ce_skips_vocab_block])
def test_train_check_catches_a_planted_kernel_fault(tmp_path, monkeypatch,
                                                    plant):
    plant(monkeypatch)
    cfg = dataclasses.replace(
        get_smoke_config("esm2-650m"), kernel_impl="pallas_interpret"
    )
    tc = dataclasses.replace(
        chip_smoke._train_config(3), global_batch=2, seq_len=32
    )
    with pytest.raises(AssertionError, match="reference"):
        chip_smoke.train_vs_xla(cfg, tc, str(tmp_path))


def test_train_reference_disagreement_raises(tmp_path, monkeypatch):
    # no tolerance admits any pair of losses: the comparison must fail
    monkeypatch.setattr(chip_smoke, "LOSS_RTOL", -1.0)
    cfg = dataclasses.replace(get_smoke_config("esm2-650m"), kernel_impl="xla")
    tc = dataclasses.replace(
        chip_smoke._train_config(1), global_batch=2, seq_len=32
    )
    with pytest.raises(AssertionError, match="reference"):
        chip_smoke.train(cfg, tc, str(tmp_path), ref_impl="xla")


def test_serve_parity_in_interpret_mode():
    cfg = dataclasses.replace(
        get_smoke_config("qwen2-7b"), kernel_impl="pallas_interpret"
    )
    r = chip_smoke.serve(cfg, n=4, lengths=(8, 40), prefix_len=16,
                         max_new=4, chunk=16, page=8)
    assert r["parity"]["steps"] == 4 * 4
    assert r["parity"]["row_ulps"] <= chip_smoke.ROW_ULPS
    assert all(len(c.tokens) == 4 for c in r["cold"]["completions"])
    assert r["prefix_hit_tokens"] > 0


def test_serve_parity_catches_a_dropped_kv_head_in_paged_decode(monkeypatch):
    from repro.kernels import paged_attention

    decode = paged_attention.paged_flash_decode

    def broken(q, k_pool, v_pool, *args, **kw):
        return decode(q, k_pool, v_pool.at[:, :, 0].set(0), *args, **kw)

    monkeypatch.setattr(paged_attention, "paged_flash_decode", broken)
    cfg = dataclasses.replace(
        get_smoke_config("qwen2-7b"), kernel_impl="pallas_interpret"
    )
    with pytest.raises(AssertionError, match="bf16 ulps"):
        chip_smoke.serve(cfg, n=4, lengths=(8, 40), prefix_len=16,
                         max_new=4, chunk=16, page=8)


def test_serve_parity_rejects_a_token_that_is_no_tie():
    """Rows 3 ulps apart pass; a pick that takes a 3-ulp move of the
    measured row to flip is no tie."""
    xrow = np.linspace(-2.0, 1.0, 64).astype(np.float32)
    ulp = chip_smoke._bf16_ulp(2.0)
    a, b = 63, 62
    xrow[b] = 1.0 - 0.5 * ulp                   # the reference picks a
    row = xrow.copy()
    row[a], row[b] = 1.0 - 2.5 * ulp, 1.0 + 2.5 * ulp
    ref = [[(xrow, a, -1.0)]]
    sp = [chip_smoke.SamplingParams()]
    chip_smoke.check_parity(ref, ref, [[a]], [[a]], sp, "xla", "xla")
    with pytest.raises(AssertionError, match="not a tie"):
        chip_smoke.check_parity([[(row, b, -1.0)]], ref, [[a]], [[b]], sp,
                                "xla", "xla")


def test_contract_line_last_and_only_after_every_phase(capsys):
    chip_smoke.run_phases([lambda: print("phase ran")])
    lines = capsys.readouterr().out.splitlines()
    assert lines[0] == "phase ran"
    assert json.loads(lines[-1]) == {
        "ok": True, "device": chip_smoke.device_info(),
    }

    def broken():
        raise AssertionError("phase failed")

    with pytest.raises(AssertionError):
        chip_smoke.run_phases([lambda: None, broken])
    assert '"ok"' not in capsys.readouterr().out


def test_no_tpu_exits_nonzero_without_contract_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "cpu" in out.stderr


def test_compile_cache_follows_env_or_repo(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import REPO_ROOT, use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == was   # nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert use_compile_cache() == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_flip_ulps_measures_a_greedy_tie():
    row = np.linspace(-2.0, 2.0, 256).astype(np.float32)   # argmax: 255
    ulp = chip_smoke._bf16_ulp(2.0)
    row[100] = row[255] - 3 * ulp                          # 3 ulps behind
    sp = chip_smoke.SamplingParams()
    assert chip_smoke._flip_ulps(row, 255, sp, 0, ulp) == 0
    assert chip_smoke._flip_ulps(row, 100, sp, 0, ulp) == 2   # 2 + 2 >= 3
    assert chip_smoke._flip_ulps(row, 0, sp, 0, ulp) is None  # no tie


def test_sharded_phase_on_four_virtual_devices(tmp_path):
    """``--chips 4`` wiring: (4,1) and (2,2) meshes against one device,
    at a reduced size with the kernels interpreted per shard."""
    code = f"""
import dataclasses, sys
sys.path.insert(0, {REPO!r})
import chip_smoke as cs
from repro.configs import get_smoke_config
cs.get_config = lambda n: dataclasses.replace(
    get_smoke_config(n), kernel_impl="pallas_interpret")
cs.TRAIN_SEQ = 32
cs.DATA_DIR = {str(tmp_path)!r}
cs.run_phases([cs.sharded_phase])
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "mesh 2x2" in out.stdout
    assert json.loads(out.stdout.splitlines()[-1])["device"]["count"] == 4


def test_flip_ulps_measures_a_top_k_boundary_tie():
    """Sampled with top-k: the reference's pick ``b`` sits at the filter
    boundary; a measured pick ``a`` that wins once ``b`` drops out of the
    kept set is a tie."""
    from repro.kernels.sampling import gumbel_noise

    V, k = 256, 5
    sp = chip_smoke.SamplingParams(temperature=1.0, top_k=k, seed=7)
    g = np.asarray(gumbel_noise(jnp.full((1, 1), 7, jnp.uint32),
                                jnp.zeros((1, 1), jnp.uint32),
                                jnp.arange(V, dtype=jnp.uint32)[None]))[0]
    order = np.argsort(-g)
    b, a, low = order[0], order[1], order[-(k - 1):]
    ulp = chip_smoke._bf16_ulp(5.0)
    row = np.full(V, -5.0, np.float32)
    row[low] = 5.0                 # kept, but the noise sinks them
    row[b] = 1.0                   # the k-th highest: at the boundary
    row[a] = 1.0 - 3 * ulp         # the (k+1)-th, 3 ulps outside
    assert chip_smoke._flip_ulps(row, b, sp, 0, ulp) == 0
    assert chip_smoke._flip_ulps(row, a, sp, 0, ulp) == 2
