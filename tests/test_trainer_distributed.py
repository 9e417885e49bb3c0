"""Distributed training engine acceptance suite.

Single-device (run in-process):
  * gradient accumulation: ``accum_steps=4`` equals one 4×-larger batch
    (CLM all-ones masks AND MLM uneven masks — token-weighted accumulation)
  * kill -> ``resume_from`` reproduces the uninterrupted run bit-exactly
    (full TrainState + data-iterator cursor round-trip)
  * steady-state transfer contract: ONE bulk ``jax.device_get`` per log
    interval and no implicit transfers (``jax.transfer_guard``)

8-virtual-device mesh (subprocess, ``xla_force_host_platform_device_count``):
  * sharded Trainer loss/grad-norm trajectory matches single-device
  * a checkpoint written on mesh (2,4) restores onto mesh (4,2) with
    identical leaf values and keeps training there
"""
import os
import subprocess
import sys
import textwrap
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.core.config import ModelConfig, TrainConfig
from repro.data.dataset import build_synthetic_protein_memmap
from repro.data.pipeline import CLMBatches, MLMBatches
from repro.models.model import build_model
from repro.training import train_step as TS
from repro.training.loop import Trainer

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def tiny_cfg(**kw):
    base = dict(
        name="tiny", family="dense", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=64, dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


def tiny_tc(**kw):
    base = dict(
        global_batch=8, seq_len=32, total_steps=6, log_every=2,
        warmup_steps=2, decay_steps=2, learning_rate=1e-3,
    )
    base.update(kw)
    return TrainConfig(**base)


def clm_pipeline(tmp_path, name="prot"):
    ds, _ = build_synthetic_protein_memmap(str(tmp_path / name), n=200, seed=0)
    return CLMBatches(ds, 8, 32, seed=0)


# --------------------------------------------------- gradient accumulation
def _one_step(model, tc, batch, params_key=0):
    state = TS.init_train_state(model, jax.random.PRNGKey(params_key), tc)
    new_state, metrics = jax.jit(TS.make_train_step(model, tc))(state, batch)
    return new_state, metrics


def test_accum_equals_large_batch_clm():
    cfg = tiny_cfg()
    model = build_model(cfg)
    tc = tiny_tc()
    batch = {
        "tokens": np.random.default_rng(0)
        .integers(0, 64, size=(8, 32))
        .astype(np.int32)
    }
    s1, m1 = _one_step(model, tc, batch)
    s4, m4 = _one_step(model, replace(tc, accum_steps=4), batch)
    _assert_step_equivalent(s1, m1, s4, m4)


def _assert_step_equivalent(s1, m1, s4, m4):
    # a wrong accumulation scheme (unweighted mean, missing fp32
    # accumulators, sum instead of mean) diverges at O(1e-4)+; the slack
    # below only absorbs f32 reduction-order noise, which varies with CPU
    # thread availability under load
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 5e-5
    assert abs(float(m1["grad_norm"]) - float(m4["grad_norm"])) < 5e-4
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s4.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )


def test_accum_equals_large_batch_mlm_uneven_masks(tmp_path):
    """MLM microbatches mask different token counts — token-weighted
    accumulation must still reproduce the single large-batch step."""
    cfg = tiny_cfg(objective="mlm", causal=False, vocab_size=33)
    model = build_model(cfg)
    tc = tiny_tc()
    ds, tok = build_synthetic_protein_memmap(str(tmp_path / "prot"), n=200, seed=0)
    batch = next(iter(MLMBatches(ds, tok, None, 8, 32)))
    # uneven by construction: per-microbatch (2-row) masked-token counts
    counts = batch["loss_mask"].reshape(4, -1).sum(axis=1)
    assert len(set(counts.tolist())) > 1, counts
    s1, m1 = _one_step(model, tc, batch)
    s4, m4 = _one_step(model, replace(tc, accum_steps=4), batch)
    _assert_step_equivalent(s1, m1, s4, m4)


def test_accum_requires_divisible_batch():
    model = build_model(tiny_cfg())
    tc = tiny_tc(accum_steps=3)
    batch = {"tokens": np.zeros((8, 32), np.int32)}
    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(TS.make_train_step(model, tc))(
            TS.init_train_state(model, jax.random.PRNGKey(0), tc), batch
        )


# ----------------------------------------------------------- resume exact
def test_save_resume_bit_exact(tmp_path):
    """Kill at step 3 of 6, resume from the checkpoint with the SAME
    config: params, optimizer moments and step counter must match the
    uninterrupted run bit-for-bit (state + data cursor round-trip)."""
    cfg = tiny_cfg()
    tc = tiny_tc(ckpt_every=3, ckpt_dir=str(tmp_path / "ck"))
    s_full, _ = Trainer(build_model(cfg), tc, verbose=False).run(
        clm_pipeline(tmp_path, "a")
    )
    s_res, hist = Trainer(build_model(cfg), tc, verbose=False).run(
        clm_pipeline(tmp_path, "b"),
        resume_from=str(tmp_path / "ck" / "step_3"),
    )
    assert [m["step"] for m in hist] == [4, 5]
    for a, b in zip(
        jax.tree.leaves((s_full.params, s_full.opt)),
        jax.tree.leaves((s_res.params, s_res.opt)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_restores_counters_and_cursor(tmp_path):
    """tokens_seen continues across the resume and the restored pipeline
    draws the exact batch the interrupted run would have drawn next."""
    cfg = tiny_cfg()
    tc = tiny_tc(ckpt_every=3, ckpt_dir=str(tmp_path / "ck"))
    tr_a = Trainer(build_model(cfg), tc, verbose=False)
    _, hist_a = tr_a.run(clm_pipeline(tmp_path, "a"))

    pipe_b = clm_pipeline(tmp_path, "b")
    tr_b = Trainer(build_model(cfg), tc, verbose=False)
    tr_b.load(str(tmp_path / "ck" / "step_3"), pipe_b)
    assert tr_b.step_idx == 3
    # the cursor says 4 batches were drawn (3 consumed + none beyond: the
    # snapshot is per-consumed-batch, prefetch depth must not leak)
    ref = clm_pipeline(tmp_path, "c")
    ref_it = iter(ref)
    for _ in range(3):
        next(ref_it)
    want = next(ref_it)["tokens"]
    got = next(iter(pipe_b))["tokens"]
    np.testing.assert_array_equal(want, got)
    # uninterrupted tokens_seen at the end equals resumed run's total
    _, hist_b = tr_b.run(pipe_b)  # prepare() keeps the loaded state
    assert hist_b[-1]["tokens_seen"] == hist_a[-1]["tokens_seen"]


def test_resume_tokens_seen_at_misaligned_checkpoint(tmp_path):
    """A checkpoint between log flushes must still count the steps whose
    metrics are pending (ckpt_every=2 vs log_every=3: step_2 is saved
    while step 1's metrics sit unflushed)."""
    cfg = tiny_cfg()
    tc = tiny_tc(total_steps=6, log_every=3, ckpt_every=2,
                 ckpt_dir=str(tmp_path / "ck"))
    _, hist_a = Trainer(build_model(cfg), tc, verbose=False).run(
        clm_pipeline(tmp_path, "a")
    )
    _, hist_b = Trainer(build_model(cfg), tc, verbose=False).run(
        clm_pipeline(tmp_path, "b"),
        resume_from=str(tmp_path / "ck" / "step_2"),
    )
    per_step = 8 * 31
    assert hist_a[-1]["tokens_seen"] == 6 * per_step
    assert hist_b[-1]["tokens_seen"] == 6 * per_step


def test_seq2seq_pipeline_cursor(tmp_path):
    """The enc-dec launcher pipeline delegates the resume cursor to its
    underlying CLM packer (a raw generator would silently replay)."""
    from repro.launch.train import Seq2SeqBatches

    ds, _ = build_synthetic_protein_memmap(str(tmp_path / "p"), n=100, seed=0)
    pipe = Seq2SeqBatches(CLMBatches(ds, 4, 16, seed=0))
    it = iter(pipe)
    for _ in range(2):
        next(it)
    cursor = pipe.state_dict()
    want = next(iter(pipe))
    pipe2 = Seq2SeqBatches(CLMBatches(ds, 4, 16, seed=1))
    pipe2.load_state_dict(cursor)
    got = next(iter(pipe2))
    np.testing.assert_array_equal(want["tokens"], got["tokens"])
    np.testing.assert_array_equal(got["src_tokens"], got["tokens"])


# ------------------------------------------------- steady-state transfers
def test_one_bulk_transfer_per_log_interval(tmp_path, monkeypatch):
    """Acceptance: metrics stay on device between logs — a steady-state
    trainer step performs NO implicit transfers, and each log interval
    costs exactly ONE bulk device_get (serving-engine contract)."""
    cfg = tiny_cfg()
    tc = tiny_tc(total_steps=9, log_every=3)
    tr = Trainer(build_model(cfg), tc, verbose=False)
    tr.prepare(clm_pipeline(tmp_path))
    tr.step()  # s=0: compile + first log flush, outside the guard
    calls = []
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: calls.append(1) or real_get(x)
    )
    with jax.transfer_guard("disallow"):
        while tr.step_idx < tc.total_steps:
            tr.step()
    # steps 1..8 under the guard flush at s=3, s=6, s=8
    assert len(calls) == 3, f"expected 3 bulk transfers, saw {len(calls)}"


def test_token_accounting_every_step(tmp_path):
    """tokens_seen counts EVERY step once (the old loop multiplied the
    logged step's count by log_every — wrong at step 0 and the final
    line) and tokens_per_sec is reported."""
    cfg = tiny_cfg()
    tc = tiny_tc(total_steps=5, log_every=2)
    _, hist = Trainer(build_model(cfg), tc, verbose=False).run(
        clm_pipeline(tmp_path)
    )
    # CLM: (seq_len - 1) targets per row, every step
    per_step = 8 * 31
    assert [m["tokens_seen"] for m in hist] == [
        per_step, 3 * per_step, 5 * per_step
    ]
    assert all(m["tokens_per_sec"] > 0 for m in hist)
    assert all("step_time" in m for m in hist)


# ------------------------------------------------------ 8-device subprocess
CODE = textwrap.dedent("""
    import tempfile
    from dataclasses import replace
    import jax, numpy as np
    from repro.core.config import ModelConfig, ParallelConfig, TrainConfig
    from repro.models.model import build_model
    from repro.data.dataset import build_synthetic_protein_memmap
    from repro.data.pipeline import CLMBatches
    from repro.training.loop import Trainer
    from repro.training import train_step as TS
    from repro.checkpoint import ckpt
    from repro.launch.mesh import make_mesh

    assert jax.device_count() == 8, jax.device_count()
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=8, num_kv_heads=2, d_ff=128, vocab_size=128,
                      dtype="float32")
    tmp = tempfile.mkdtemp()
    ds, _ = build_synthetic_protein_memmap(tmp + "/prot", n=200, seed=0)
    def pipe():
        return CLMBatches(ds, 8, 32, seed=0)
    tc = TrainConfig(global_batch=8, seq_len=32, total_steps=4, log_every=1,
                     warmup_steps=1, decay_steps=1, learning_rate=1e-3)

    # (a) sharded loss/grad-norm trajectory matches single-device
    _, h_ref = Trainer(build_model(cfg), tc, verbose=False).run(pipe())
    mesh = make_mesh((2, 4), ("data", "model"))
    m_sh = build_model(cfg, ParallelConfig(), mesh)
    tr_sh = Trainer(m_sh, tc, verbose=False)
    state_sh, h_sh = tr_sh.run(pipe())
    for a, b in zip(h_ref, h_sh):
        assert abs(a["loss"] - b["loss"]) < 1e-4, (a["loss"], b["loss"])
        assert abs(a["grad_norm"] - b["grad_norm"]) / max(b["grad_norm"], 1) < 1e-3
    print("trajectory ok")

    # (d) checkpoint saved on (2,4) restores onto (4,2): identical leaves
    ckdir = tmp + "/ck"
    tr_sh.save(ckdir)
    mesh2 = make_mesh((4, 2), ("data", "model"))
    m2 = build_model(cfg, ParallelConfig(), mesh2)
    st2, step2, extra = ckpt.restore_train_state(
        ckdir, TS.abstract_train_state(m2), TS.state_shardings(m2))
    assert step2 == 4 and extra["step_idx"] == 4, (step2, extra)
    for a, b in zip(jax.tree.leaves((state_sh.params, state_sh.opt)),
                    jax.tree.leaves((st2.params, st2.opt))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("remesh restore ok")

    # ... and training continues from it on the new mesh shape
    tc2 = replace(tc, total_steps=6)
    _, h2 = Trainer(m2, tc2, verbose=False).run(pipe(), resume_from=ckdir)
    assert [m["step"] for m in h2] == [4, 5], h2
    print("ALL_OK")
""")


def test_sharded_trainer_8dev_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", CODE], capture_output=True, text=True, env=env,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL_OK" in out.stdout


# ------------------------------------------------------- non-finite guard
def test_nonfinite_step_withholds_update():
    """A step with NaN loss applies NO update: params and AdamW moments
    keep their old values and opt.step does not advance (so the lr
    schedule is unaffected); the metrics carry skipped=1."""
    model = build_model(tiny_cfg())
    tc = tiny_tc()
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, 64, size=(8, 32)).astype(np.int32)}
    step_fn = jax.jit(TS.make_train_step(model, tc))
    state = TS.init_train_state(model, jax.random.PRNGKey(0), tc)
    s1, m1 = step_fn(state, batch)
    assert float(m1["skipped"]) == 0.0
    assert int(s1.opt.step) == 1
    # poison the params: the forward loss goes non-finite, and without
    # the guard the "update" would overwrite everything with NaN
    import jax.numpy as jnp

    poisoned = jax.tree.map(
        lambda p: p.at[(0,) * p.ndim].set(jnp.nan)
        if jnp.issubdtype(p.dtype, jnp.floating) else p,
        s1.params,
    )
    from repro.training.train_step import TrainState

    s2, m2 = step_fn(TrainState(poisoned, s1.opt), batch)
    assert float(m2["skipped"]) == 1.0
    assert not np.isfinite(float(m2["loss"]))
    assert int(s2.opt.step) == 1  # did not advance
    for got, want in zip(jax.tree.leaves(s2.params), jax.tree.leaves(poisoned)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(jax.tree.leaves(s2.opt.mu), jax.tree.leaves(s1.opt.mu)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_trainer_aborts_after_consecutive_nonfinite(tmp_path):
    """K consecutive skipped steps abort the run with the offending step
    number instead of silently flatlining for the rest of the schedule."""
    from repro.training.loop import NonFiniteLossError

    model = build_model(tiny_cfg())
    tc = tiny_tc(total_steps=10, log_every=1, max_nonfinite_skips=3)
    pipe = clm_pipeline(tmp_path, name="nanprot")
    state = TS.init_train_state(model, jax.random.PRNGKey(0), tc)
    import jax.numpy as jnp

    state.params = jax.tree.map(
        lambda p: jnp.full_like(p, jnp.nan)
        if jnp.issubdtype(p.dtype, jnp.floating) else p,
        state.params,
    )
    tr = Trainer(model, tc, verbose=False)
    tr.prepare(pipe, state=state)
    with pytest.raises(NonFiniteLossError) as ei:
        while tr.step_idx < tc.total_steps:
            tr.step()
    assert ei.value.skips == 3
    assert ei.value.step == 2  # steps 0,1,2 skipped -> streak hits 3 at 2
    assert tr.skipped_total == 3


def test_trainer_compile_error_propagates(tmp_path, monkeypatch):
    """A train step that does not compile fails the run; the Trainer
    never falls back to compiling on dispatch."""
    tr = Trainer(build_model(tiny_cfg()), tiny_tc(), verbose=False)

    def refuse(*a, **k):
        raise RuntimeError("compiler refused the step")

    monkeypatch.setattr(tr._jit_step, "lower", refuse)
    with pytest.raises(RuntimeError, match="compiler refused"):
        tr.run(clm_pipeline(tmp_path))
