"""Training-engine smoke bench: tokens/s, step time, accumulation on/off.

Runs the distributed Trainer (single device on this CPU container; the
same code path drives the mesh) over a tiny CLM model and reports:

  * ``train_tps_accum1`` / ``train_tps_accum4`` — tokens/s and mean step
    time with gradient accumulation off/on (accum=4 microbatches)

The steady-state host-transfer contract is ASSERTED, not just reported:
the guarded portion of each run must perform exactly one bulk
``jax.device_get`` per log interval and no implicit transfers
(``jax.transfer_guard("disallow")``), mirroring the serving bench's
single-transfer regression.
"""
from __future__ import annotations

import tempfile

import jax


def _run_one(report, accum: int) -> None:
    from repro.core.config import ModelConfig, TrainConfig
    from repro.data.dataset import build_synthetic_protein_memmap
    from repro.data.pipeline import CLMBatches
    from repro.models.model import build_model
    from repro.training.loop import Trainer

    cfg = ModelConfig(
        name="train-bench", family="dense", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=256, vocab_size=64,
        dtype="float32",
    )
    tmp = tempfile.mkdtemp(prefix="repro_train_bench_")
    ds, _ = build_synthetic_protein_memmap(tmp + "/prot", n=400, seed=0)
    tc = TrainConfig(
        global_batch=8, seq_len=64, total_steps=10, log_every=4,
        warmup_steps=2, decay_steps=2, learning_rate=1e-3,
        accum_steps=accum,
    )
    tr = Trainer(build_model(cfg), tc, verbose=False)
    tr.prepare(CLMBatches(ds, tc.global_batch, tc.seq_len, seed=0))
    tr.step()  # s=0: compile + first log flush, outside the guard

    calls = []
    real_get = jax.device_get
    jax.device_get = lambda x: calls.append(1) or real_get(x)
    try:
        with jax.transfer_guard("disallow"):
            while tr.step_idx < tc.total_steps:
                tr.step()
    finally:
        jax.device_get = real_get
    # steps 1..9 under the guard flush at s=4, s=8, s=9
    assert len(calls) == 3, f"expected 3 bulk transfers, saw {len(calls)}"

    last = tr.history[-1]
    report(
        f"train_step_accum{accum}",
        last["step_time"] * 1e6,
        f"tok/s={last['tokens_per_sec']:.0f}",
    )


def run(report) -> None:
    for accum in (1, 4):
        _run_one(report, accum)


if __name__ == "__main__":
    rows = []
    print("name,us_per_call,derived")
    run(lambda n, us, d="": (rows.append(n), print(f"{n},{us:.1f},{d}")))
    assert rows
