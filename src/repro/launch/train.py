"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch esm2-650m \
        --steps 200 --batch 8 --seq 128 [--smoke] [--accum 4] \
        [--mesh auto|none|DxM] [--resume auto|<ckpt_dir>]

On this CPU container ``--smoke`` (reduced config) is the practical mode;
the same launcher drives the full config on a real TPU mesh.  When more
than one device is present (a real mesh, or CPU simulation via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the launcher
constructs a (data, model) mesh and the Trainer runs the sharded train
step; ``--mesh 4x2`` pins the shape explicitly, ``--mesh none`` forces the
single-device path.

Telemetry: ``--metrics-dir DIR`` feeds the unified registry
(``repro.obs``) and refreshes a Prometheus exposition + JSON snapshot
there at every log flush; ``--profile DIR`` captures a ``jax.profiler``
trace of the whole run.  The trainer's spans (``train.data``,
``train.compile``, ``train.dispatch``, ``train.flush``,
``train.checkpoint``) are always recorded in the step log, and their
means are printed at exit.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.configs import get_config, get_smoke_config
from repro.core.config import ParallelConfig, TrainConfig
from repro.data.dataset import (
    MemmapTokenDataset,
    build_synthetic_protein_memmap,
    build_synthetic_protein_store,
)
from repro.data.pipeline import CLMBatches, MLMBatches
from repro.data.producer import BackgroundProducer
from repro.data.sampler import ClusterSampler, greedy_length_clusters
from repro.data.size_aware import SizeAwareSampler
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import build_model
from repro.training.loop import Trainer


class Seq2SeqBatches:
    """CLM packing with a ``src_tokens`` mirror (enc-dec archs), delegating
    the resume cursor to the underlying pipeline."""

    def __init__(self, base: CLMBatches):
        self.base = base

    def state_dict(self):
        return self.base.state_dict()

    def load_state_dict(self, st):
        self.base.load_state_dict(st)

    def __iter__(self):
        for b in self.base:
            b = dict(b)
            b["src_tokens"] = b["tokens"]
            yield b


def make_batches(cfg, tc: TrainConfig, data_dir: str, seed: int = 0, *,
                 sharded: bool = False, max_tokens: int = 0,
                 producer_depth: int = 0, round_to: int = 1):
    """Returns the pipeline OBJECT (not an iterator) so the Trainer can
    checkpoint/restore its cursor (``state_dict``/``load_state_dict``).

    ``sharded`` feeds from the multi-shard memmap store instead of the
    single-file dataset; ``max_tokens`` > 0 switches to size-aware
    (token-budget) batching with per-bucket shapes, ``round_to`` keeping
    every batch's row count divisible by the mesh's data axis;
    ``producer_depth`` > 0 wraps the pipeline in a background producer.
    """
    if sharded:
        ds, tok = build_synthetic_protein_store(
            f"{data_dir}/protein_store", n=2000, seed=seed
        )
    else:
        ds, tok = build_synthetic_protein_memmap(
            f"{data_dir}/protein", n=2000, seed=seed
        )
    lengths = ds.lengths()
    base = ClusterSampler(greedy_length_clusters(lengths, 64), seed=seed)
    if cfg.objective == "mlm":
        if max_tokens:
            sampler = SizeAwareSampler(
                np.minimum(lengths, tc.seq_len), max_tokens,
                base=base, round_to=round_to,
            )
        else:
            sampler = base
        pipe = MLMBatches(ds, tok, sampler, tc.global_batch, tc.seq_len,
                          cfg.mlm_mask_prob, seed)
    elif cfg.is_encoder_decoder:
        pipe = Seq2SeqBatches(
            CLMBatches(ds, tc.global_batch, tc.seq_len, seed,
                       eos_id=tok.eos_id)
        )
    else:
        sampler = (
            SizeAwareSampler(np.minimum(lengths, tc.seq_len), max_tokens,
                             base=base, round_to=round_to)
            if max_tokens else None
        )
        pipe = CLMBatches(ds, tc.global_batch, tc.seq_len, seed,
                          eos_id=tok.eos_id, sampler=sampler)
    if producer_depth:
        pipe = BackgroundProducer(pipe, depth=producer_depth)
    return pipe


def build_mesh(spec: str):
    """"auto" = (n_devices, 1) data-parallel mesh when >1 device is
    visible; "none" = single-device; "DxM" = explicit (data, model)."""
    n = jax.device_count()
    if spec == "none":
        return None
    if spec == "auto":
        return make_mesh((n, 1), ("data", "model")) if n > 1 else None
    d, m = (int(x) for x in spec.lower().split("x"))
    return make_mesh((d, m), ("data", "model"))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="esm2-650m")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--warmup", type=int, default=0,
                   help="warmup steps (0 = steps//10)")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient-accumulation microbatches per step")
    p.add_argument("--mesh", default="auto",
                   help="auto | none | DxM, e.g. 4x2 = (data=4, model=2)")
    p.add_argument("--smoke", action="store_true", help="reduced config")
    p.add_argument("--data-dir", default="/tmp/repro_data")
    p.add_argument("--sharded-data", action="store_true",
                   help="feed from the multi-shard memmap store "
                        "(repro.data.store) instead of the single-file "
                        "dataset")
    p.add_argument("--max-tokens-per-batch", type=int, default=0,
                   help="enable size-aware (token-budget) batching: "
                        "variable-row batches padded per length bucket, "
                        "every batch under this many padded tokens "
                        "(0 = fixed --batch x --seq shapes)")
    p.add_argument("--producer", type=int, default=0,
                   help="background-producer prefetch depth (0 = build "
                        "batches inline on the consumer thread)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=0,
                   help="checkpoint period in steps (0 = final-only when "
                        "--ckpt-dir is set)")
    p.add_argument("--resume", default="",
                   help="checkpoint dir to resume from, or 'auto' = latest "
                        "step_* under --ckpt-dir")
    p.add_argument("--history-out", default="")
    p.add_argument("--metrics-dir", default="",
                   help="write Prometheus exposition + JSON metric snapshots "
                        "here (refreshed every log flush via a trainer hook)")
    p.add_argument("--profile", default="",
                   help="capture a jax.profiler trace of the run into this "
                        "directory")
    a = p.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(a.arch) if a.smoke else get_config(a.arch)
    tc = TrainConfig(
        global_batch=a.batch, seq_len=a.seq, learning_rate=a.lr,
        accum_steps=a.accum,
        total_steps=a.steps,
        warmup_steps=a.warmup or max(a.steps // 10, 1),
        decay_steps=max(a.steps // 10, 1),
        ckpt_dir=a.ckpt_dir,
        ckpt_every=a.ckpt_every or (a.steps if a.ckpt_dir else 0),
    )
    print("resolved TrainConfig:")
    print(json.dumps(dataclasses.asdict(tc), indent=1))
    mesh = build_mesh(a.mesh)
    model = build_model(cfg, ParallelConfig(), mesh)
    print(
        f"arch={cfg.name} params(analytic)={cfg.param_count():,} "
        f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else None}"
    )
    # size-aware batches must keep rows divisible by the data axis so
    # sharded placement never sees a ragged leading dim
    data_axis = (
        dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
        if mesh is not None else 1
    )
    batches = make_batches(
        cfg, tc, a.data_dir,
        sharded=a.sharded_data, max_tokens=a.max_tokens_per_batch,
        producer_depth=a.producer, round_to=data_axis,
    )
    resume = a.resume
    if resume == "auto":
        resume = ckpt.latest_step(a.ckpt_dir) or ""
        print(f"resume: {resume or '(no checkpoint found — cold start)'}")
    from repro.obs import STEP_LOG, MetricsRegistry, trace_ctx

    reg = MetricsRegistry() if a.metrics_dir else None
    hooks = []
    if reg is not None:
        import os

        os.makedirs(a.metrics_dir, exist_ok=True)

        def _dump(step, m, _reg=reg, _dir=a.metrics_dir):
            # refreshed at every log flush: mid-run dashboards see live
            # tokens/s / grad-norm, not just the final summary
            _reg.write_prometheus(os.path.join(_dir, "train.prom"))
            _reg.dump_json(os.path.join(_dir, "train_metrics.json"))

        hooks.append(_dump)
    trainer = Trainer(model, tc, hooks=hooks, metrics=reg)
    try:
        with trace_ctx(a.profile):
            state, history = trainer.run(batches, resume_from=resume or None)
    finally:
        if hasattr(batches, "close"):
            batches.close()
    print("step log:")
    for line in STEP_LOG.report("train").splitlines():
        print(f"  {line}")
    if a.history_out:
        with open(a.history_out, "w") as f:
            json.dump(history, f, indent=1)
    if history:
        print(
            f"final loss {history[-1]['loss']:.4f} "
            f"(from {history[0]['loss']:.4f})  "
            f"{history[-1]['tokens_per_sec']:.0f} tok/s  "
            f"tokens_seen={history[-1]['tokens_seen']:.0f}"
        )


if __name__ == "__main__":
    main()
