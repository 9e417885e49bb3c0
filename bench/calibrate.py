#!/usr/bin/env python3
"""Readings that set the limits of a cell's comparison.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 ... \\
        --control-seeds 21 22 23 [--out FILE]

In one process on the cell's chips, and with no measured window:

- ``program``: for each of ``--seeds``, the program's first steps read as
  a run reads them (``bench/entries/train.py``), against the reference;
- ``control``: for each of ``--control-seeds``, the reference computed in
  fp8 put in the program's place, against the reference;
- ``half_batch``: for the same seeds, the reference on the first half of
  each batch's rows, the mean taken over them, against the reference;
- ``no_exchange`` (cells on several chips): for the same seeds, the
  first step with the exchange between chips left out, against the
  reference's first step: chip ``k`` reduces only its own rows, and its
  gradient stands for slice ``k`` of each leaf's last axis; the loss is
  chip 0's. Only ``loss_gap`` and ``grad_norm_gap`` are read.

A step that returns its state unchanged reads 1 on ``grad_norm_gap`` and
``change_norm_gap`` by their definition and needs no run. One JSON line
per reading goes to standard output and to ``--out``. The limits in
``bench/limits/<cell>.json`` are set from these readings by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from types import SimpleNamespace

sys.path.append(os.path.dirname(os.path.abspath(__file__)))

import run as R  # noqa: E402


def readings(args, *, root: str = R.ROOT, chips_check=R.require_chips):
    bench = os.path.join(root, "bench")
    r = R.resolve(R.load_json(root, "BENCHMARK.json"), args.workload, bench)
    R.add_paths(root)
    import jax

    chips_check(jax, r.cell["chips"])
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    E = r.entry
    cfg = E.program_config(r.config)

    def ctx_of(seed, tmp):
        return SimpleNamespace(cell=r.cell, config=r.config, mix=r.mix,
                               generator=r.generator, seed=seed, tmp=tmp)

    for seed in args.seeds:
        with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
            ctx = ctx_of(seed, tmp)
            feed = E.make_feed(ctx)
            trainer = E.build(ctx, cfg, feed)
            prog = E.program_readings(ctx, trainer, cfg)
            del trainer
            E.free_device()
            ref = E.reference_readings(ctx, E.reference_steps(ctx, feed.kept))
            yield {"kind": "program", "seed": seed, **E.compare(prog, ref),
                   "losses": prog["losses"], "ref_losses": ref["losses"]}
    for seed in args.control_seeds:
        with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
            ctx = ctx_of(seed, tmp)
            feed = E.make_feed(ctx)
            batches = E.reference_steps(
                ctx, [next(feed) for _ in range(E.CHECK_STEPS)])
            ref = E.reference_readings(ctx, batches)
            fp8 = E.reference_readings(ctx, batches, quant="fp8")
            yield {"kind": "control", "seed": seed, **E.compare(fp8, ref)}
            half = [{k: v[: len(v) // 2] for k, v in b.items()} for b in batches]
            yield {"kind": "half_batch", "seed": seed,
                   **E.compare(E.reference_readings(ctx, half), ref)}
            if r.cell["chips"] > 1:
                fault = no_exchange(E, ctx, batches[0], r.cell["chips"])
                yield {"kind": "no_exchange", "seed": seed,
                       "loss_gap": abs(fault["loss"] - ref["losses"][0])
                       / abs(ref["losses"][0]),
                       "grad_norm_gap": E.norm_gap(
                           fault["grad_norms"], ref["grad_norms"],
                           list(ref["grad_norms"]))}
            E.free_device()


def no_exchange(E, ctx, batch, chips: int) -> dict:
    """The first step's loss and per-leaf gradient norms (after clipping)
    with no exchange between ``chips`` chips (see the module docstring)."""
    import jax
    import jax.numpy as jnp

    esm2, c = E.esm2, ctx.config
    shapes = {k: s for k, (s, _) in esm2.shapes(c).items()}
    shardings = E.reference_shardings(shapes)
    params = jax.jit(lambda k: esm2.init_params(c, k),
                     out_shardings=shardings)(esm2.seed_key(ctx.seed))
    rows = batch["tokens"].shape[0] // chips
    grads = jax.jit(esm2.make_grads(c, row_block=max(rows // 4, 1)))
    sq, loss = {k: 0.0 for k in shapes}, None
    with jax.default_matmul_precision("highest"):
        for k in range(chips):
            part = {n: jnp.asarray(batch[n][k * rows:(k + 1) * rows])
                    for n in ("tokens", "targets", "loss_mask")}
            lk, g = grads(params, part)
            loss = float(lk) if loss is None else loss
            own = jax.jit(lambda g, k=k: {
                n: jnp.sum(jnp.square(x[..., k * (x.shape[-1] // chips):
                                        (k + 1) * (x.shape[-1] // chips)]))
                for n, x in g.items()})(g)
            for n, v in own.items():
                sq[n] += float(v)
            del g
    clip = min(1.0, c["optimizer"]["grad_clip"] / max(sum(sq.values()) ** 0.5, 1e-9))
    return {"loss": loss,
            "grad_norms": {n: clip * v ** 0.5 for n, v in sq.items()}}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--out", default="")
    args = p.parse_args(argv)
    out = open(args.out, "a") if args.out else None
    try:
        for line in readings(args):
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
    finally:
        if out:
            out.close()


if __name__ == "__main__":
    main()
