#!/usr/bin/env python3
"""Run the repo's two main paths once on a TPU, at published widths.

    python3 chip_smoke.py            # one chip: phase `train`, then `serve`
    python3 chip_smoke.py --chips 4  # four chips: sharded training only

Phase ``train``: ESM-2 650M MLM (33 layers x 1280, 20 heads) takes
``TRAIN_STEPS`` optimizer steps at 8 x 1024 through ``build_model`` ->
``Trainer`` with ``kernel_impl="auto"`` (the Pallas kernels), on batches
from ``launch.train.make_batches`` over the synthetic protein memmap.
Its step-0 loss is checked against the same params and batch run through
the XLA implementations at ``highest`` matmul precision, its loss
trajectory against the same steps on the XLA path (``train_vs_xla``),
and the compiled step must contain a Pallas kernel (``tpu_custom_call``).

Phase ``serve``: qwen2-7b at published widths with depth cut to
``SERVE_LAYERS`` layers serves 8 seeded requests (64-512 prompt tokens,
half of them sharing a 256-token prefix, 32 new tokens, greedy and
seeded sampling mixed) through ``serving.api.LLM`` on the paged cache
with prefix caching and chunked prefill.  The reference is the same
engine on the XLA implementations (``kernel_impl`` reaches attention,
cross-entropy, the paged kernels and the sampler).  Both engines are fed
the reference's token streams, and at every position their logit rows,
logprobs and picked tokens must agree; a different pick is allowed only
where bf16 rounding can flip it (``check_parity``).

``--chips 4`` trains ESM-2 650M for ``SHARDED_STEPS`` steps on the
launcher's ``--mesh auto`` shape (4, 1), then on (2, 2) (FSDP over
``data``, heads over ``model``), and compares both loss trajectories with
the same steps on one chip, all in this one process.

Every number printed is one run on one machine, not a benchmark.  The
last line of standard output is the JSON contract line
``{"ok": true, "device": {...}}``; it is printed only after every phase
passed.  With no TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import itertools
import json
import math
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.config import ParallelConfig, TrainConfig  # noqa: E402
from repro.core.precision import compute_view  # noqa: E402
from repro.kernels.ops import sample_tokens  # noqa: E402
from repro.kernels.sampling import gumbel_noise  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.train import build_mesh, make_batches  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serving.api import LLM  # noqa: E402
from repro.serving.sampling import SamplingParams  # noqa: E402
from repro.training.loop import Trainer  # noqa: E402

SEED = 0
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, SHARDED_STEPS = 8, 1024, 5, 3
SERVE_LAYERS = 4
# relative tolerance on a loss, Pallas path vs XLA path: on a v5e they
# agreed to 1.0e-4 at step 0 and 9.1e-4 over 5 steps (one run)
LOSS_RTOL = 3e-3
# gradients, Pallas path vs XLA path at `highest`, on the first GRAD_ROWS
# sequences of batch 0: largest relative L2 difference over the
# parameter leaves (0.014 on a v5e, one run)
GRAD_ROWS, GRAD_RTOL = 2, 4e-2
# serving parity (check_parity), in bf16 ulps of the reference logit
# row's largest entry: the largest row difference allowed (2.09 read on
# a v5e), and the largest move of a row that may flip a pick (1 read)
ROW_ULPS = 3
FLIP_ULPS = 2
# logprob of a token both engines picked (0.039 apart on a v5e)
LOGP_ATOL = 0.1
DATA_DIR = os.path.join(REPO, ".chip_smoke")


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu(count: int) -> None:
    """Exit non-zero, before any work, unless ``count`` TPU chips are here."""
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < count:
        sys.exit(f"chip_smoke: needs {count} TPU chip(s); JAX found "
                 f"{info['count']} {info['platform']} device(s) "
                 f"({info['kind']})")


def run_phases(phases) -> None:
    """Run each phase in order; the contract line is printed only after
    every phase returned.  A phase that fails raises, so the process exits
    non-zero with no contract line."""
    for phase in phases:
        phase()
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)


def _close(a: float, b: float) -> bool:
    return (math.isfinite(a) and math.isfinite(b)
            and abs(a - b) <= LOSS_RTOL * abs(b))


def _free() -> None:
    """Drop every dead buffer and compiled program before the next phase."""
    gc.collect()
    jax.clear_caches()


# --------------------------------------------------------------------- #
# phase: train
# --------------------------------------------------------------------- #
def train(cfg, tc: TrainConfig, data_dir: str, *, mesh=None,
          ref_impl: str = "") -> dict:
    """``tc.total_steps`` Trainer steps of ``cfg`` on ``make_batches``.

    With ``ref_impl`` the step-0 loss is also computed from the same
    params and batch with ``kernel_impl=ref_impl`` at ``highest`` matmul
    precision, and so are the gradients (``grad_gap``), before step 0
    consumes (donates) the state.  Raises on a non-finite loss or a
    reference disagreement beyond ``LOSS_RTOL`` or ``GRAD_RTOL``."""
    model = build_model(cfg, ParallelConfig(), mesh)
    batches = iter(make_batches(cfg, tc, data_dir, seed=SEED))
    first = next(batches)
    trainer = Trainer(model, tc, verbose=False)
    trainer.prepare(itertools.chain([first], batches))
    out: dict = {}
    if ref_impl:
        ref = build_model(dataclasses.replace(cfg, kernel_impl=ref_impl))
        loss_fn = jax.jit(
            lambda p, b: ref.loss_fn(compute_view(ref.policy, p), b)[0]
        )
        with jax.default_matmul_precision("highest"):
            out["ref_loss0"] = float(loss_fn(trainer.state.params, first))
        out["grad_gap"] = grad_gap(
            model, ref, trainer.state.params,
            jax.tree.map(lambda x: x[:GRAD_ROWS], first),
        )
        del loss_fn, ref
    while trainer.step_idx < tc.total_steps:
        trainer.step()
    entry = next(iter(trainer._compiled.values()))
    hist = trainer.history
    stats = jax.devices()[0].memory_stats() or {}
    out.update(
        losses=[m["loss"] for m in hist],
        step_s=[m["step_time"] for m in hist],
        compile_s=entry["compile_s"],
        hlo=entry["fn"].as_text(),
        peak=stats.get("peak_bytes_in_use"),
        limit=stats.get("bytes_limit"),
    )
    if not all(math.isfinite(x) for x in out["losses"]):
        raise AssertionError(f"non-finite training loss: {out['losses']}")
    if ref_impl and not _close(out["losses"][0], out["ref_loss0"]):
        raise AssertionError(
            f"step-0 loss {out['losses'][0]!r} vs {ref_impl} reference "
            f"{out['ref_loss0']!r}: beyond rtol {LOSS_RTOL}"
        )
    if ref_impl and not out["grad_gap"][0] <= GRAD_RTOL:
        raise AssertionError(
            f"gradient of {out['grad_gap'][1]} vs {ref_impl} reference: "
            f"{out['grad_gap'][0]!r} relative, beyond {GRAD_RTOL}"
        )
    return out


def grad_gap(model, ref, params, batch) -> tuple:
    """Largest relative L2 difference, over the parameter leaves, between
    the gradients of ``model``'s loss and ``ref``'s (at ``highest``
    precision) at ``params`` on ``batch``, and the leaf it is at.  Taken
    with respect to the compute view, one tree at a time."""
    def grads(m):
        return jax.jit(jax.grad(lambda v, b: m.loss_fn(v, b)[0]))

    view = compute_view(model.policy, params)
    got = grads(model)(view, batch)
    with jax.default_matmul_precision("highest"):
        want = grads(ref)(view, batch)
    rel = jax.device_get(jax.jit(lambda g, r: jax.tree.map(
        lambda x, y: jnp.linalg.norm((x - y).astype(jnp.float32))
        / jnp.maximum(jnp.linalg.norm(y.astype(jnp.float32)), 1e-30), g, r,
    ))(got, want))
    path, worst = max(jax.tree_util.tree_flatten_with_path(rel)[0],
                      key=lambda kv: float(kv[1]))
    return float(worst), jax.tree_util.keystr(path)


def train_vs_xla(cfg, tc: TrainConfig, data_dir: str) -> dict:
    """``train`` on ``cfg`` (its step 0 against the XLA implementations),
    then the same steps with ``kernel_impl="xla"``; raises unless the two
    loss trajectories agree within ``LOSS_RTOL`` at every step, which the
    backward kernels decide from step 1 on."""
    r = train(cfg, tc, data_dir, ref_impl="xla")
    _free()
    ref = train(dataclasses.replace(cfg, kernel_impl="xla"), tc, data_dir)
    _free()
    for step, (a, b) in enumerate(zip(r["losses"], ref["losses"])):
        if not _close(a, b):
            raise AssertionError(f"step {step}: loss {a!r} vs xla path {b!r}")
    r["xla"] = ref
    return r


def train_phase() -> None:
    cfg = get_config("esm2-650m")
    log(f"[train] {cfg.name}: {cfg.num_layers} layers x {cfg.d_model}, "
        f"{cfg.num_heads} heads, kernel_impl={cfg.kernel_impl}, "
        f"batch {TRAIN_BATCH} x {TRAIN_SEQ}, {TRAIN_STEPS} steps")
    r = train_vs_xla(cfg, _train_config(TRAIN_STEPS), DATA_DIR)
    if "tpu_custom_call" not in r["hlo"]:
        raise AssertionError("compiled train step holds no Pallas kernel")
    for name, x in (("pallas", r), ("xla", r["xla"])):
        step = statistics.median(x["step_s"][1:])
        log(f"[train] {name} path: compile {x['compile_s']:.1f} s; median "
            f"step {step:.4f} s over steps 1-{TRAIN_STEPS - 1}: "
            f"{TRAIN_BATCH * TRAIN_SEQ / step:.0f} tokens/s")
    log(f"[train] peak_bytes_in_use after the pallas run {r['peak']} of "
        f"bytes_limit {r['limit']}")
    log(f"[train] loss first {r['losses'][0]:.6f} last {r['losses'][-1]:.6f}; "
        f"step-0 vs xla reference {r['ref_loss0']:.6f} (rtol {LOSS_RTOL})")
    log(f"[train] step-0 gradients vs xla reference on {GRAD_ROWS} "
        f"sequences: largest leaf difference {r['grad_gap'][0]:.6f} relative "
        f"at {r['grad_gap'][1]} (limit {GRAD_RTOL})")
    log(f"[train] losses {r['losses']} vs xla path {r['xla']['losses']} "
        f"(rtol {LOSS_RTOL})")


def _train_config(steps: int) -> TrainConfig:
    return TrainConfig(
        global_batch=TRAIN_BATCH, seq_len=TRAIN_SEQ, total_steps=steps,
        log_every=1, learning_rate=1e-4, warmup_steps=1, decay_steps=1,
        seed=SEED,
    )


# --------------------------------------------------------------------- #
# phase: serve
# --------------------------------------------------------------------- #
def make_requests(cfg, *, n: int, lengths, prefix_len: int, max_new: int):
    """``n`` seeded prompts with lengths in ``lengths``; the first half
    share a ``prefix_len``-token prefix.  Odd requests sample (seeded),
    even ones are greedy."""
    rng = np.random.default_rng(SEED)
    prefix = rng.integers(5, cfg.vocab_size, prefix_len).astype(np.int32)
    prompts, params = [], []
    for i in range(n):
        shared = i < n // 2
        lo = max(lengths[0], prefix_len + 1) if shared else lengths[0]
        L = int(rng.integers(lo, lengths[1] + 1))
        p = rng.integers(5, cfg.vocab_size, L).astype(np.int32)
        if shared:
            p[:prefix_len] = prefix
        prompts.append(p)
        params.append(
            SamplingParams(temperature=0.8, top_k=50, top_p=0.9,
                           seed=1000 + i, max_new=max_new, logprobs=True)
            if i % 2 else SamplingParams(max_new=max_new, logprobs=True)
        )
    return prompts, params


def _bf16_ulp(x: float) -> float:
    """Spacing of bf16 numbers at magnitude ``x`` (8 significant bits)."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -126))) - 7)


def _flip_ulps(row, tok: int, sp: SamplingParams, step: int, ulp: float,
               impl: str = "xla"):
    """Fewest bf16 ulps m (0 to ``FLIP_ULPS``) such that moving every
    logit of ``row`` by at most m ulps makes the sampler (``impl``) pick
    ``tok``; ``None`` past that.  Two moves witness it: ``tok`` up and
    all else down (an argmax tie), and, for a top-k filter, the k-1
    highest tokens that ``tok`` outscores (gumbel noise included) up with
    ``tok`` and all else down — the filter boundary moves past the tokens
    that beat ``tok``."""
    score = row / (sp.temperature if not sp.greedy else 1.0)
    if not sp.greedy:
        vocab = jnp.arange(row.shape[0], dtype=jnp.uint32)[None]
        score = score + np.asarray(gumbel_noise(
            jnp.full((1, 1), sp.seed, jnp.uint32),
            jnp.full((1, 1), step, jnp.uint32), vocab,
        ))[0]
    beaten = np.flatnonzero(score <= score[tok])
    beaten = beaten[beaten != tok]
    k = sp.top_k if sp.top_k > 0 else row.shape[0]
    kept = np.append(beaten[np.argsort(-row[beaten])][:k - 1], tok)
    witnesses = [np.full(row.shape, -1.0, np.float32) for _ in range(2)]
    witnesses[0][tok] = 1.0
    witnesses[1][kept] = 1.0
    for m in range(FLIP_ULPS + 1):
        for w in witnesses:
            pick, _ = sample_tokens(
                jnp.asarray(row + m * ulp * w)[None],
                jnp.asarray([sp.temperature], jnp.float32),
                jnp.asarray([sp.top_k], jnp.int32),
                jnp.asarray([sp.top_p], jnp.float32),
                jnp.asarray([sp.seed], jnp.uint32),
                jnp.asarray([step], jnp.uint32), impl=impl,
            )
            if int(pick[0]) == tok:
                return m
    return None


def forced_run(cfg, params, engine_kw: dict, prompts, plist, streams):
    """Serve ``prompts`` on a fresh engine of ``cfg`` with ``streams[i]``
    fed back as request ``i``'s generated tokens (teacher forcing), and
    return, for every request ``i`` and generated position ``g``,
    ``(row, pick, logp)``: the logit row the engine computed for that
    position, the token its own sampler picked from it, and that token's
    logprob.

    The rows are the engine's own: the first from its prefill or last
    prefill chunk, the rest from its fused decode step (the paged
    kernels, the sampler), jitted here with the step's logits as one
    more output.  Chunking, prefix caching and batching run as
    ``engine_kw`` configures them; only the token fed back is replaced."""
    model = build_model(cfg)
    decode_step, traced = model.decode_step, []

    def spy(*args):
        out = decode_step(*args)
        traced.append(out[0][:, -1].astype(jnp.float32))
        return out

    model.decode_step = spy
    llm = LLM(model, params, **engine_kw)
    eng = llm.engine
    fused, admit = eng._decode.__wrapped__, eng._admit_slot

    def step_rows(*args):
        traced.clear()
        return fused(*args) + (traced[-1],)

    step_rows = jax.jit(step_rows, donate_argnums=(1, 3))
    rec = [{} for _ in prompts]   # LLM numbers its requests 0.. (uid)

    def admit_slot(samp, last_tok, logits, slot, temp, k, p, seed, gen0,
                   inject):
        tok, logp, bad, samp, last_tok = admit(
            samp, last_tok, logits, slot, temp, k, p, seed, gen0, inject
        )
        i, g, s = eng.slot_req[int(slot)].uid, int(gen0), int(slot)
        rec[i][g] = (np.asarray(logits[0, -1], np.float32), int(tok[0]),
                     float(logp[0]))
        f = np.int32(streams[i][g])
        return (jnp.asarray([f]), logp, bad, samp,
                last_tok.at[s].set(f))

    def decode(params, cache, tok, samp, inject):
        nxt, logp, bad, cache, samp, rows = step_rows(
            params, cache, tok, samp, inject
        )
        nxt_h, logp_h, rows_h = jax.device_get((nxt, logp, rows))
        forced = np.zeros_like(nxt_h)
        for s, req in enumerate(eng.slot_req):
            if req is None or s in eng._prefill_state:
                continue
            g = len(req.output)
            rec[req.uid][g] = (rows_h[s], int(nxt_h[s]), float(logp_h[s]))
            forced[s] = streams[req.uid][g]
        return jnp.asarray(forced), logp, bad, cache, samp

    eng._decode, eng._admit_slot = decode, admit_slot
    for i, c in enumerate(llm.generate(prompts, plist)):
        if c.tokens != list(streams[i]):
            raise AssertionError(f"request {i}: teacher forcing fed "
                                 f"{c.tokens}, not {list(streams[i])}")
    return [[r[g] for g in range(len(r))] for r in rec]


def check_parity(got, ref, streams, free, plist, impl: str,
                 ref_impl: str) -> dict:
    """Token parity of the measured engine (sampler ``impl``) with the
    reference engine (``ref_impl``), fed the same token streams
    (``forced_run``: ``got`` measured, ``ref`` reference, both fed the
    reference's free-running ``streams``).

    At every generated position of every request: the two logit rows
    agree within ``ROW_ULPS`` bf16 ulps of the reference row's largest
    logit; where both samplers pick the same token, its logprobs agree
    within ``LOGP_ATOL``; where they differ, the pick is a tie: moving
    the measured row by 1 to ``FLIP_ULPS`` ulps makes the measured
    sampler pick the reference's token, a choice bf16 rounding can flip.

    The forced runs must also account for the free runs.  Wherever a
    free run's prefix is the stream's (the measured one's ``free`` up to
    its first divergence, the reference's throughout), the free run's
    token is the forced pick or a tie on the forced row.  The forced
    decode program carries the logits as one more output, so it is not
    the engine's program byte for byte and may round a row differently.
    Returns the readings; raises listing every violation."""
    fails, ties, reruns = [], [], []
    worst_row = worst_logp = 0.0
    steps = 0

    def tie(row, pick, want, i, g, ulp, sampler, what):
        m = _flip_ulps(row, want, plist[i], g, ulp, sampler)
        if not m:      # None: no move up to FLIP_ULPS; 0: row gives want
            fails.append(f"request {i} token {g}: {what} {pick} vs {want} "
                         f"is not a tie (ulps to flip: {m})")
        return m

    for i, (a, b) in enumerate(zip(got, ref)):
        n = next((g for g, (x, y) in enumerate(zip(free[i], streams[i]))
                  if x != y), min(len(free[i]), len(streams[i])))
        for g, ((row, pick, lp), (xrow, xpick, xlp)) in enumerate(zip(a, b)):
            steps += 1
            ulp = _bf16_ulp(float(np.abs(xrow).max()))
            if xpick != streams[i][g]:
                m = tie(xrow, xpick, streams[i][g], i, g, ulp, ref_impl,
                        "reference forced vs free run:")
                reruns.append((i, g, ref_impl, m))
            if g <= n and g < len(free[i]) and pick != free[i][g]:
                m = tie(row, pick, free[i][g], i, g, ulp, impl,
                        "measured forced vs free run:")
                reruns.append((i, g, impl, m))
            d = float(np.abs(row - xrow).max()) / ulp
            worst_row = max(worst_row, d)
            if d > ROW_ULPS:
                fails.append(f"request {i} token {g}: logit rows differ by "
                             f"{d:.2f} bf16 ulps (> {ROW_ULPS})")
            if pick != xpick:
                m = tie(row, pick, xpick, i, g, ulp, impl,
                        "measured vs reference:")
                ties.append((i, g, round(d, 2), m))
                continue
            worst_logp = max(worst_logp, abs(lp - xlp))
            if abs(lp - xlp) > LOGP_ATOL:
                fails.append(f"request {i} token {g}: logprobs {lp:.4f} vs "
                             f"{xlp:.4f} (> {LOGP_ATOL} apart)")
    out = dict(steps=steps, row_ulps=worst_row, logp=worst_logp, ties=ties,
               reruns=reruns)
    if fails:
        raise AssertionError(
            f"{len(fails)} parity failures {out}:\n" + "\n".join(fails[:20])
        )
    return out


def serve(cfg, *, n: int = 8, lengths=(64, 512), prefix_len: int = 256,
          max_new: int = 32, chunk: int = 128, page: int = 16,
          ref_impl: str = "xla") -> dict:
    """Serve ``n`` requests with ``cfg`` (its ``kernel_impl``), then with
    ``kernel_impl=ref_impl``; raises unless every request finished and
    the engines agree at every position of the reference's streams
    (``forced_run``, ``check_parity``)."""
    prompts, plist = make_requests(
        cfg, n=n, lengths=lengths, prefix_len=prefix_len, max_new=max_new
    )
    max_len = lengths[1] + max_new
    max_len += -max_len % page
    params = build_model(cfg).init(jax.random.PRNGKey(SEED))
    kw = dict(slots=n, max_len=max_len, cache_layout="paged",
              page_size=page, prefix_cache=True, prefill_chunk=chunk)
    ref_cfg = dataclasses.replace(cfg, kernel_impl=ref_impl)

    def finished(outs):
        bad = [(c.index, c.finish_reason) for c in outs
               if c.finish_reason not in ("length", "stop")]
        if bad:
            raise AssertionError(f"requests ended abnormally: {bad}")
        return outs

    out: dict = {}
    llm = LLM(build_model(cfg), params, **kw)
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        outs = finished(llm.generate(prompts, plist))
        wall = time.perf_counter() - t0
        out[run] = {
            "completions": outs,
            "ttft_s": statistics.median(c.ttft_s for c in outs),
            "tokens_per_s": sum(len(c.tokens) for c in outs) / wall,
        }
    out["prefix_hit_tokens"] = llm.engine.alloc.stats["hit_tokens"]
    del llm
    want = finished(LLM(build_model(ref_cfg), params, **kw)
                    .generate(prompts, plist))
    streams = [c.tokens for c in want]
    free = [c.tokens for c in out["cold"]["completions"]]
    out["exact"] = sum(a == b for a, b in zip(free, streams))
    ref = forced_run(ref_cfg, params, kw, prompts, plist, streams)
    got = forced_run(cfg, params, kw, prompts, plist, streams)
    out["parity"] = check_parity(got, ref, streams, free, plist,
                                 cfg.kernel_impl, ref_impl)
    return out


def serve_phase() -> None:
    full = get_config("qwen2-7b")
    cfg = dataclasses.replace(full, num_layers=SERVE_LAYERS,
                              param_dtype="bfloat16")
    log(f"[serve] {cfg.name}: d_model {cfg.d_model}, {cfg.num_heads}/"
        f"{cfg.num_kv_heads} heads of {cfg.resolved_head_dim}, vocab "
        f"{cfg.vocab_size}; depth cut {full.num_layers} -> {cfg.num_layers} "
        f"layers; bf16 weights; kernel_impl={cfg.kernel_impl}")
    r = serve(cfg)
    for run in ("cold", "warm"):
        log(f"[serve] {run} pass: median TTFT {r[run]['ttft_s']:.4f} s, "
            f"{r[run]['tokens_per_s']:.1f} tokens/s"
            + (" (includes compile)" if run == "cold" else
               f" ({r['prefix_hit_tokens']} prefix-cache hit tokens so far)"))
    p = r["parity"]
    log(f"[serve] parity with the xla engine, both fed its token streams: "
        f"{p['steps']} positions; logit rows within {p['row_ulps']:.2f} bf16 "
        f"ulps (limit {ROW_ULPS}); logprobs of shared picks within "
        f"{p['logp']:.4f} (limit {LOGP_ATOL}); {len(p['ties'])} picks "
        f"differ, each a tie (request, token, row ulps apart, ulps to "
        f"flip, limit {FLIP_ULPS}): {p['ties']}")
    log(f"[serve] free-running streams identical: {r['exact']}/"
        f"{len(r['cold']['completions'])}; free-run tokens that the forced "
        f"run picked differently, each a tie on the forced row (request, "
        f"token, engine, ulps to flip): {p['reruns']}")
    _free()


# --------------------------------------------------------------------- #
# --chips 4: sharded training
# --------------------------------------------------------------------- #
def sharded_phase() -> None:
    cfg = get_config("esm2-650m")
    tc = _train_config(SHARDED_STEPS)
    ref = train(cfg, tc, DATA_DIR)["losses"]
    _free()
    log(f"[sharded] one chip: losses {ref}")
    for spec in ("auto", "2x2"):
        mesh = build_mesh(spec)
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        r = train(cfg, tc, DATA_DIR, mesh=mesh)
        _free()
        log(f"[sharded] mesh {spec} {shape}: compile {r['compile_s']:.1f} s, "
            f"median step {statistics.median(r['step_s'][1:]):.4f} s, "
            f"losses {r['losses']}")
        for step, (a, b) in enumerate(zip(r["losses"], ref)):
            if not _close(a, b):
                raise AssertionError(
                    f"mesh {spec} step {step}: loss {a!r} vs one chip {b!r}"
                )
    stats = [d.memory_stats() or {} for d in jax.devices()]
    log(f"[sharded] peak_bytes_in_use per chip "
        f"{[s.get('peak_bytes_in_use') for s in stats]}")


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: train + serve on one chip; 4: sharded training")
    a = p.parse_args(argv)
    require_tpu(a.chips)
    cache = use_compile_cache()
    info = device_info()
    log(f"device: platform={info['platform']} device_kind={info['kind']} "
        f"count={info['count']}; jax {jax.__version__}; compile cache {cache}")
    if a.chips == 4:
        run_phases([sharded_phase])
    else:
        run_phases([train_phase, serve_phase])


if __name__ == "__main__":
    main()
