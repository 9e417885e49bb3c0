"""Serving launcher over the Generation API v2 ``LLM`` facade.

Continuous-batching mode (decoder-only archs) drives the slot engine
through ``serving/api.py::LLM``; ``--cache-layout paged`` serves from the
paged KV cache, ``--prefix-cache`` / ``--prefill-chunk N`` layer
content-addressed prefix sharing and bounded chunked prefill on top, and
``--temperature/--top-k/--top-p/--seed`` set the per-request sampling
params (greedy by default — fused on-device sampling either way):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --continuous --cache-layout paged --page-size 16 --requests 16 \
        --prefix-cache --prefill-chunk 32 --temperature 0.8 --top-k 40

``--mesh DxM`` (e.g. ``--mesh 2x4``) serves tensor-parallel on a
(data, model) device mesh: K/V storage shards over the model axis while
the page allocator stays global, and per-request sampling is
token-reproducible, so the output stream is identical to single-device
(see serving/README.md "Sharded serving").  On CPU, virtual devices come
from ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Telemetry (``repro.obs``, see ``src/repro/obs/README.md``):
``--health-every N`` prints the engine health snapshot every N steps
while serving (default 64 — a wedged engine is visible as the watchdog
climbs, not only at exit); ``--metrics-dir DIR`` refreshes a Prometheus
exposition + JSON snapshot there on the same cadence; ``--trace PATH``
writes the request-lifecycle JSONL at exit; ``--profile DIR`` captures
a ``jax.profiler`` trace of the whole serving run.  The engine's spans
(``engine.prefill``, ``engine.decode``, ``engine.host_sync``) are always
recorded in the step log, and their means are printed at exit.

The static-batch path (``generate``) remains for encoder-decoder /
vision-frontend archs the slot engine does not admit; it is a deprecated
shim for decoder-only callers.
"""
from __future__ import annotations

import argparse
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.config import ParallelConfig, ServeConfig
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import build_model


def generate(
    model, params, batch, *, max_len: int, steps: int, temperature: float = 0.0,
    seed: int = 0, top_k: int = 0, top_p: float = 1.0,
):
    """Static-batch generation loop; returns (tokens (B, steps), toks/s).

    .. deprecated:: Generation API v2
        Decoder-only serving should use ``serving.api.LLM`` (per-request
        ``SamplingParams``, continuous batching, streaming).  This shim
        stays for encoder-decoder / vision-frontend static batches; its
        token selection now runs through the same fused on-device
        sampler as the engine (``ops.sample_tokens``), so greedy output
        is unchanged and sampled output is seed-reproducible.
    """
    warnings.warn(
        "launch.serve.generate is a legacy static-batch path; use "
        "serving.api.LLM for decoder-only serving",
        DeprecationWarning, stacklevel=2,
    )
    from repro.kernels import ops

    B = batch["tokens"].shape[0]
    impl = model.cfg.kernel_impl
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len))

    def step_fn(p, cache, logits, gen_idx):
        tok, _ = ops.sample_tokens(
            logits[:, -1],
            jnp.full((B,), temperature, jnp.float32),
            jnp.full((B,), top_k, jnp.int32),
            jnp.full((B,), top_p, jnp.float32),
            jnp.arange(B, dtype=jnp.uint32) + jnp.uint32(seed),
            jnp.full((B,), gen_idx, jnp.uint32),
            impl=impl,
        )
        logits, cache = model.decode_step(p, cache, tok[:, None])
        return tok, logits, cache

    step = jax.jit(step_fn)
    logits, cache = prefill(params, batch)
    outs = []
    t0 = time.time()
    for i in range(steps):
        tok, logits, cache = step(params, cache, logits, i)
        outs.append(tok[:, None])
    toks = jnp.concatenate(outs, axis=1)
    jax.block_until_ready(toks)
    dt = time.time() - t0
    return toks, (toks.size / dt)


def _health_line(h) -> str:
    return (
        f"steps={h.steps} queue={h.queue_depth} "
        f"active={h.active_slots}/{h.slots} "
        f"free_pages={h.free_pages}/{h.total_pages} "
        f"stalled_steps={h.steps_since_progress} counters={h.counters}"
    )


def serve_continuous(model, params, sc: ServeConfig, *, gen: int,
                     prompt_len: int, requests: int,
                     health_every: int = 0, metrics_dir: str = "",
                     trace_path: str = "") -> None:
    """Drive the continuous-batching engine through the LLM facade.

    Telemetry: ``health_every=N`` prints the health snapshot every N
    engine steps WHILE serving (a stall is visible as the watchdog
    climbs, not just in the exit summary) and, with ``metrics_dir``,
    refreshes the Prometheus exposition + JSON snapshot there on the
    same cadence.  ``trace_path`` writes the lifecycle JSONL at exit.
    The step log's per-span means are printed at exit."""
    from repro.obs import STEP_LOG, MetricsRegistry, TraceRecorder
    from repro.serving.api import LLM
    from repro.serving.sampling import SamplingParams

    reg = MetricsRegistry() if (metrics_dir or health_every) else None
    tracer = TraceRecorder(capacity=16384) if trace_path else None

    def _dump_metrics() -> None:
        if reg is not None and metrics_dir:
            import os

            os.makedirs(metrics_dir, exist_ok=True)
            reg.write_prometheus(os.path.join(metrics_dir, "serve.prom"))
            reg.dump_json(os.path.join(metrics_dir, "serve_metrics.json"))

    def _on_step(eng) -> None:
        # periodic liveness emission: stalls show up while the watchdog
        # climbs, not only in the exit summary
        if health_every and eng.steps % health_every == 0:
            print(f"  [step {eng.steps}] {_health_line(eng.health())}")
            _dump_metrics()

    cfg = model.cfg
    rng = np.random.default_rng(0)
    llm = LLM.from_config(model, params, sc, metrics=reg, trace=tracer,
                          on_step=_on_step if health_every else None)
    # a shared task preamble on half the requests exercises the prefix
    # cache the way protein/chemistry serving does (fixed scaffolds);
    # at least one full page long, else no block can ever hash-hit
    preamble = rng.integers(
        5, cfg.vocab_size, size=max(sc.page_size, prompt_len // 2)
    ).astype(np.int32)
    prompts, plist = [], []
    for i in range(requests):
        L = int(rng.integers(max(1, prompt_len // 2), prompt_len + 1))
        prompt = rng.integers(5, cfg.vocab_size, size=L).astype(np.int32)
        if sc.prefix_cache and i % 2 == 0:
            prompt = np.concatenate([preamble, prompt])[: sc.max_seq_len - gen - 1]
        prompts.append(prompt)
        plist.append(SamplingParams(
            temperature=sc.temperature, top_k=sc.top_k, top_p=sc.top_p,
            seed=sc.seed + i, max_new=gen, deadline_ms=sc.deadline_ms,
        ))
    t0 = time.time()
    outs = llm.generate(prompts, plist)
    wall = time.time() - t0
    eng = llm.engine
    served = [c for c in outs if c.finish_reason in ("stop", "length")]
    degraded = [c for c in outs if c.finish_reason not in ("stop", "length")]
    toks = sum(len(c.tokens) for c in outs)
    ttft = float(np.mean([c.ttft_s for c in served])) * 1e3 if served else 0.0
    itl = float(np.mean([
        (c.latency_s - c.ttft_s) / max(len(c.tokens) - 1, 1) for c in served
    ])) * 1e3 if served else 0.0
    extra = ""
    if eng.alloc is not None and sc.prefix_cache:
        st = eng.alloc.stats
        extra = (
            f", prefix-cache: {st['hit_tokens']} tokens reused, "
            f"{st['evictions']} evictions, {st['cow_copies']} COW copies"
        )
    print(
        f"[{sc.cache_layout}] served {len(served)}/{len(outs)} requests / "
        f"{toks} tokens on {eng.B} slots: {toks / wall:.1f} tok/s, "
        f"ttft {ttft:.1f}ms, itl {itl:.2f}ms{extra}"
    )
    if degraded:
        by_reason: dict = {}
        for c in degraded:
            by_reason[c.finish_reason] = by_reason.get(c.finish_reason, 0) + 1
        print("  degraded outcomes: "
              + ", ".join(f"{k}={v}" for k, v in sorted(by_reason.items())))
    print(f"  health: {_health_line(eng.health())}")
    _dump_metrics()
    if tracer is not None:
        tracer.write(trace_path)
        print(f"  trace: {len(tracer)} lifecycle events -> {trace_path}"
              + (f" ({tracer.dropped} older events dropped)"
                 if tracer.dropped else ""))
    print("  step log:")
    for line in STEP_LOG.report("engine").splitlines():
        print(f"    {line}")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="molmim-65m")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0,
                   help="per-request top-k filter (0 = disabled)")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="per-request nucleus filter (1.0 = disabled)")
    p.add_argument("--seed", type=int, default=0,
                   help="sampling PRNG seed (request i uses seed+i)")
    p.add_argument("--continuous", action="store_true",
                   help="continuous-batching engine instead of a static batch")
    p.add_argument("--mesh", default="",
                   help="serve tensor-parallel on a DATAxMODEL device mesh, "
                        "e.g. --mesh 2x4 (K/V storage shards over the model "
                        "axis; sampling stays token-reproducible, so output "
                        "is identical to single-device).  Requires "
                        "data*model visible jax devices — on CPU set "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    p.add_argument("--cache-layout", choices=("dense", "paged"),
                   default="dense")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--prefix-cache", action="store_true",
                   help="content-addressed prefix sharing (paged layout)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="bound prefill to N-token chunks interleaved with "
                        "decode steps (paged layout; 0 = one chunk)")
    p.add_argument("--max-queue", type=int, default=0,
                   help="bounded admission queue; overflow submits are "
                        "rejected with a typed retriable error (0 = unbounded)")
    p.add_argument("--preempt", action="store_true",
                   help="under page pressure, preempt-and-requeue the newest "
                        "in-flight decode instead of head-of-line blocking "
                        "(paged layout; resumed output is token-identical)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="default per-request deadline from submit; expired "
                        "requests finish with finish_reason='timeout'")
    p.add_argument("--health-every", type=int, default=64,
                   help="print Engine.health() (and refresh --metrics-dir) "
                        "every N engine steps while serving (0 = exit-only)")
    p.add_argument("--metrics-dir", default="",
                   help="write Prometheus exposition + JSON metric snapshots "
                        "here (refreshed on the --health-every cadence)")
    p.add_argument("--trace", default="", dest="trace_path",
                   help="write the request-lifecycle JSONL trace to this "
                        "path at exit")
    p.add_argument("--profile", default="",
                   help="capture a jax.profiler trace of the serving run "
                        "into this directory")
    a = p.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(a.arch) if a.smoke else get_config(a.arch)
    mesh = None
    if a.mesh:
        try:
            d, m = (int(x) for x in a.mesh.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--mesh wants DATAxMODEL (e.g. 2x4), got {a.mesh!r}")
        if d * m > len(jax.devices()):
            raise SystemExit(
                f"--mesh {a.mesh} needs {d * m} devices, "
                f"{len(jax.devices())} visible (on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={d * m})"
            )
        mesh = make_mesh((d, m), ("data", "model"))
    model = build_model(cfg, ParallelConfig(), mesh)
    params = model.init(jax.random.PRNGKey(0))
    if a.continuous:
        max_prompt = a.prompt_len * (2 if a.prefix_cache else 1)
        sc = ServeConfig(
            max_seq_len=max_prompt + a.gen + cfg.num_frontend_tokens + 1,
            batch_size=a.batch, temperature=a.temperature,
            top_k=a.top_k, top_p=a.top_p, seed=a.seed,
            cache_layout=a.cache_layout, page_size=a.page_size,
            prefix_cache=a.prefix_cache, prefill_chunk=a.prefill_chunk,
            max_queue=a.max_queue, preempt=a.preempt,
            deadline_ms=a.deadline_ms,
        )
        from repro.obs.profile import trace_ctx

        with trace_ctx(a.profile):
            serve_continuous(model, params, sc, gen=a.gen,
                             prompt_len=a.prompt_len, requests=a.requests,
                             health_every=a.health_every,
                             metrics_dir=a.metrics_dir,
                             trace_path=a.trace_path)
        return
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(5, cfg.vocab_size, size=(a.batch, a.prompt_len)), jnp.int32
        )
    }
    if cfg.is_encoder_decoder:
        if cfg.frontend == "audio_stub":
            batch["enc_embeds"] = jnp.asarray(
                rng.normal(size=(a.batch, cfg.num_frontend_tokens, cfg.d_model)),
                jnp.float32,
            )
        else:
            batch["src_tokens"] = batch["tokens"]
    if cfg.frontend == "vision_stub":
        batch["img_embeds"] = jnp.asarray(
            rng.normal(size=(a.batch, cfg.num_frontend_tokens, cfg.d_model)),
            jnp.float32,
        )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)  # shim, by design
        toks, tps = generate(
            model, params, batch,
            max_len=a.prompt_len + a.gen + cfg.num_frontend_tokens + 1,
            steps=a.gen, temperature=a.temperature, seed=a.seed,
            top_k=a.top_k, top_p=a.top_p,
        )
    print(f"generated {toks.shape} tokens at {tps:.1f} tok/s")
    print(toks[:, :12])


if __name__ == "__main__":
    main()
