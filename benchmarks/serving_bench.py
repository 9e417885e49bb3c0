"""Serving-path benchmark: dense-slot vs paged KV-cache engine, prefix
caching + chunked prefill vs the cold paged baseline, and sampled decode
(Generation API v2 fused on-device sampler) vs greedy.

Four measurements:

  * engine comparison — the continuous-batching engine end-to-end on a
    smoke model under both cache layouts, reporting tokens/s,
    time-to-first-token and inter-token latency.  Token-for-token output
    parity between the layouts is ASSERTED (the subsystem's acceptance
    criterion), not just reported.  Every engine runs the workload once
    as a WARMUP before the measured pass, so TTFT no longer includes the
    first-call jit compile; compile time is reported separately
    (``*_compile`` rows = first pass minus steady-state wall).
  * shared-prefix workload — requests carrying a long common task
    preamble (the protein/chemistry serving pattern), served by the
    paged baseline vs the prefix-cached + chunked-prefill engine.
    Token parity is asserted, and the prefix-cached TTFT must be at
    least 2x better: hash-hit blocks skip prefill entirely, so only the
    unique tail is computed.
  * sampled-decode workload — the same engine/prompts with per-request
    SamplingParams (temperature/top-k/top-p, fixed seeds).  Token
    selection runs fused inside the jitted decode step, so sampled
    throughput is ASSERTED within 10% of greedy; the identical-pass
    output check doubles as a sampled-determinism assertion.
  * decode cache-write microbenchmark at a long-cache config — the dense
    layout's O(B·T) one-hot masked select vs the paged O(B·page)
    scatter (``ops.paged_kv_update``).  The paged write must win; this
    asserts the per-token write really is page-local, independent of the
    cache length.
  * degraded-mode workload — a 3x-oversubscribed arrival pattern served
    by an UNBOUNDED queue vs a bounded one (``max_queue``): the bounded
    engine must reject some arrivals AND cut the p99 TTFT of the
    accepted ones (rejections instead of unbounded queueing — the
    fault-tolerance contract), with token parity on every accepted
    request asserted against the unbounded run.  A seeded ``FaultPlan``
    chaos pass (NaN injection + allocator outage) then must drain with
    survivors token-identical to the fault-free engine.

  * sharded-serving scaling workload — the SAME paged workload served
    tensor-parallel on (1, N) meshes for N in 1/4/8 virtual CPU devices
    (``xla_force_host_platform_device_count``, one subprocess per N —
    the device-count flag must be set before jax initializes, mirroring
    the PR 5 ``train-distributed`` harness).  Per-token output parity of
    every mesh run against the single-device run is ASSERTED — the
    tentpole guarantee that sharding the K/V storage changes where bytes
    live, never what tokens come out.  tok/s per mesh size is reported;
    on virtual devices all shards share the same cores, so the numbers
    prove the mechanism (the sharded engine pays no per-step reshard or
    extra host sync), not a speedup — on real accelerators the model
    axis is what fits 35B+ configs at all.

CPU numbers prove the mechanism (data volume per token write, prompt
rows not recomputed); on TPU the same ratios show up as HBM traffic per
decode step and MXU time per admitted prompt.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

_SCALING_CODE = textwrap.dedent("""
    import json, time
    import jax, numpy as np
    from repro.configs import get_smoke_config
    from repro.core.config import ParallelConfig
    from repro.models.model import build_model
    from repro.serving.engine import Engine, Request
    from repro.launch.mesh import make_mesh

    mesh_shape = __MESH_SHAPE__
    mesh = (make_mesh(mesh_shape, ("data", "model"))
            if mesh_shape is not None else None)
    cfg = get_smoke_config("qwen2-7b")
    model = build_model(cfg, ParallelConfig(), mesh)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(5, cfg.vocab_size, size=int(rng.integers(4, 32)))
        .astype(np.int32)
        for _ in range(8)
    ]

    def serve_pass():
        eng = Engine(model, params, slots=4, max_len=64,
                     cache_layout="paged", page_size=16)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new=16))
        t0 = time.time()
        eng.run()
        wall = time.time() - t0
        outs = {r.uid: list(r.output) for r in eng.done}
        return outs, sum(len(o) for o in outs.values()) / wall

    serve_pass()                      # warm the jit caches
    best = 0.0
    for _ in range(3):
        outs, tps = serve_pass()
        best = max(best, tps)
    print("RESULT " + json.dumps({"outs": outs, "tok_s": best}))
""")


def _scaling_run(n_dev: int, mesh_shape=None):
    """Serve the scaling workload on `n_dev` virtual devices (subprocess:
    the XLA device-count flag must be set before jax initializes).

    ``mesh_shape`` is the (data, model) mesh; the model axis must divide
    the smoke config's 4 attention heads, so 8 devices run as (2, 4).

    Refuses to run unless this process is on the CPU backend: the
    children run on virtual CPU devices, and on an accelerator host their
    numbers would be CPU numbers reported as serving scaling — while this
    parent holds the chip, which a child could not use anyway."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "serving scaling workload: virtual-CPU-device children only; "
            f"this process runs on {jax.default_backend()!r}, so their "
            "timings would not describe this device (and this process "
            "holds it)"
        )
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         _SCALING_CODE.replace("__MESH_SHAPE__", repr(mesh_shape))],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, (
        f"scaling run on {n_dev} devices failed:\n{out.stderr[-4000:]}"
    )
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def _run_pass(eng, prompts, max_new, make_params=None):
    """Submit `prompts` to `eng` and run this batch to completion.

    ``make_params(i)`` supplies a per-request ``SamplingParams`` (the
    sampled-decode workload); ``None`` keeps legacy greedy requests."""
    from repro.serving.engine import Request

    n_before = len(eng.done)
    t0 = time.time()
    for i, p in enumerate(prompts):
        sp = make_params(i) if make_params is not None else None
        eng.submit(Request(uid=i, prompt=p, max_new=max_new, params=sp))
    eng.run()
    wall = time.time() - t0
    done = eng.done[n_before:]
    toks = sum(len(r.output) for r in done)
    # median, not mean: a single OS-noise hiccup on a CI box shouldn't
    # dominate an 8-request latency figure
    ttft = float(np.median([r.t_first - r.t_submit for r in done])) * 1e3
    itl = float(np.mean([
        (r.t_done - r.t_first) / max(len(r.output) - 1, 1) for r in done
    ])) * 1e3
    outs = {r.uid: r.output for r in done}
    return outs, toks / wall, ttft, itl, wall


def _serve(model, params, prompts, layout, max_new, slots=4, max_len=128,
           **kw):
    """Warmup pass + measured pass on ONE engine.

    The warmup runs the identical workload first, so the measured TTFT
    excludes the first-call jit compile (and, for the prefix-cached
    engine, reflects a warm hash index — the steady-serving state).  A
    single-request primer pass precedes the warmup batch: it seeds the
    hash index, so the warmup batch itself takes the hash-hit admission
    path and compiles the short-suffix chunk shapes the measured pass
    will use.  Returns measured stats plus the warmup overhead
    (warmup wall minus steady wall, dominated by jit compile)."""
    from repro.serving.engine import Engine

    eng = Engine(
        model, params, slots=slots, max_len=max_len, cache_layout=layout,
        page_size=16, **kw,
    )
    # primer: seeds the hash index so the warmup batch already takes the
    # hash-hit admission path
    *_, primer_wall = _run_pass(eng, prompts[:1], max_new)
    *_, warm_wall = _run_pass(eng, prompts, max_new)
    # best-of-2 measured passes: steady-state latency, not OS jitter
    outs, tps, ttft, itl, wall = _run_pass(eng, prompts, max_new)
    outs2, tps2, ttft2, itl2, wall2 = _run_pass(eng, prompts, max_new)
    assert outs2 == outs, "engine output changed between identical passes"
    if ttft2 < ttft:
        tps, ttft, itl, wall = tps2, ttft2, itl2, wall2
    # compile overhead = cold passes minus their steady-state equivalents
    # (the primer serves 1 of len(prompts) requests)
    steady_cold = wall * (1 + 1 / max(len(prompts), 1))
    compile_s = max(primer_wall + warm_wall - steady_cold, 0.0)
    return outs, tps, ttft, itl, wall, compile_s


def run(report):
    from repro.configs import get_smoke_config
    from repro.kernels import ops
    from repro.models.model import build_model

    # ---------------------------------------------------- engine A/B
    cfg = get_smoke_config("qwen2-7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(5, cfg.vocab_size, size=int(rng.integers(4, 48)))
        .astype(np.int32)
        for _ in range(12)
    ]
    stats = {}
    for layout in ("dense", "paged"):
        outs, tps, ttft, itl, wall, compile_s = _serve(
            model, params, prompts, layout, 16
        )
        stats[layout] = outs
        report(
            f"serving/engine_{layout}", wall * 1e6,
            f"tok/s={tps:.1f} ttft_ms={ttft:.1f} itl_ms={itl:.2f}",
        )
        report(
            f"serving/engine_{layout}_compile", compile_s * 1e6,
            "first-pass jit compile overhead (excluded from ttft)",
        )
    assert stats["paged"] == stats["dense"], \
        "paged engine diverged from dense-slot engine (greedy parity)"

    # ------------------------------------- sampled-decode workload
    # Generation API v2: per-request temperature/top-k/top-p through the
    # fused on-device sampler.  Selection runs inside the same jitted
    # decode step as greedy (the filter is a few VMEM sweeps over the
    # (B, V) logit panel vs the model's matmuls), so sampled throughput
    # must stay within 10% of greedy on the identical workload.  Greedy
    # and sampled passes run INTERLEAVED on one engine (same compiled
    # step, best-of-3 each) so machine drift between phases cannot fake
    # a regression; fixed per-request seeds make the sampled passes
    # deterministic, asserted across repeats.
    from repro.serving.engine import Engine
    from repro.serving.sampling import SamplingParams

    def mk(i):
        return SamplingParams(temperature=0.8, top_k=40, top_p=0.9,
                              seed=1000 + i, max_new=16)

    eng = Engine(model, params, slots=4, max_len=128, cache_layout="paged",
                 page_size=16)
    _run_pass(eng, prompts, 16)             # warm greedy shapes
    _run_pass(eng, prompts, 16, mk)         # warm sampled shapes
    # best-of-3 per variant, interleaved: a single noisy pass on a loaded
    # CI box must not be able to fake a >10% regression
    gs, ss = [], []
    for _ in range(3):
        gs.append(_run_pass(eng, prompts, 16))
        ss.append(_run_pass(eng, prompts, 16, mk))
    assert all(s[0] == ss[0][0] for s in ss), \
        "fixed-seed sampled pass not deterministic"
    assert gs[0][0] == stats["paged"], "greedy output drifted between engines"
    tps_g = max(g[1] for g in gs)
    tps_s = max(s[1] for s in ss)
    ratio = tps_s / max(tps_g, 1e-9)
    report(
        "serving/engine_paged_sampled", min(s[4] for s in ss) * 1e6,
        f"tok/s={tps_s:.1f} itl_ms={min(s[3] for s in ss):.2f} "
        f"vs_greedy={ratio:.2f}x (interleaved best-of-3)",
    )
    assert tps_s >= 0.9 * tps_g, (
        f"sampled decode must stay within 10% of greedy tok/s "
        f"(greedy {tps_g:.1f}, sampled {tps_s:.1f})"
    )

    # ------------------------------------- telemetry overhead A/B
    # Unified telemetry (repro.obs) is host-side appends on paths the
    # engine already walks, so turning the registry + lifecycle tracer ON
    # must cost nothing the clock can see: interleaved best-of-5 greedy
    # passes on two warmed engines (pass-to-pass OS noise on a CI box is
    # ~8%, so the best-of envelope needs more samples than the 10%-band
    # sampled assertion above), token parity asserted, ON tok/s within 2%
    # of OFF.  The instrumented engine's histograms then supply
    # the TTFT/ITL latency distribution rows (p50/p95/p99) — quantiles a
    # single pass's median/mean summary cannot express.
    from repro.obs import MetricsRegistry, TraceRecorder

    reg = MetricsRegistry()
    tracer = TraceRecorder(capacity=16384)
    eng_off = Engine(model, params, slots=4, max_len=128,
                     cache_layout="paged", page_size=16)
    eng_on = Engine(model, params, slots=4, max_len=128,
                    cache_layout="paged", page_size=16,
                    metrics=reg, trace=tracer)
    _run_pass(eng_off, prompts, 16)         # warm (jit caches are shared,
    _run_pass(eng_on, prompts, 16)          # but warm both for symmetry)
    offs, ons = [], []
    for _ in range(5):
        offs.append(_run_pass(eng_off, prompts, 16))
        ons.append(_run_pass(eng_on, prompts, 16))
    assert ons[0][0] == offs[0][0] == stats["paged"], \
        "telemetry changed generated tokens"
    tps_off = max(o[1] for o in offs)
    tps_on = max(o[1] for o in ons)
    report(
        "serving/telemetry_off", min(o[4] for o in offs) * 1e6,
        f"tok/s={tps_off:.1f} (registry+tracer disabled, best-of-5)",
    )
    report(
        "serving/telemetry_on", min(o[4] for o in ons) * 1e6,
        f"tok/s={tps_on:.1f} overhead={(tps_off / max(tps_on, 1e-9) - 1) * 100:+.1f}% "
        f"trace_events={tracer.emitted}",
    )
    assert tps_on >= 0.98 * tps_off, (
        f"instrumentation must cost <2% tok/s "
        f"(off {tps_off:.1f}, on {tps_on:.1f})"
    )
    # registry counters must agree with the engine's own health view
    h = eng_on.health()
    fam = reg.get("engine_requests_total")
    for k, v in h.counters.items():
        assert fam.labels(k).value == v, f"registry/health drift on {k!r}"
    # latency distribution rows from the instrumented engine's histograms
    # (warmup + 5 measured passes x 12 requests): these land in
    # BENCH_serving.json, so TTFT/ITL tail regressions become visible in
    # the trajectory, not just the medians
    h_ttft = reg.get("engine_ttft_seconds")
    h_itl = reg.get("engine_itl_seconds")
    report(
        "serving/ttft_quantiles", h_ttft.quantile(0.5) * 1e6,
        f"p50={h_ttft.quantile(0.5) * 1e3:.1f}ms "
        f"p95={h_ttft.quantile(0.95) * 1e3:.1f}ms "
        f"p99={h_ttft.quantile(0.99) * 1e3:.1f}ms n={h_ttft.count}",
    )
    report(
        "serving/itl_quantiles", h_itl.quantile(0.5) * 1e6,
        f"p50={h_itl.quantile(0.5) * 1e3:.2f}ms "
        f"p95={h_itl.quantile(0.95) * 1e3:.2f}ms "
        f"p99={h_itl.quantile(0.99) * 1e3:.2f}ms n={h_itl.count}",
    )

    # ------------------------------------- shared-prefix workload
    # every request carries the same 480-token task preamble + a unique
    # short tail (the fixed-scaffold protein/chemistry pattern): the
    # prefix cache prefills the preamble once and shares its pages; the
    # baseline recomputes all 488 rows for every request.
    preamble = rng.integers(5, cfg.vocab_size, size=480).astype(np.int32)
    shared_prompts = [
        np.concatenate(
            [preamble, rng.integers(5, cfg.vocab_size, size=8).astype(np.int32)]
        )
        for _ in range(8)
    ]
    # enough slots to admit the whole batch at once: TTFT is then purely
    # prefill-side (admission order), not shared decode-completion waits
    base_out, _, ttft_base, _, _, _ = _serve(
        model, params, shared_prompts, "paged", 8, slots=8, max_len=512
    )
    pfx_out, _, ttft_pfx, _, _, _ = _serve(
        model, params, shared_prompts, "paged", 8, slots=8, max_len=512,
        prefix_cache=True, prefill_chunk=32,
    )
    assert pfx_out == base_out, \
        "prefix caching changed tokens on the shared-prefix workload"
    speedup = ttft_base / max(ttft_pfx, 1e-9)
    report("serving/shared_prefix_ttft_base", ttft_base * 1e3,
           "paged baseline: full 488-token prefill per request")
    report("serving/shared_prefix_ttft_cached", ttft_pfx * 1e3,
           f"prefix cache + chunked prefill; ttft_speedup={speedup:.1f}x")
    assert speedup >= 2.0, (
        f"prefix caching must cut shared-prefix TTFT >=2x "
        f"(got {speedup:.2f}x: {ttft_base:.1f}ms -> {ttft_pfx:.1f}ms)"
    )

    # ------------------------------------- long-cache decode write A/B
    B, T, Hkv, D, page = 8, 4096, 4, 64, 16
    key = jax.random.PRNGKey(1)
    k_cache = jax.random.normal(key, (B, T, Hkv, D), jnp.float32)
    v_cache = jax.random.normal(jax.random.fold_in(key, 1), k_cache.shape,
                                jnp.float32)
    k_new = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, Hkv, D),
                              jnp.float32)
    v_new = jax.random.normal(jax.random.fold_in(key, 3), k_new.shape,
                              jnp.float32)
    widx = jnp.asarray(rng.integers(0, T, size=B), jnp.int32)

    def dense_write(kc, vc, kn, vn, w):
        # the O(B·T) masked select models/attention.py uses per decode
        # token in the dense per-slot layout
        onehot = (jnp.arange(T)[None, :] == w[:, None])[..., None, None]
        return jnp.where(onehot, kn, kc), jnp.where(onehot, vn, vc)

    num_pages = 1 + B * (T // page)
    k_pool = jax.random.normal(key, (num_pages, page, Hkv, D), jnp.float32)
    v_pool = jax.random.normal(jax.random.fold_in(key, 4), k_pool.shape,
                               jnp.float32)
    page_idx = jnp.asarray(1 + rng.integers(0, num_pages - 1, size=B),
                           jnp.int32)
    row = jnp.asarray(rng.integers(0, page, size=B), jnp.int32)

    def _bench_state(fn, state, *args, iters=10, warmup=2) -> float:
        # donate the cache buffers (the serving decode loop's steady state)
        # so XLA may update in place — without donation both layouts pay a
        # full-pool copy that hides the write cost difference
        jfn = jax.jit(fn, donate_argnums=(0, 1))
        for _ in range(warmup):
            state = jfn(*state, *args)
            jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(iters):
            state = jfn(*state, *args)
            jax.block_until_ready(state)
        return (time.perf_counter() - t0) / iters * 1e6

    us_dense = _bench_state(
        dense_write, (k_cache, v_cache), k_new, v_new, widx
    )
    us_paged = _bench_state(
        lambda kp, vp, kn, vn, pi, r: ops.paged_kv_update(
            kp, vp, kn, vn, pi, r, impl="xla"
        ),
        (k_pool, v_pool), k_new, v_new, page_idx, row,
    )
    report("serving/kv_write_dense_T4096", us_dense,
           f"O(B*T) masked select, {B * T * Hkv * D * 4 * 2 / 1e6:.0f}MB touched")
    report("serving/kv_write_paged_T4096", us_paged,
           f"O(B*page) scatter; speedup={us_dense / us_paged:.1f}x")
    assert us_paged < us_dense, (
        f"paged decode write ({us_paged:.0f}us) should beat the O(B*T) "
        f"masked select ({us_dense:.0f}us) at T={T}"
    )

    # ------------------------------------- degraded-mode workload
    # The fault-tolerance contract under overload: 4 new requests arrive
    # per engine step against 4 slots completing ~0.5 req/step (8x
    # oversubscribed).  The unbounded engine queues every arrival, so the
    # p99 TTFT of ACCEPTED requests grows with the backlog; the bounded
    # engine (max_queue=6) converts the backlog into typed
    # EngineOverloaded rejections the client can retry, keeping accepted
    # p99 TTFT low.  Rejections instead of unbounded queueing — asserted,
    # plus greedy token parity per accepted uid against the unbounded run
    # (backpressure must not change what survivors generate).
    from repro.serving.engine import EngineOverloaded, Request

    over_prompts = [
        rng.integers(5, cfg.vocab_size, size=int(rng.integers(6, 24)))
        .astype(np.int32)
        for _ in range(32)
    ]

    def _overload(max_queue):
        eng = Engine(model, params, slots=4, max_len=64,
                     cache_layout="paged", page_size=16,
                     max_queue=max_queue)
        _run_pass(eng, over_prompts[:4], 8)  # warm the jit caches
        n_before = len(eng.done)
        accepted, rejected = [], 0
        pending = list(enumerate(over_prompts))
        t0 = time.time()
        while pending:
            for _ in range(4):  # 4 arrivals per engine step
                if not pending:
                    break
                i, p = pending.pop(0)
                try:
                    eng.submit(Request(uid=i, prompt=p, max_new=8))
                    accepted.append(i)
                except EngineOverloaded:
                    rejected += 1
            eng.step()
        eng.run()
        wall = time.time() - t0
        done = {r.uid: r for r in eng.done[n_before:]}
        assert sorted(done) == sorted(accepted), \
            "overload pass lost accepted requests"
        ttft_ms = np.asarray(
            [done[u].t_first - done[u].t_submit for u in accepted]
        ) * 1e3
        p99 = float(np.percentile(ttft_ms, 99))
        return {u: done[u].output for u in accepted}, p99, rejected, wall

    outs_unb, p99_unb, rej_unb, _ = _overload(0)
    outs_bnd, p99_bnd, rej_bnd, _ = _overload(6)
    report("serving/overload_unbounded_p99ttft", p99_unb * 1e3,
           f"accepted={len(outs_unb)}/32 rejected={rej_unb} "
           "(every arrival queued)")
    report("serving/overload_bounded_p99ttft", p99_bnd * 1e3,
           f"accepted={len(outs_bnd)}/32 rejected={rej_bnd} max_queue=6 "
           f"p99_cut={p99_unb / max(p99_bnd, 1e-9):.1f}x")
    assert rej_unb == 0, "unbounded engine must not reject"
    assert rej_bnd > 0, "bounded engine must shed load under 8x overload"
    assert p99_bnd < p99_unb, (
        f"bounded queue must cut accepted p99 TTFT under overload "
        f"(unbounded {p99_unb:.1f}ms, bounded {p99_bnd:.1f}ms)"
    )
    for u, out in outs_bnd.items():
        assert out == outs_unb[u], \
            f"backpressure changed tokens for accepted request {u}"

    # seeded chaos pass: NaN injection + an allocator outage from
    # serving/faults.FaultPlan.  The engine must drain every request, and
    # the non-quarantined survivors must be token-identical to a
    # fault-free engine on the same workload (fault isolation: a poisoned
    # slot never contaminates its batch neighbours).
    from repro.serving.faults import FaultPlan

    def _chaos(plan):
        eng = Engine(model, params, slots=4, max_len=64,
                     cache_layout="paged", page_size=16, faults=plan)
        for i, p in enumerate(over_prompts[:8]):
            eng.submit(Request(uid=i, prompt=p, max_new=8))
        t0 = time.time()
        eng.run()
        return ({r.uid: r for r in eng.done}, dict(eng.counters),
                time.time() - t0)

    ref, _, _ = _chaos(None)
    # seed 2 schedules a NaN at step 4 (all slots still active) plus a
    # 4-step allocator outage, so the quarantine path provably fires
    plan = FaultPlan.seeded(2, horizon=24, slots=4, nan_events=2, outages=1)
    fau, counters, chaos_wall = _chaos(plan)
    assert len(fau) == 8, "chaos engine failed to drain all requests"
    assert counters["errors"] >= 1, \
        "seeded plan must quarantine at least one slot"
    survivors = [u for u, r in fau.items()
                 if r.finish_reason in ("stop", "length")]
    for u in survivors:
        assert fau[u].output == ref[u].output, \
            f"chaos survivor {u} diverged from fault-free run"
    report("serving/chaos_seeded_drain", chaos_wall * 1e6,
           f"errors={counters['errors']} survivors={len(survivors)}/8 "
           "token-parity ok")

    # ------------------------------------- sharded-serving scaling
    # one subprocess per device count (the XLA virtual-device flag must
    # be set before jax initializes); per-token parity of every mesh run
    # against the 1-device run is the acceptance assertion — tok/s across
    # 1 -> 8 virtual devices is reported for the trajectory.
    base = _scaling_run(1)
    for n_dev, mesh_shape in ((4, (1, 4)), (8, (2, 4))):
        res = _scaling_run(n_dev, mesh_shape)
        assert res["outs"] == base["outs"], (
            f"{mesh_shape} mesh diverged from single-device output"
        )
        report(
            f"serving/scaling_{n_dev}dev",
            1e6 / max(res["tok_s"], 1e-9),
            f"tok/s={res['tok_s']:.1f} vs 1dev={base['tok_s']:.1f} "
            f"{mesh_shape} mesh, per-token parity asserted; virtual "
            "devices share cores — mechanism proof, not speedup",
        )
    report(
        "serving/scaling_1dev", 1e6 / max(base["tok_s"], 1e-9),
        f"tok/s={base['tok_s']:.1f} single-device reference",
    )


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run(lambda name, us, derived="": print(f"{name},{us:.1f},{derived}",
                                           flush=True))
