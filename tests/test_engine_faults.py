"""Chaos suite for the fault-tolerant serving engine.

Covers the full degraded-request lifecycle: bounded-queue rejection,
deadline expiry in queue and in flight (deterministic via an injected
fake clock), preempt-and-requeue token parity (xla and pallas_interpret
sampler impls), NaN-quarantine isolation, seeded FaultPlan schedules
across dense/paged/prefix layouts, crash-and-rebuild recovery, deadline
storms, and the health/watchdog snapshot.

The sharded section at the bottom re-runs the fault lifecycle on (1,8)
and (2,4) CPU meshes (subprocess: the XLA device-count flag must be set
before jax initializes) and asserts parity against a single-device
engine in the same process — faults must degrade identically no matter
how the cache is sharded.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.config import ModelConfig
from repro.models.model import build_model
from repro.serving.engine import Engine, EngineOverloaded, Request
from repro.serving.faults import FaultPlan, crash_and_rebuild, deadline_storm
from repro.serving.sampling import SamplingParams

VOCAB = 64


class FakeClock:
    """Deterministic time source for deadline tests: deadlines fire when
    the test says so, never when CI is slow."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


_CACHE = {}


def build(kernel_impl="auto"):
    if kernel_impl not in _CACHE:
        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=64, num_heads=4,
            num_kv_heads=2, d_ff=128, vocab_size=VOCAB, dtype="float32",
            kernel_impl=kernel_impl,
        )
        model = build_model(cfg)
        _CACHE[kernel_impl] = (model, model.init(jax.random.PRNGKey(0)))
    return _CACHE[kernel_impl]


def prompts_for(n, seed=0, lo=4, hi=10):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, VOCAB, size=int(rng.integers(lo, hi + 1))).astype(np.int32)
        for _ in range(n)
    ]


def by_uid(reqs):
    return sorted(reqs, key=lambda r: r.uid)


# ------------------------------------------------------------ backpressure
def test_overload_rejection_is_typed_and_retriable():
    model, params = build()
    ps = prompts_for(5)
    eng = Engine(model, params, slots=1, max_len=64, max_queue=2)
    eng.submit(Request(uid=0, prompt=ps[0], max_new=3))
    eng.submit(Request(uid=1, prompt=ps[1], max_new=3))
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(Request(uid=2, prompt=ps[2], max_new=3))
    assert ei.value.retriable and ei.value.max_queue == 2
    assert eng.counters["rejected"] == 1
    # the rejected request was not partially admitted anywhere
    assert len(eng.queue) == 2 and all(r is None for r in eng.slot_req)
    eng.run()
    # retriable by contract: after the queue drains the same submit works
    late = Request(uid=2, prompt=ps[2], max_new=3)
    eng.submit(late)
    eng.run()
    assert late.finish_reason == "length" and len(late.output) == 3
    assert eng.counters["completed"] == 3
    assert eng.counters["submitted"] == 3  # rejections never counted as submitted


def test_unbounded_queue_never_rejects():
    model, params = build()
    eng = Engine(model, params, slots=1, max_len=64)  # max_queue=0
    for i, p in enumerate(prompts_for(8)):
        eng.submit(Request(uid=i, prompt=p, max_new=2))
    assert len(eng.queue) == 8
    eng.run()
    assert len(eng.done) == 8


# --------------------------------------------------------------- deadlines
def test_deadline_expires_in_queue():
    model, params = build()
    clk = FakeClock()
    ps = prompts_for(3)
    eng = Engine(model, params, slots=1, max_len=64, clock=clk)
    slow = Request(uid=0, prompt=ps[0], max_new=6)
    tight = Request(uid=1, prompt=ps[1], max_new=6, deadline_ms=50.0)
    # params.deadline_ms takes precedence over the Request field
    loose = Request(uid=2, prompt=ps[2], max_new=6, deadline_ms=1.0,
                    params=SamplingParams(deadline_ms=60_000.0))
    for r in (slow, tight, loose):
        eng.submit(r)
    clk.advance(0.2)  # 200ms: past tight's deadline before anything ran
    eng.run()
    assert tight.finish_reason == "timeout" and tight.output is None
    assert tight.t_first == 0.0
    assert slow.finish_reason == "length" and len(slow.output) == 6
    assert loose.finish_reason == "length" and len(loose.output) == 6
    assert eng.counters["timeouts"] == 1


def test_deadline_expires_in_flight_keeps_partial_output():
    model, params = build()
    clk = FakeClock()
    p = prompts_for(1)[0]
    eng = Engine(model, params, slots=1, max_len=64, clock=clk)
    req = Request(uid=0, prompt=p, max_new=20, deadline_ms=1_000.0)
    eng.submit(req)
    for _ in range(4):  # admit + a few decode steps, all inside deadline
        eng.step()
    produced = len(req.output)
    assert req.finish_reason == "" and produced >= 2
    clk.advance(5.0)  # blow the deadline; release at next step boundary
    eng.step()
    assert req.finish_reason == "timeout"
    assert len(req.output) >= produced  # partial tokens survive
    assert req.t_done == clk.t
    # slot is actually free again: a new request admits and completes
    nxt = Request(uid=1, prompt=p, max_new=3)
    eng.submit(nxt)
    eng.run()
    assert nxt.finish_reason == "length"


def test_deadline_storm_drains_deterministically():
    model, params = build()
    clk = FakeClock()
    ps = prompts_for(8, seed=3)
    reqs = [Request(uid=i, prompt=p, max_new=6) for i, p in enumerate(ps)]
    stormed = deadline_storm(reqs, seed=7, fraction=0.6,
                             deadline_ms=(5.0, 40.0))
    assert stormed  # seed 7 storms at least one request
    eng = Engine(model, params, slots=2, max_len=64, cache_layout="paged",
                 page_size=8, clock=clk)
    for r in reqs:
        eng.submit(r)
    steps = 0
    while (eng.queue or any(s is not None for s in eng.slot_req)) and steps < 500:
        eng.step()
        clk.advance(0.004)  # 4ms per step: some storm deadlines fire mid-run
        steps += 1
    assert all(r.finish_reason for r in reqs)
    for r in reqs:
        assert r.finish_reason in ("length", "timeout"), r.finish_reason
        if r.uid not in stormed:
            assert r.finish_reason == "length"
    assert eng.counters["timeouts"] == sum(
        r.finish_reason == "timeout" for r in reqs
    )
    eng.alloc.check_invariants()


# -------------------------------------------------------------- preemption
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_preempt_resume_token_parity(impl):
    """The acceptance bar: a preempted-and-resumed request is
    token-for-token identical to the same request run without preemption,
    under real (non-greedy) sampling — the counter-hash PRNG keyed on
    (seed, gen index) is what makes the replay exact."""
    model, params = build(impl)
    ps = prompts_for(3, seed=1, lo=5, hi=6)

    def serve(preempt, num_pages):
        eng = Engine(model, params, slots=3, max_len=32, cache_layout="paged",
                     page_size=8, num_pages=num_pages, preempt=preempt,
                     prefix_cache=True)
        reqs = [
            Request(uid=i, prompt=ps[i], max_new=12,
                    params=SamplingParams(temperature=0.8, top_k=12,
                                          seed=40 + i))
            for i in range(3)
        ]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return eng, reqs

    # generous pool: all three run concurrently, nobody preempted
    base_eng, base = serve(preempt=False, num_pages=0)
    assert base_eng.counters["preempted"] == 0
    # tight pool: 7 usable pages, 3 per request -> the third admission
    # must evict the newest in-flight decode and resume it later
    eng, reqs = serve(preempt=True, num_pages=8)
    assert eng.counters["preempted"] >= 1
    assert eng.counters["resumed"] >= 1
    assert any(r.preempted == 1 for r in reqs)
    for got, ref in zip(by_uid(reqs), by_uid(base)):
        assert got.finish_reason == ref.finish_reason
        assert list(got.output) == list(ref.output), (
            f"uid {got.uid} diverged after preemption"
        )
    eng.alloc.check_invariants()


def test_preempt_disabled_head_of_line_blocks():
    """Same tight pool without preempt=True: nobody is evicted; the
    blocked request waits for a slot's pages (FIFO preserved)."""
    model, params = build()
    ps = prompts_for(3, seed=1, lo=5, hi=6)
    eng = Engine(model, params, slots=3, max_len=32, cache_layout="paged",
                 page_size=8, num_pages=8)
    reqs = [Request(uid=i, prompt=ps[i], max_new=12) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.counters["preempted"] == 0
    assert all(r.preempted == 0 for r in reqs)
    assert all(r.finish_reason == "length" for r in reqs)


def test_preempt_requires_paged_layout():
    model, params = build()
    with pytest.raises(ValueError, match="preempt"):
        Engine(model, params, slots=2, max_len=32, preempt=True)


# ------------------------------------------------------------- quarantine
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_nan_quarantine_isolates_one_slot(layout):
    model, params = build()
    ps = prompts_for(2, seed=2)

    def serve(faults):
        eng = Engine(model, params, slots=2, max_len=64,
                     cache_layout=layout, page_size=8, faults=faults)
        reqs = [Request(uid=i, prompt=ps[i], max_new=8) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return eng, reqs

    _, clean = serve(None)
    eng, faulted = serve(FaultPlan(nan={4: (1,)}))
    victim, survivor = faulted[1], faulted[0]
    assert victim.finish_reason == "error"
    assert len(victim.output) < 8  # cut short at the injected step
    assert eng.counters["errors"] == 1
    # the whole point: the other slot's tokens are bit-identical to the
    # fault-free run — one slot's NaN never leaks into the batch
    assert survivor.finish_reason == clean[0].finish_reason
    assert list(survivor.output) == list(clean[0].output)


def test_nan_on_admission_first_token():
    model, params = build()
    p = prompts_for(1)[0]
    # step 1 is the admission step for the first request: the injected
    # NaN hits the prefill first-token path, not the decode loop
    eng = Engine(model, params, slots=1, max_len=64,
                 faults=FaultPlan(nan={1: (0,)}))
    bad = Request(uid=0, prompt=p, max_new=8)
    ok = Request(uid=1, prompt=p, max_new=4)
    eng.submit(bad)
    eng.submit(ok)
    eng.run()
    assert bad.finish_reason == "error" and not bad.output
    assert ok.finish_reason == "length" and len(ok.output) == 4


# ------------------------------------------------------------ chaos sweep
CHAOS_LAYOUTS = (
    dict(cache_layout="dense"),
    dict(cache_layout="paged", page_size=8),
    dict(cache_layout="paged", page_size=8, prefix_cache=True,
         prefill_chunk=4),
)


@pytest.mark.parametrize("seed", range(5))
def test_chaos_seeded_fault_plans(seed):
    """Acceptance bar: >=5 seeded FaultPlan schedules, rotating through
    dense / paged / paged+prefix layouts.  Every request must reach a
    terminal state, allocator invariants must hold, and every request
    that finished NORMALLY must be token-identical to a fault-free run
    (faults may kill requests; they may never corrupt survivors)."""
    model, params = build()
    ps = prompts_for(6, seed=100 + seed)
    layout = CHAOS_LAYOUTS[seed % len(CHAOS_LAYOUTS)]

    def serve(faults):
        eng = Engine(model, params, slots=2, max_len=64, faults=faults,
                     **layout)
        reqs = [Request(uid=i, prompt=p, max_new=6)
                for i, p in enumerate(ps)]
        for r in reqs:
            eng.submit(r)
        eng.run(max_steps=2_000)
        return eng, reqs

    _, clean = serve(None)
    assert all(r.finish_reason == "length" for r in clean)
    plan = FaultPlan.seeded(seed, horizon=24, slots=2, nan_events=2,
                            outages=1, max_outage=4)
    eng, reqs = serve(plan)
    assert all(r.finish_reason for r in reqs), "chaos run did not drain"
    for got, ref in zip(by_uid(reqs), by_uid(clean)):
        assert got.finish_reason in ("length", "error")
        if got.finish_reason == "length":
            assert list(got.output) == list(ref.output), (
                f"seed {seed}: survivor uid {got.uid} corrupted"
            )
    assert eng.counters["errors"] == sum(
        r.finish_reason == "error" for r in reqs
    )
    if eng.alloc is not None:
        eng.alloc.check_invariants()
        assert eng.alloc.free_pages == eng.alloc.num_pages - 1


def test_crash_and_rebuild_token_parity():
    model, params = build()
    ps = prompts_for(4, seed=5)

    def mk():
        return Engine(model, params, slots=2, max_len=64,
                      cache_layout="paged", page_size=8,
                      faults=FaultPlan(crash_at=4))

    ref_eng = Engine(model, params, slots=2, max_len=64,
                     cache_layout="paged", page_size=8)
    ref = [Request(uid=i, prompt=p, max_new=6) for i, p in enumerate(ps)]
    for r in ref:
        ref_eng.submit(r)
    ref_eng.run()

    reqs = [Request(uid=i, prompt=p, max_new=6) for i, p in enumerate(ps)]
    done, crashed = crash_and_rebuild(mk, reqs)
    assert crashed
    assert len(done) == len(reqs)
    for got, want in zip(by_uid(reqs), by_uid(ref)):
        assert got.finish_reason == want.finish_reason
        assert list(got.output) == list(want.output)


# ----------------------------------------------------------------- health
def test_health_watchdog_climbs_during_outage():
    model, params = build()
    p = prompts_for(1)[0]
    # a 6-step allocator outage from step 1: the queued request cannot
    # admit, nothing progresses, the watchdog counts every stalled step
    eng = Engine(model, params, slots=1, max_len=64,
                 faults=FaultPlan(alloc_outages=((1, 6),)))
    eng.submit(Request(uid=0, prompt=p, max_new=3))
    for _ in range(6):
        eng.step()
    h = eng.health()
    assert h.steps == 6
    assert h.steps_since_progress == 6
    assert h.queue_depth == 1 and h.active_slots == 0
    eng.run()
    h = eng.health()
    assert h.steps_since_progress == 0
    assert h.counters["completed"] == 1
    assert h.queue_depth == 0 and h.active_slots == 0


def test_health_reports_pages_and_counters():
    model, params = build()
    ps = prompts_for(2)
    eng = Engine(model, params, slots=2, max_len=32, cache_layout="paged",
                 page_size=8)
    h0 = eng.health()
    assert h0.free_pages == h0.total_pages
    for i, p in enumerate(ps):
        eng.submit(Request(uid=i, prompt=p, max_new=4))
    eng.step()
    assert eng.health().free_pages < h0.total_pages
    eng.run()
    h = eng.health()
    assert h.free_pages == h0.total_pages
    assert h.counters["submitted"] == 2 and h.counters["completed"] == 2


# -------------------------------------------------------------- API facade
def test_llm_surfaces_overload_and_timeout_as_outcomes():
    from repro.serving.api import LLM

    model, params = build()
    ps = prompts_for(5, seed=4)
    llm = LLM(model, params, slots=1, max_len=64, max_queue=2)
    outs = llm.generate(ps, SamplingParams(max_new=3))
    assert len(outs) == 5
    reasons = [c.finish_reason for c in outs]
    # submission happens before any engine step, so the queue cap of 2
    # admits exactly 2 of the 5 prompts; the other 3 come back as typed
    # outcomes, not raises, and the accepted ones still run
    assert reasons.count("overloaded") == 3
    assert reasons.count("length") == 2
    for c in outs:
        if c.finish_reason == "overloaded":
            # never produced a token / never reached a slot: timings are
            # explicitly None, not a fake 0.0
            assert c.tokens == [] and c.ttft_s is None
            assert c.queue_wait_s is None
        else:
            assert len(c.tokens) == 3
    # the engine is still healthy for the next call
    outs2 = llm.generate(ps[:2], SamplingParams(max_new=2))
    assert [c.finish_reason for c in outs2] == ["length", "length"]


def test_llm_stream_emits_terminal_chunk_for_rejected_request():
    from repro.serving.api import LLM

    model, params = build()
    ps = prompts_for(4, seed=4)
    llm = LLM(model, params, slots=1, max_len=64, max_queue=2)
    chunks = list(llm.stream(ps, SamplingParams(max_new=2)))
    done = {c.index: c.finish_reason for c in chunks if c.done}
    assert set(done) == {0, 1, 2, 3}  # every request gets a terminal chunk
    assert sorted(done.values()) == ["length", "length", "overloaded",
                                     "overloaded"]
    rejected = [c for c in chunks if c.finish_reason == "overloaded"]
    assert all(c.token == -1 for c in rejected)


# -------------------------------------------------- faults on the mesh
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# 8 kv heads (not the in-process suite's 2) so the paged pools genuinely
# shard over every tested model-axis size instead of degrading to
# replication via sharding.fit_spec.
_MESH_COMMON = textwrap.dedent("""
    import jax, numpy as np
    from repro.core.config import ModelConfig, ParallelConfig
    from repro.models.model import build_model
    from repro.obs.trace import TraceRecorder
    from repro.serving.engine import Engine, Request
    from repro.serving.faults import FaultPlan
    from repro.serving.sampling import SamplingParams
    from repro.launch.mesh import make_mesh

    class FakeClock:
        def __init__(self):
            self.t = 0.0
        def __call__(self):
            return self.t
        def advance(self, s):
            self.t += s

    CFG = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=8, num_kv_heads=8, d_ff=128, vocab_size=64,
                      dtype="float32")
    PARAMS = build_model(CFG).init(jax.random.PRNGKey(0))
    MESH = make_mesh(__MESH__, ("data", "model"))

    def model_for(mesh):
        return build_model(CFG, ParallelConfig(), mesh)

    def prompts_for(n, seed=0, lo=4, hi=10):
        rng = np.random.default_rng(seed)
        return [rng.integers(0, 64, size=int(rng.integers(lo, hi + 1)))
                .astype(np.int32) for _ in range(n)]

    def by_uid(reqs):
        return sorted(reqs, key=lambda r: r.uid)
""")

_MESH_LIFECYCLE = _MESH_COMMON + textwrap.dedent("""
    # --- preempt-resume parity: tight page pool forces an eviction on
    # the mesh; tokens must match the un-preempted single-device run
    ps = prompts_for(3, seed=1, lo=5, hi=6)

    def serve(mesh, preempt, num_pages):
        eng = Engine(model_for(mesh), PARAMS, slots=3, max_len=32,
                     cache_layout="paged", page_size=8, num_pages=num_pages,
                     preempt=preempt, prefix_cache=True)
        reqs = [Request(uid=i, prompt=ps[i], max_new=12,
                        params=SamplingParams(temperature=0.8, top_k=12,
                                              seed=40 + i))
                for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return eng, reqs

    _, base = serve(None, False, 0)
    eng, reqs = serve(MESH, True, 8)
    assert eng.counters["preempted"] >= 1 and eng.counters["resumed"] >= 1
    for got, ref in zip(by_uid(reqs), by_uid(base)):
        assert got.finish_reason == ref.finish_reason
        assert list(got.output) == list(ref.output), got.uid
    eng.alloc.check_invariants()
    print("OK preempt")

    # --- NaN quarantine: logits are computed sharded; the injected NaN
    # must still quarantine exactly one slot, and the neighbour's tokens
    # stay bit-identical to the fault-free single-device run
    qs = prompts_for(2, seed=2)

    def serve_q(mesh, faults):
        eng = Engine(model_for(mesh), PARAMS, slots=2, max_len=64,
                     cache_layout="paged", page_size=8, faults=faults)
        reqs = [Request(uid=i, prompt=qs[i], max_new=8) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return eng, reqs

    _, clean = serve_q(None, None)
    eng, faulted = serve_q(MESH, FaultPlan(nan={4: (1,)}))
    victim, survivor = faulted[1], faulted[0]
    assert victim.finish_reason == "error" and len(victim.output) < 8
    assert eng.counters["errors"] == 1
    assert survivor.finish_reason == clean[0].finish_reason
    assert list(survivor.output) == list(clean[0].output)
    print("OK quarantine")

    # --- trace byte-parity: the lifecycle JSONL of a seeded chaos run
    # (fake clock) is byte-identical on and off the mesh
    ts = prompts_for(4, seed=9)

    def serve_t(mesh):
        clk, rec = FakeClock(), TraceRecorder()
        eng = Engine(model_for(mesh), PARAMS, slots=2, max_len=64,
                     cache_layout="paged", page_size=8, clock=clk, trace=rec,
                     faults=FaultPlan.seeded(3, horizon=24, slots=2,
                                             nan_events=1, outages=1,
                                             max_outage=3))
        for i, p in enumerate(ts):
            eng.submit(Request(uid=i, prompt=p, max_new=6))
        while eng.queue or any(s is not None for s in eng.slot_req):
            eng.step()
            clk.advance(0.01)
        return rec.to_jsonl()

    assert serve_t(MESH) == serve_t(None)
    print("OK trace")
""")


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_mesh_fault_lifecycle_parity(mesh_shape):
    """Preempt-resume parity, NaN-quarantine isolation, and byte-identical
    lifecycle traces, re-pinned on the mesh."""
    out = run_py(_MESH_LIFECYCLE.replace("__MESH__", repr(mesh_shape)))
    assert out.count("OK") == 3, out


_MESH_CHAOS = _MESH_COMMON + textwrap.dedent("""
    for seed in range(5):
        ps = prompts_for(6, seed=100 + seed)

        def serve(mesh, faults):
            eng = Engine(model_for(mesh), PARAMS, slots=2, max_len=64,
                         cache_layout="paged", page_size=8, faults=faults)
            reqs = [Request(uid=i, prompt=p, max_new=6)
                    for i, p in enumerate(ps)]
            for r in reqs:
                eng.submit(r)
            eng.run(max_steps=2_000)
            return eng, reqs

        _, clean = serve(None, None)
        plan = FaultPlan.seeded(seed, horizon=24, slots=2, nan_events=2,
                                outages=1, max_outage=4)
        eng, reqs = serve(MESH, plan)
        assert all(r.finish_reason for r in reqs), f"seed {seed} did not drain"
        for got, ref in zip(by_uid(reqs), by_uid(clean)):
            assert got.finish_reason in ("length", "error")
            if got.finish_reason == "length":
                assert list(got.output) == list(ref.output), (seed, got.uid)
        eng.alloc.check_invariants()
        assert eng.alloc.free_pages == eng.alloc.num_pages - 1
        print("OK seed", seed)
""")


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_mesh_chaos_seeded_drain(mesh_shape):
    """Five seeded FaultPlan schedules drain on the mesh; survivors stay
    token-identical to the fault-free single-device run."""
    out = run_py(_MESH_CHAOS.replace("__MESH__", repr(mesh_shape)))
    assert out.count("OK seed") == 5, out
