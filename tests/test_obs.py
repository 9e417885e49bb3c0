"""Unified-telemetry acceptance suite (``repro.obs``).

Registry mechanics (counters/gauges/fixed-bucket histograms, labels,
Prometheus exposition, trajectory-format JSON dumps), ring-buffer trace
semantics, and the three cross-cutting contracts the observability layer
must honour:

  * **Determinism** — a seeded ``FaultPlan`` run driven by a fake clock
    produces a byte-identical, schema-valid JSONL lifecycle trace across
    runs (the trace is evidence, so it must be reproducible evidence).
  * **Parity** — the registry's lifecycle counters and ``Engine.health()``
    agree exactly across seeded chaos plans: both views are fed through
    the same ``_bump``, so they can never drift.
  * **Zero added transfers** — with the full instrumentation stack ON
    (registry + tracer + step-log spans) the engine still performs
    exactly ONE bulk device->host transfer per steady-state step and the
    trainer ONE per log interval, under ``jax.transfer_guard``.
"""
import json

import jax
import numpy as np
import pytest

from repro.core.config import ModelConfig, TrainConfig
from repro.data.dataset import build_synthetic_protein_memmap
from repro.data.pipeline import CLMBatches
from repro.models.model import build_model
from repro.obs import (
    EVENTS,
    LATENCY_BUCKETS,
    SCOPES,
    STEP_LOG,
    MetricsRegistry,
    StepLog,
    TraceRecorder,
    scope_map,
    span,
    trace_ctx,
)
from repro.serving.engine import Engine, Request
from repro.serving.faults import FaultPlan
from repro.serving.sampling import SamplingParams
from repro.training.loop import Trainer

VOCAB = 64


class FakeClock:
    """Deterministic time source (starts away from 0.0 so "never stamped"
    sentinels can never collide with a real timestamp)."""

    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class AutoClock(FakeClock):
    """Advances by a fixed dt on every read — lets ``Engine.run()`` hit
    deadlines without the test driving the step loop manually."""

    def __init__(self, t=1000.0, dt=0.05):
        super().__init__(t)
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


_CACHE = {}


def build():
    if "m" not in _CACHE:
        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=64, num_heads=4,
            num_kv_heads=2, d_ff=128, vocab_size=VOCAB, dtype="float32",
        )
        model = build_model(cfg)
        _CACHE["m"] = (model, model.init(jax.random.PRNGKey(0)))
    return _CACHE["m"]


def prompts_for(n, seed=0, lo=4, hi=10):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, VOCAB, size=int(rng.integers(lo, hi + 1)))
        .astype(np.int32)
        for _ in range(n)
    ]


# ------------------------------------------------------------ registry
def test_counter_gauge_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", labels=("event",))
    c.labels("submitted").inc()
    c.labels("submitted").inc(2)
    c.labels("rejected").inc()
    assert c.labels("submitted").value == 3
    assert c.labels("rejected").value == 1
    with pytest.raises(ValueError):
        c.labels("submitted").inc(-1)   # counters are monotonic
    with pytest.raises(ValueError):
        c.inc()                         # labeled family: must resolve first
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5                 # unlabeled family forwards to solo


def test_registry_idempotent_and_conflicting():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x", labels=("k",))
    b = reg.counter("x_total", "x", labels=("k",))
    assert a is b                       # two subsystems share one series
    with pytest.raises(ValueError):
        reg.gauge("x_total")            # same name, different kind
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=())  # same kind, different labels
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("ok_total", labels=("bad-label",))


def test_histogram_buckets_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(1.0, 2.0, 4.0))
    assert h.quantile(0.5) == 0.0       # empty: defined, not a crash
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(106.5)
    # p50 rank lands in the (1, 2] bucket; interpolated inside it
    assert 1.0 <= h.quantile(0.5) <= 2.0
    # overflow ranks clamp to the last finite boundary (lower bound)
    assert h.quantile(0.99) == 4.0
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(2.0, 1.0))
    assert LATENCY_BUCKETS == tuple(sorted(LATENCY_BUCKETS))


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "total requests", labels=("event",)) \
        .labels("submitted").inc(3)
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    assert "# HELP reqs_total total requests" in text
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{event="submitted"} 3' in text
    assert "depth 2" in text
    # histogram buckets are CUMULATIVE and end at +Inf == _count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    assert "lat_seconds_sum 5.55" in text
    assert text.endswith("\n")


def test_dump_json_matches_trajectory_shape(tmp_path):
    reg = MetricsRegistry()
    reg.counter("steps_total").inc(4)
    reg.histogram("ttft_seconds", buckets=(0.1, 1.0)).observe(0.2)
    path = str(tmp_path / "metrics.json")
    reg.dump_json(path, now=0.0, extra={"git": "abc1234"})
    reg.counter("steps_total").inc()
    reg.dump_json(path, now=60.0)
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"runs"}         # {"runs": [...]} trajectory shape
    assert len(doc["runs"]) == 2        # appended, not clobbered
    first, second = doc["runs"]
    assert first["timestamp"] == "1970-01-01T00:00:00Z"
    assert first["git"] == "abc1234"
    rows = {r["name"]: r for r in second["rows"]}
    assert rows["steps_total"]["value"] == 5
    hist = rows["ttft_seconds"]
    assert hist["count"] == 1 and "p95" in hist and "p99" in hist
    assert not list(tmp_path.glob("*.tmp.*"))  # atomic write left no turds


# --------------------------------------------------------------- trace
def test_trace_ring_buffer_bounds_and_validation():
    tr = TraceRecorder(capacity=4)
    for i in range(10):
        tr.emit("decode", ts=float(i), uid=i, step=i)
    assert len(tr) == 4 and tr.emitted == 10 and tr.dropped == 6
    assert [e["uid"] for e in tr.events()] == [6, 7, 8, 9]  # oldest fell off
    with pytest.raises(ValueError):
        tr.emit("reticulate", ts=0.0)   # typo'd events fail the producer
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)
    tr.clear()
    assert len(tr) == 0 and tr.emitted == 0


def test_trace_jsonl_render_and_write(tmp_path):
    tr = TraceRecorder()
    tr.emit("submit", ts=1.5, uid=3, step=0, prompt_tokens=7)
    tr.emit("finish", ts=2.5, uid=3, step=4, reason="length", tokens=8)
    lines = tr.to_jsonl().splitlines()
    assert len(lines) == 2
    first = json.loads(lines[0])
    assert first == {"event": "submit", "prompt_tokens": 7, "step": 0,
                     "ts": 1.5, "uid": 3}
    # keys sorted + compact separators => equal streams give equal bytes
    assert lines[0] == json.dumps(first, sort_keys=True,
                                  separators=(",", ":"))
    path = tmp_path / "trace.jsonl"
    tr.write(str(path))
    assert path.read_text() == tr.to_jsonl()
    assert not list(tmp_path.glob("*.tmp.*"))


# ----------------------------------------------- deterministic fault trace
def _traced_fault_run(seed):
    model, params = build()
    clk = FakeClock()
    tracer = TraceRecorder()
    reg = MetricsRegistry()
    plan = FaultPlan.seeded(seed, horizon=24, slots=4, nan_events=2,
                            outages=1)
    eng = Engine(model, params, slots=4, max_len=64, cache_layout="paged",
                 page_size=16, faults=plan, clock=clk, trace=tracer,
                 metrics=reg)
    ps = prompts_for(8, seed=1)
    for i, p in enumerate(ps):
        eng.submit(Request(uid=i, prompt=p, max_new=6))
    for _ in range(200):
        clk.advance(0.125)
        eng.step()
        if len(eng.done) == len(ps):
            break
    assert len(eng.done) == len(ps), "fault run failed to drain"
    return eng, reg, tracer


def test_fault_run_trace_is_byte_identical_and_schema_valid():
    eng, _, tr1 = _traced_fault_run(2)
    _, _, tr2 = _traced_fault_run(2)
    j1, j2 = tr1.to_jsonl(), tr2.to_jsonl()
    assert j1.encode() == j2.encode(), \
        "same seed + same clock must give the same bytes"
    events = []
    for line in j1.splitlines():
        e = json.loads(line)
        # schema: the three envelope fields always present and typed,
        # the event drawn from the closed vocabulary, keys sorted
        assert e["event"] in EVENTS
        assert isinstance(e["step"], int) and isinstance(e["uid"], int)
        assert isinstance(e["ts"], float) and e["ts"] >= 1000.0
        assert line == json.dumps(e, sort_keys=True, separators=(",", ":"))
        events.append(e)
    kinds = [e["event"] for e in events]
    assert kinds.count("submit") == 8 and kinds.count("finish") == 8
    # the seeded plan provably exercised a degraded path
    assert "quarantine" in kinds
    # per-request lifecycle ordering: submit precedes finish for every uid
    for uid in range(8):
        seq = [e["event"] for e in events if e["uid"] == uid]
        assert seq[0] == "submit" and seq[-1] == "finish"
    # timestamps are the engine clock: non-decreasing in buffer order
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)


@pytest.mark.parametrize("seed", range(5))
def test_chaos_counter_parity_with_health(seed):
    eng, reg, tracer = _traced_fault_run(seed)
    h = eng.health()
    fam = reg.get("engine_requests_total")
    for k, v in h.counters.items():
        assert fam.labels(k).value == v, \
            f"registry drifted from health() on {k!r} (seed {seed})"
    assert reg.get("engine_steps_total").value == eng.steps
    # tokens counter counts APPENDED tokens only — quarantined emissions
    # are dropped before they reach any request
    assert reg.get("engine_tokens_total").value == \
        sum(len(r.output or []) for r in eng.done)
    # every terminal outcome in the counters has a finish event on tape
    kinds = [e["event"] for e in tracer.events()]
    assert kinds.count("finish") == len(eng.done)


# -------------------------------------------------- transfer-guard parity
def test_instrumented_engine_still_one_bulk_transfer_per_step(monkeypatch):
    """The full stack ON (registry + tracer + step-log spans + on_step
    health probe) must not add a single device sync to the steady-state
    decode step."""
    model, params = build()
    reg = MetricsRegistry()
    tracer = TraceRecorder()
    probes = []
    eng = Engine(model, params, slots=2, max_len=64, cache_layout="paged",
                 page_size=8, metrics=reg, trace=tracer,
                 on_step=lambda e: probes.append(e.health().counters))
    rng = np.random.default_rng(9)
    for i in range(2):   # fill every slot; queue empty => no admissions
        eng.submit(Request(uid=i, prompt=rng.integers(0, VOCAB, size=6)
                           .astype(np.int32), max_new=40))
    eng.step()           # admissions + first decode (compiles)
    eng.step()           # warm steady state
    calls = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real_get(x))
    with jax.transfer_guard("disallow"):
        n = eng.step()
    assert n == 2
    assert len(calls) == 1, f"expected 1 bulk transfer, saw {len(calls)}"
    recs = STEP_LOG.last("engine", eng.steps)
    assert probes and [r.index for r in recs] == list(range(1, eng.steps + 1))
    assert all(r.spans["engine.decode"] > 0 and "engine.host_sync" in r.spans
               for r in recs)
    assert "engine.prefill" in recs[0].spans


def _tiny_trainer(tmp_path, reg):
    cfg = ModelConfig(
        name="tiny", family="dense", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=64, dtype="float32",
    )
    tc = TrainConfig(
        global_batch=8, seq_len=32, total_steps=9, log_every=3,
        warmup_steps=2, decay_steps=2, learning_rate=1e-3,
    )
    ds, _ = build_synthetic_protein_memmap(str(tmp_path / "prot"), n=200,
                                           seed=0)
    tr = Trainer(build_model(cfg), tc, verbose=False, metrics=reg)
    tr.prepare(CLMBatches(ds, 8, 32, seed=0))
    return tr, tc


def test_instrumented_trainer_still_one_transfer_per_interval(
        tmp_path, monkeypatch):
    reg = MetricsRegistry()
    tr, tc = _tiny_trainer(tmp_path, reg)
    tr.step()  # s=0: compile + first log flush, outside the guard
    calls = []
    real_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: calls.append(1) or real_get(x)
    )
    with jax.transfer_guard("disallow"):
        while tr.step_idx < tc.total_steps:
            tr.step()
    # steps 1..8 under the guard flush at s=3, s=6, s=8 — identical to
    # the uninstrumented contract in test_trainer_distributed.py
    assert len(calls) == 3, f"expected 3 bulk transfers, saw {len(calls)}"
    assert reg.get("train_steps_total").value == 9
    # one observe per flush: s=0 (pre-guard), s=3, s=6, s=8
    assert reg.get("train_step_time_seconds").count == 4
    assert reg.get("train_tokens_total").value == 9 * 8 * 31
    assert reg.get("train_loss").value > 0
    recs = STEP_LOG.last("train", 9)
    assert [r.index for r in recs] == list(range(9))
    assert all(r.spans["train.dispatch"] > 0 and "train.data" in r.spans
               for r in recs)
    assert [r.index for r in recs if "train.flush" in r.spans] == [0, 3, 6, 8]
    assert [r.counters.get("compiles", 0) for r in recs] == [1] + [0] * 8
    assert "train.compile" in recs[0].spans
    assert len({r.program for r in recs}) == 1
    # the registry's data wait and compiles come from those records
    assert reg.get("train_compiles_total").value == 1
    assert reg.get("train_data_wait_seconds_total").value == pytest.approx(
        sum(r.spans["train.data"] for r in recs))


# ------------------------------------------------- Completion timing facts
def test_completion_ttft_none_on_queued_timeout():
    """"No first token" must surface as ttft_s=None (and queue_wait_s=None
    for a request that never reached a slot) — not as a fake 0.0 that an
    SLO average would happily swallow."""
    from repro.serving.api import LLM

    model, params = build()
    llm = LLM(model, params, slots=1, max_len=64)
    # AutoClock: every read advances 50ms, so the queued request's 200ms
    # deadline expires deterministically while slot 0 grinds through 30
    # tokens — no wall-clock dependence
    llm.engine._clock = AutoClock(dt=0.05)
    outs = llm.generate(
        prompts_for(2, seed=4),
        [SamplingParams(max_new=30), SamplingParams(max_new=4,
                                                    deadline_ms=200)],
    )
    served, expired = outs
    assert served.finish_reason == "length"
    assert served.ttft_s is not None and served.ttft_s > 0
    assert served.queue_wait_s is not None and served.queue_wait_s >= 0
    assert expired.finish_reason == "timeout" and expired.tokens == []
    assert expired.ttft_s is None
    assert expired.queue_wait_s is None


# ----------------------------------------------------------- profiling
def test_step_timer_accumulates_per_phase():
    """The step log's spans accumulate per phase over steps (what the
    step timer did), and the summary averages over the steps that ran
    each span."""
    clock = FakeClock(0.0)
    log = StepLog(clock=clock)
    for i, dt in enumerate((1.0, 3.0)):
        with log.step("engine", i):
            with log.span("decode"):
                clock.advance(dt)
    with log.step("engine", 2):
        with log.span("host_sync"):
            clock.advance(0.5)
    s = log.summary()
    assert s["decode"] == {"count": 2, "total_s": 4.0, "mean_s": 2.0}
    assert s["host_sync"]["count"] == 1
    assert "missing" not in s
    assert "decode: n=2 mean=2000.000ms" in log.report()
    assert log.summary("train") == {}


def test_profile_hooks_are_noops_when_disabled(tmp_path):
    with trace_ctx(""):          # falsy dir: plain passthrough
        pass
    with trace_ctx(None):
        pass
    # a span outside any step only annotates: it must survive on a
    # CPU-only wheel and record nothing
    n = len(STEP_LOG.records())
    with span("engine.decode"):
        pass
    assert len(STEP_LOG.records()) == n
    with trace_ctx(str(tmp_path / "prof")):
        with STEP_LOG.step("test", 0), span("test.work"):
            jax.block_until_ready(jax.numpy.ones(4) * 2)


def test_step_log_records_nesting_and_counters():
    """Fake clock: a record holds its wall start and end, each span's
    seconds (a nested span counted in both), repeated spans summed, and
    counters; spans and counters go to the innermost open step."""
    clock = FakeClock(10.0)
    log = StepLog(clock=clock)
    with log.step("train", 7) as rec:
        assert log.last("train", 1) == []       # still open
        with log.span("train.flush"):
            clock.advance(1.0)
            with log.span("train.inner"):
                clock.advance(0.25)
        with log.span("train.flush"):
            clock.advance(0.5)
        log.count("compiles")
        log.count("compiles", 2)
        with log.step("engine", 0) as inner:
            log.count("x")
            with log.span("engine.decode"):
                clock.advance(2.0)
        clock.advance(0.125)
    assert (rec.kind, rec.index, rec.start, rec.end) == ("train", 7, 10.0, 13.875)
    assert rec.spans == {"train.flush": 1.75, "train.inner": 0.25}
    assert rec.counters == {"compiles": 3}
    assert inner.counters == {"x": 1} and inner.spans == {"engine.decode": 2.0}
    assert log.last("train", 5) == [rec]
    assert log.records() == [rec, inner]
    log.count("outside")                        # no open step: dropped
    assert rec.counters == {"compiles": 3}


def test_step_log_ring_is_bounded():
    log = StepLog(capacity=3, programs=2, clock=FakeClock())
    for i in range(5):
        with log.step("train", i):
            pass
    assert [r.index for r in log.records()] == [2, 3, 4]
    assert [r.index for r in log.last("train", 2)] == [3, 4]
    assert [r.index for r in log.last("train", 9)] == [2, 3, 4]
    assert log.last("train", 0) == []
    for k in "abc":
        log.add_program(k, lambda k=k: {"i": (k, "fwd")})
    assert log.scopes("a") == {}                # beyond the bound
    assert log.scopes("c") == {"i": ("c", "fwd")}


def test_scope_map_is_built_lazily_on_a_tiny_model(tmp_path, monkeypatch):
    """The trainer registers each compiled step program; its scope map is
    read from the compiled HLO only at the first read, names every layer
    scope, and tells the passes apart (the tiny model's blocks are
    rematerialised: each unit body is a ``jax.checkpoint``)."""
    import repro.training.loop as loop

    texts = []
    monkeypatch.setattr(loop, "scope_map",
                        lambda t: texts.append(1) or scope_map(t))
    tr, _ = _tiny_trainer(tmp_path, None)
    tr.step()
    tr.step()
    key = STEP_LOG.last("train", 1)[0].program
    assert texts == []                          # nothing read yet
    m = STEP_LOG.scopes(key)
    assert STEP_LOG.scopes(key) is m and texts == [1]  # built once
    got = set(m.values())
    for scope in SCOPES:
        assert any(s == scope for s, _ in got), scope
    for pair in [("attention", "fwd"), ("attention", "bwd"),
                 ("attention", "remat"), ("ffn", "remat"), ("head", "fwd"),
                 ("head", "bwd"), ("optimizer", "step")]:
        assert pair in got, pair
    # the join with a trace: an instruction printed as the profiler does
    name = next(k for k, v in m.items() if v == ("optimizer", "step"))
    recs = STEP_LOG.last("train", 2)
    by = STEP_LOG.device_seconds(
        {f"%{name} = f32[4]{{0}} fusion(f32[4]{{0}} %p)": 1.5,
         "%not.an.instruction = f32[] add()": 0.5}, recs)
    assert by == {("optimizer", "step"): 1.5, (None, None): 0.5}
    assert StepLog().device_seconds({"x": 1.0}, recs) == {}


def test_scope_map_classifies_op_names():
    text = "\n".join([
        'ENTRY %main {',
        '  %a.1 = f32[] add(), metadata={op_name="jit(f)/jvp()/while/body/'
        'closed_call/attention/dot_general" stack_frame_id=1}',
        '  %b.2 = f32[] mul(), metadata={op_name="jit(f)/transpose(jvp())/'
        'while/body/closed_call/checkpoint/rematted_computation/ffn/mul"}',
        '  ROOT %c.3 = f32[] mul(), metadata={op_name="jit(f)/'
        'transpose(jvp(head))/cross_entropy_dw/pallas_call"}',
        '  %d.4 = f32[] sub(), metadata={op_name="jit(f)/optimizer/sub"}',
        '  %e.5 = f32[] sub(), metadata={}',
        '  %f.6 = f32[] sub(), metadata={op_name="jit(f)/jvp()/convert"}',
        '}',
    ])
    assert scope_map(text) == {
        "a.1": ("attention", "fwd"), "b.2": ("ffn", "remat"),
        "c.3": ("head", "bwd"), "d.4": ("optimizer", "step"),
        "f.6": (None, "fwd"),
    }


def test_trace_ctx_raises_when_trace_cannot_start(tmp_path):
    """A profile that was asked for and cannot start fails the run
    instead of exiting 0 without a trace (here: one already running)."""
    with trace_ctx(str(tmp_path / "outer")):
        with pytest.raises(RuntimeError):
            with trace_ctx(str(tmp_path / "inner")):
                pass
