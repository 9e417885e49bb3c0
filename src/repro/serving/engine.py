"""Continuous-batching serving engine (slot-based, iteration-level).

BioNeMo's serving story (NIM) is request-level batching; this engine
implements the standard slot scheduler on top of the framework's
per-slot-position decode path:

  * a fixed pool of B slots shares one preallocated KV cache
    (``Model.init_cache`` with a (B,) position vector);
  * an admitted request is prefilled alone (batch-1) and its cache is
    inserted into its slot — decoding of other slots is never paused for
    padding;
  * every engine step decodes ALL active slots in lockstep hardware-wise
    but with independent positions; finished slots (eos / max tokens) are
    released and refilled from the queue immediately.

Two cache layouts:

``cache_layout="dense"``
    One (B, max_len) KV buffer per layer; the per-slot decode write is a
    masked O(B·max_len) select.  Simple, always available.

``cache_layout="paged"`` — the production path
    Fixed-size pages of a shared pool, mapped per slot by a block table
    (``paged_cache.PageAllocator``).  Admission reserves the request's
    full budget (prompt + max_new) — capacity-aware: a request that does
    not fit waits in the queue, one that can never fit is rejected at
    submit.  Release returns pages to the free list for immediate reuse.
    The decode write is an O(B·page) Pallas scatter and attention reads
    K/V through the block table (``kernels/paged_attention.py``).

Prefix caching + chunked prefill (paged layout only):

``prefix_cache=True``
    Admission hashes the prompt's full blocks against the allocator's
    content-addressed page index.  Hash-hit blocks are *shared* — their
    pages are mapped into the new slot (refcounted) and prefill skips
    them entirely, running only over the suffix.  After a prompt
    finishes prefilling, its full blocks are registered for future
    sharing; a shared page is never written (copy-on-write privatizes
    the final page when a fully-cached prompt recomputes its last token
    for logits).

``prefill_chunk=N``
    Prompts prefill in bounded chunks of at most N tokens, one chunk per
    engine step, interleaved with decode iterations — a long prompt can
    no longer stall in-flight decodes for its whole length.  ``N=0``
    with ``prefix_cache=True`` prefills the (possibly shortened) suffix
    in one chunk.  Mid-prefill slots are invisible to the lockstep
    decode: their block-table rows are masked to the null page in the
    device copy, so concurrent decode writes touch no live data.

Both features need right-paddable causal attention-only stacks (the same
condition as prompt bucketing) and are rejected otherwise.

Prompt bucketing: prompts are right-padded to power-of-2 buckets so the
jitted prefill compiles once per bucket instead of once per unique prompt
length.  Sound only for causal attention-only stacks (pad rows sit in the
future of every real row; SSM state would carry pad garbage), so it is
auto-disabled elsewhere.

Generation API v2 (per-request sampling, on-device selection):

Every request may carry a ``SamplingParams`` (``serving/sampling.py``) —
temperature / top-k / top-p / seed / stop tokens / stop sequences /
logprobs — and the numeric fields live on device as per-slot vectors.
Token *selection* happens inside the jitted decode step
(``ops.sample_tokens``: fused per-slot filter + categorical, greedy rows
degrade to argmax), so the steady-state decode loop is token-in /
token-out: the previous step's sampled tokens feed the next step without
ever visiting the host, and the only host traffic per step is ONE bulk
``jax.device_get`` of the sampled (tokens, logprobs, fault flags) triple
for bookkeeping and stop checks.  A request without params decodes
greedily with its legacy ``max_new``/``eos_id`` fields — old
``Engine(...)`` call sites keep working unchanged;
``serving/api.py::LLM`` is the v2 facade.

Fault tolerance (the request-lifecycle hardening pass):

  * **Bounded backpressure** — ``max_queue=N`` caps the admission queue;
    ``submit`` raises the typed, retriable :class:`EngineOverloaded`
    instead of growing the queue without bound (overload then costs the
    caller a rejection, not every caller an unbounded TTFT).
  * **Deadlines** — a request carrying ``deadline_ms`` (on its
    ``SamplingParams`` or directly on the ``Request``) times out as a
    wall-clock SLO from submit: expired *queued* requests finish with
    ``finish_reason="timeout"`` without running; expired *in-flight*
    requests are released at the next step boundary with whatever
    tokens they produced.  ``clock`` is injectable for deterministic
    tests.
  * **Preempt-and-requeue** (``preempt=True``, paged layout) — when the
    queue head is blocked on page pressure, the engine evicts the
    most-recently-admitted in-flight decode instead of head-of-line
    blocking: the victim's exclusive pages free (prefix-registered ones
    park in the evictable set), the request re-queues right behind the
    blocked head, and on re-admission it *replays* via prefill over
    prompt + generated-so-far.  Its generation index is the resume
    cursor — the counter-hash sampling PRNG (keyed on request seed +
    generation index, PR 4) makes the resumed request token-identical
    to an unpreempted run.  Each request is preempted at most once and
    only requests that were never preempted trigger or suffer
    preemption, so the cycle cannot livelock.
  * **Fault isolation** — a non-finite sentinel inside the jitted step
    (and the admission first-token path) quarantines only the offending
    slot with ``finish_reason="error"``; every other slot's sampled
    token is provably untouched (the sentinel also sanitizes the bad
    row before it reaches the fused sampler, so a NaN in one slot's
    logits can never poison a batch-wide reduction).
  * **Observability** — :meth:`Engine.health` snapshots queue depth,
    slot occupancy, free pages, a steps-since-progress watchdog counter
    and the lifecycle counters; ``serving/faults.py`` injects
    deterministic fault schedules (NaN logits, allocator outages,
    crash-and-rebuild) through the ``faults=FaultPlan(...)`` hook.

Unified telemetry (``repro.obs``): pass ``metrics=MetricsRegistry()``
and every lifecycle counter, the watchdog, queue/slot/page gauges and
the TTFT / ITL / queue-wait / e2e-latency histograms become
registry-backed (``health()`` counters and the registry agree by
construction — both go through :meth:`_bump`); pass
``trace=TraceRecorder()`` and every lifecycle transition emits one
structured event stamped by the engine's injectable clock, so a seeded
fault run yields a byte-identical JSONL trace.  Both hooks are
host-side appends on paths the engine already walks: the
one-bulk-transfer-per-step contract is unchanged (transfer-guard
asserted in ``tests/test_obs.py``) and the tokens are the same as with
both off (``tests/test_serving_engine.py``).  Every ``step()`` is a
record of the step log (``repro.obs.STEP_LOG``, kind ``"engine"``) with
host spans ``engine.prefill`` (each prompt's prefill dispatch),
``engine.decode`` (the fused decode dispatch) and ``engine.host_sync``
(its one bulk ``device_get``), always on and host-side only;
``on_step`` is a per-step callback the launchers use for periodic
health/exposition emission.

Sharded serving (tensor-parallel inference on the mesh):

A model built with a multi-device mesh (``build_model(cfg, pc, mesh)``,
``serve.py --mesh DxM``) makes the whole engine mesh-aware with no API
change: the paged K/V pools (and dense K/V buffers) shard over the
head/``model`` axis per :meth:`Model.cache_specs` while the host-side
page allocator, refcounts and prefix-hash index stay global — one
logical cache, sharded storage, so a page id means the same thing on
every device and prefix sharing / COW semantics are mesh-invariant.
Every jit below pins ``in_shardings``/``out_shardings`` to the canonical
placement with donation intact, so the steady-state decode loop updates
the sharded pools in place and keeps the one-bulk-transfer-per-step
contract (re-asserted on the mesh in tests/test_serving_sharded.py).
The fused sampler and the NaN sentinel consume the *replicated* logits
row, so a request's token stream depends only on its seed + generation
index: greedy and seeded-sampled outputs are token-identical across
(1,), (1,8) and (2,4) meshes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.models.model import Model
from repro.parallel.sharding import fit_spec
from repro.kernels import ops
from repro.serving.paged_cache import (
    NULL_PAGE,
    PageAllocator,
    copy_pages,
    pages_for,
    write_slot_paged,
)
from repro.obs.metrics import LATENCY_BUCKETS, MetricsRegistry
from repro.obs.profile import STEP_LOG, span
from repro.obs.trace import TraceRecorder
from repro.serving.sampling import SamplingParams, StopChecker, effective_params


class EngineOverloaded(RuntimeError):
    """Typed admission rejection: the bounded queue is full.

    Raised by :meth:`Engine.submit` when ``max_queue`` is reached.  It is
    *retriable* by contract — the request was not mutated or partially
    admitted, and the caller may resubmit once :meth:`Engine.health`
    shows the queue draining (the serving analogue of HTTP 429/503)."""

    retriable = True

    def __init__(self, uid: int, depth: int, max_queue: int):
        super().__init__(
            f"request {uid}: admission queue full ({depth}/{max_queue}); "
            f"retry after the queue drains"
        )
        self.queue_depth = depth
        self.max_queue = max_queue


@dataclasses.dataclass
class EngineHealth:
    """One consistent snapshot of engine liveness (``Engine.health()``).

    ``steps_since_progress`` is the watchdog: engine steps since any
    request was admitted, advanced a prefill chunk, emitted a token, or
    finished.  A serving loop that sees it grow while ``queue_depth > 0``
    is wedged (e.g. a permanent allocator outage) and should alert or
    recycle the engine."""

    queue_depth: int
    slots: int
    active_slots: int
    prefilling: int
    free_pages: Optional[int]       # None for the dense layout
    total_pages: Optional[int]
    steps: int
    steps_since_progress: int
    counters: Dict[str, int]


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (S,) int32
    max_new: int = 32
    eos_id: int = -1             # -1: never stops early
    # v2 sampling intent; None = legacy greedy decode with max_new/eos_id.
    # When set, a non-None params.max_new takes precedence (normalized at
    # submit; params.max_new=None inherits the field above) and
    # eos_id >= 0 folds into the stop-token set.
    params: Optional[SamplingParams] = None
    # wall-clock SLO from submit, in ms (params.deadline_ms wins when
    # set; None = no deadline)
    deadline_ms: Optional[float] = None
    # filled by the engine:
    output: Optional[List[int]] = None
    logprobs: Optional[List[float]] = None   # per-token, if params.logprobs
    # "stop" | "length" | "timeout" | "error" | "cancelled" once done
    finish_reason: str = ""
    preempted: int = 0                       # times evicted-and-requeued
    t_submit: float = 0.0
    t_admit: float = 0.0         # first admission to a slot (0 = never ran)
    t_first: float = 0.0
    t_done: float = 0.0
    _seq: int = -1                           # submit order (engine-assigned)


@dataclasses.dataclass
class _Prefill:
    """A slot mid-way through an incremental (chunked/suffix) prefill."""

    req: Request
    prompt: np.ndarray           # original, unpadded prompt (+ replayed
                                 # generated tokens for a resumed request)
    done: int                    # tokens whose KV is already in the pages


class Engine:
    def __init__(self, model: Model, params, *, slots: int, max_len: int,
                 extra_batch: Optional[Dict[str, Any]] = None,
                 cache_layout: str = "dense", page_size: int = 16,
                 num_pages: int = 0, bucket_prompts: Optional[bool] = None,
                 prefix_cache: bool = False, prefill_chunk: int = 0,
                 max_queue: int = 0, preempt: bool = False,
                 faults: Optional[Any] = None,
                 clock: Callable[[], float] = time.time,
                 metrics: Optional[MetricsRegistry] = None,
                 trace: Optional[TraceRecorder] = None,
                 on_step: Optional[Callable[["Engine"], None]] = None):
        self.model = model
        self.params = params
        self.B = slots
        self.max_len = max_len
        self.extra = extra_batch or {}
        cfg = model.cfg
        self.layout = cache_layout
        # frontend rows are prepended only when the batch actually carries
        # img_embeds (_decoder_input); a vision model served text-only has
        # no frontend rows in its prefill
        self.n_front = (
            cfg.num_frontend_tokens
            if cfg.frontend == "vision_stub" and "img_embeds" in self.extra
            else 0
        )
        cross = cfg.num_frontend_tokens if cfg.is_encoder_decoder else 0

        # right-padding (prompt buckets, chunk buckets, prefix skips) is
        # only sound when pad rows stay in every real row's future: causal
        # attention, no SSM state carry, no rolling (sliding-window) cache
        has_ssm = any(not cfg.is_attn_layer(i) for i in range(cfg.num_layers))
        paddable = cfg.causal and not has_ssm and not cfg.sliding_window

        self.prefix_cache = prefix_cache
        self.prefill_chunk = prefill_chunk
        self._incremental = prefix_cache or prefill_chunk > 0
        if self._incremental:
            if cache_layout != "paged":
                raise ValueError(
                    "prefix_cache / prefill_chunk require cache_layout='paged'"
                )
            if not paddable or cfg.is_encoder_decoder or self.n_front:
                raise ValueError(
                    "prefix_cache / prefill_chunk require a causal "
                    "attention-only decoder with no frontend rows"
                )
        self.max_queue = int(max_queue)
        self.preempt = bool(preempt)
        if self.preempt and cache_layout != "paged":
            raise ValueError(
                "preempt=True requires cache_layout='paged' — preemption "
                "frees page-pool pressure, which the dense layout has none of"
            )
        self.faults = faults
        self._clock = clock

        if cache_layout == "paged":
            # default pool: every slot can hold a full max_len sequence,
            # +1 for the reserved null page — admission then only queues
            # on slot pressure, like the dense layout.
            pages_per_seq = pages_for(max_len, page_size)
            num_pages = num_pages or 1 + slots * pages_per_seq
            self.alloc = PageAllocator(
                num_pages, page_size, slots, max_len,
                prefix_cache=prefix_cache,
            )
            cache = model.init_cache(
                slots, max_len, cross_len=cross,
                layout="paged", page_size=page_size, num_pages=num_pages,
            )
        elif cache_layout == "dense":
            self.alloc = None
            cache = model.init_cache(slots, max_len, cross_len=cross)
        else:
            raise ValueError(f"unknown cache_layout {cache_layout!r}")
        cache["pos"] = jnp.zeros((slots,), jnp.int32)

        # ---- tensor-parallel serving: canonical placement on the mesh.
        # When the model carries a real multi-device mesh (build_model with
        # --mesh), the K/V storage shards over the head/model axis per
        # Model.cache_specs — one logical cache, sharded storage; the page
        # allocator, refcounts and prefix-hash index below stay host-global
        # and never learn about the mesh.  Params shard per param_specs
        # (fitted: axes that don't divide a dim degrade to replication) and
        # every per-slot control vector is replicated.  Off-mesh, placement
        # stays implicit and the jits below compile exactly as before.
        mesh = model.ctx.mesh
        self.mesh = (
            mesh if mesh is not None and not mesh.empty and mesh.size > 1
            else None
        )
        if self.mesh is not None:
            self._rep = NamedSharding(self.mesh, PartitionSpec())
            self._sh_cache = model.cache_shardings(cache)
            self._sh_params = jax.tree.map(
                lambda p, s: NamedSharding(
                    self.mesh, fit_spec(p.shape, self.mesh, s)
                ),
                params, model.param_specs(),
            )
            cache = jax.device_put(cache, self._sh_cache)
            self.params = jax.device_put(params, self._sh_params)
        else:
            self._rep = self._sh_cache = self._sh_params = None
        self.cache = cache
        self.slot_req: List[Optional[Request]] = [None] * slots
        self.slot_left: np.ndarray = np.zeros((slots,), np.int32)
        self.slot_deadline: List[Optional[float]] = [None] * slots
        self.queue: List[Request] = []
        self.done: List[Request] = []
        # slots mid-prefill, in admission order (FIFO chunk scheduling)
        self._prefilling: List[int] = []
        self._prefill_state: Dict[int, _Prefill] = {}

        # lifecycle bookkeeping: submit order (preemption victims must be
        # younger than nobody they displace from the queue), admission
        # recency (the preemption victim is the NEWEST in-flight decode),
        # and the health counters + watchdog.
        self._next_seq = 0
        self._admit_counter = 0
        self._admit_order: List[int] = [-1] * slots
        self.steps = 0
        self._steps_since_progress = 0
        self._progress = False
        self.counters: Dict[str, int] = {
            "submitted": 0, "completed": 0, "rejected": 0, "timeouts": 0,
            "errors": 0, "cancelled": 0, "preempted": 0, "resumed": 0,
        }

        # unified telemetry (repro.obs): every counter bump goes through
        # _bump so the registry and health() can never disagree; the
        # latency histograms observe host floats the engine already
        # computes, and the lifecycle tracer is stamped by self._clock —
        # all host-side appends, nothing touches the device hot loop.
        self.metrics = metrics
        self.trace = trace
        self.on_step = on_step
        if metrics is not None:
            fam = metrics.counter(
                "engine_requests_total",
                "request lifecycle transitions by event", labels=("event",),
            )
            self._mc = {k: fam.labels(k) for k in self.counters}
            self._g = {
                name: metrics.gauge(f"engine_{name}", help)
                for name, help in (
                    ("queue_depth", "requests waiting for admission"),
                    ("active_slots", "slots holding an in-flight request"),
                    ("prefilling", "slots mid incremental prefill"),
                    ("free_pages", "KV pool pages on the free list"),
                    ("steps_since_progress",
                     "watchdog: engine steps since any request advanced"),
                )
            }
            self._c_steps = metrics.counter(
                "engine_steps_total", "engine scheduler iterations"
            )
            self._c_toks = metrics.counter(
                "engine_tokens_total", "generated tokens across all requests"
            )
            self._h_ttft = metrics.histogram(
                "engine_ttft_seconds", "submit -> first token",
                buckets=LATENCY_BUCKETS,
            )
            self._h_itl = metrics.histogram(
                "engine_itl_seconds", "per-request mean inter-token latency",
                buckets=LATENCY_BUCKETS,
            )
            self._h_queue = metrics.histogram(
                "engine_queue_wait_seconds", "submit -> slot admission",
                buckets=LATENCY_BUCKETS,
            )
            self._h_e2e = metrics.histogram(
                "engine_e2e_latency_seconds", "submit -> finish",
                buckets=LATENCY_BUCKETS,
            )
        else:
            self._mc = None

        # per-slot sampling state.  The numeric params live on DEVICE
        # ((B,) vectors consumed by the fused sampler inside the jitted
        # decode step); the stop machinery is host-side per slot.
        # ``gen`` is each slot's generation index (tokens emitted so
        # far) — it keys the counter-based PRNG stream, so a fixed-seed
        # request reproduces its tokens in any batch composition.
        self.slot_sp: List[Optional[SamplingParams]] = [None] * slots
        self.slot_stop: List[Optional[StopChecker]] = [None] * slots
        self._samp: Dict[str, jax.Array] = {
            "temp": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.zeros((slots,), jnp.int32),
            "top_p": jnp.ones((slots,), jnp.float32),
            "seed": jnp.zeros((slots,), jnp.uint32),
            "gen": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
        }
        # token-in/token-out: the last sampled token per slot stays on
        # device and feeds the next decode step directly
        self._last_tok = jnp.zeros((slots,), jnp.int32)
        # steady-state fault-injection vector (all clear) kept on device:
        # passing it adds no host->device traffic to the decode step
        self._no_inject = jnp.zeros((slots,), bool)
        if self.mesh is not None:
            # commit the control vectors replicated so the pinned jits
            # below accept them without a placement mismatch
            self._samp = jax.device_put(self._samp, self._rep)
            self._last_tok = jax.device_put(self._last_tok, self._rep)
            self._no_inject = jax.device_put(self._no_inject, self._rep)

        if bucket_prompts is None:
            bucket_prompts = paddable
        self.bucket_prompts = bucket_prompts

        impl = cfg.kernel_impl

        def _fused_step(params, cache, tok, samp, inject):
            """One decode iteration with ON-DEVICE token selection.

            Everything the old loop did on the host — argmax, idle-slot
            pos reset, next-token feedback — happens inside this one
            jitted call: the engine only transfers the sampled (tok,
            logp, bad) triple back, once, per step.  ``inject`` is the
            fault layer's NaN vector (all-False in steady state); the
            non-finite sentinel quarantines a poisoned slot's row —
            whether injected or organic — BEFORE it reaches the fused
            sampler, so one slot's NaN can never leak into another
            slot's token."""
            logits, cache = model.decode_step(params, cache, tok[:, None])
            # idle / mid-prefill slots stepped in lockstep: reset their
            # positions (their writes touched no live data)
            cache["pos"] = jnp.where(samp["active"], cache["pos"], 0)
            row = logits[:, -1]
            # sampler + sentinel consume the REPLICATED row: the head
            # matmul may leave logits vocab-sharded on a mesh, and both
            # the counter-hash PRNG draw and the isfinite reduction must
            # see identical full rows on every device for a request's
            # token stream to be independent of the mesh shape (off-mesh
            # this constraint is a no-op)
            row = model.ctx.cons(row, None, None)
            row = jnp.where(inject[:, None], jnp.float32(jnp.nan), row)
            bad = samp["active"] & ~jnp.all(jnp.isfinite(row), axis=-1)
            row = jnp.where(bad[:, None], 0.0, row)
            # idle slots read as greedy (temp 0) no matter what request
            # last held them — otherwise one retired sampled request
            # would defeat the sampler's all-greedy fast path for every
            # later greedy-only step
            nxt, logp = ops.sample_tokens(
                row,
                jnp.where(samp["active"], samp["temp"], 0.0),
                samp["top_k"], samp["top_p"],
                samp["seed"], samp["gen"], impl=impl,
            )
            nxt = jnp.where(samp["active"], nxt, 0)
            samp = dict(samp, gen=samp["gen"] + samp["active"].astype(jnp.int32))
            return nxt, logp, bad, cache, samp

        def _admit_slot(samp, last_tok, logits, slot, temp, k, p, seed,
                        gen0, inject):
            """Sample a request's NEXT token from its prefill logits and
            bind every per-slot device field in one jitted call —
            admission costs one dispatch + one device_get instead of a
            string of eager .at[].set updates (which showed up directly
            in shared-prefix TTFT).  ``gen0`` is the generation index to
            sample at: 0 for a fresh prompt, the number of already-
            emitted tokens for a preempted request replaying its
            prompt+output (same counter-hash stream => same tokens as an
            unpreempted run).  The same non-finite sentinel as the
            decode step guards the prefill logits."""
            row = logits[:, -1]
            # same replication guarantee as the decode step: first-token
            # sampling must be mesh-shape-independent too
            row = model.ctx.cons(row, None, None)
            row = jnp.where(inject, jnp.float32(jnp.nan), row)
            bad = ~jnp.all(jnp.isfinite(row))
            row = jnp.where(bad, 0.0, row)
            tok, logp = ops.sample_tokens(
                row, temp[None], k[None], p[None], seed[None],
                gen0[None].astype(jnp.uint32), impl=impl,
            )
            samp = dict(
                samp,
                temp=samp["temp"].at[slot].set(temp),
                top_k=samp["top_k"].at[slot].set(k),
                top_p=samp["top_p"].at[slot].set(p),
                seed=samp["seed"].at[slot].set(seed),
                gen=samp["gen"].at[slot].set((gen0 + 1).astype(jnp.int32)),
                active=samp["active"].at[slot].set(True),
            )
            return tok, logp, bad, samp, last_tok.at[slot].set(tok[0])

        def _release_slot(samp, pos, slot):
            """Deactivate a finished slot and reset its pos (one call)."""
            return (
                dict(samp, active=samp["active"].at[slot].set(False)),
                pos.at[slot].set(0),
            )

        # the engine cache is serving steady state: donate it so XLA
        # updates pools/buffers in place instead of copying the whole
        # cache every decode step / prefill chunk / page insert (each
        # call consumes self.cache[...] and the engine reassigns it)
        if self.mesh is None:
            self._prefill = jax.jit(
                lambda p, b, L: model.prefill(p, b, max_len, length=L)
            )
            self._decode = jax.jit(_fused_step, donate_argnums=(1, 3))
            self._admit_slot = jax.jit(_admit_slot, donate_argnums=(0, 1))
            self._release_slot = jax.jit(
                _release_slot, donate_argnums=(0, 1)
            )
            self._insert_paged = jax.jit(
                write_slot_paged, donate_argnums=(0,)
            )
            self._chunk = jax.jit(model.prefill_chunk, donate_argnums=(1,))
            self._copy = jax.jit(copy_pages, donate_argnums=(0,))
            self._embed_fn = jax.jit(model.embed_pool)
        else:
            # mesh-aware jits: every dispatch pins its in/out shardings to
            # the canonical placement (params per param_specs, cache per
            # cache_specs, control state replicated).  jax rejects a
            # committed arg whose sharding mismatches an explicit pin, so
            # the pins PROVE the steady-state decode loop moves no data:
            # every input already lives where the pin says, every output
            # is produced there (donated sharded buffers update in
            # place), and the only host traffic stays the one bulk
            # device_get of the sampled (tok, logp, bad) triple.  The
            # batch-1 prefill tree is replicated: it is O(max_len) small,
            # and its slot insert then writes each pool shard locally.
            rep = self._rep
            csh, psh = self._sh_cache, self._sh_params
            lsh = csh["layers"]
            ssh = {k: rep for k in self._samp}
            self._prefill = jax.jit(
                lambda p, b, L: model.prefill(p, b, max_len, length=L),
                in_shardings=(psh, rep, rep), out_shardings=rep,
            )
            self._decode = jax.jit(
                _fused_step, donate_argnums=(1, 3),
                in_shardings=(psh, csh, rep, ssh, rep),
                out_shardings=(rep, rep, rep, csh, ssh),
            )
            self._admit_slot = jax.jit(
                _admit_slot, donate_argnums=(0, 1),
                in_shardings=(ssh,) + (rep,) * 9,
                out_shardings=(rep, rep, rep, ssh, rep),
            )
            self._release_slot = jax.jit(
                _release_slot, donate_argnums=(0, 1),
                in_shardings=(ssh, rep, rep), out_shardings=(ssh, rep),
            )
            self._insert_paged = jax.jit(
                write_slot_paged, donate_argnums=(0,),
                in_shardings=(lsh, rep, rep, rep), out_shardings=lsh,
            )
            self._chunk = jax.jit(
                model.prefill_chunk, donate_argnums=(1,),
                in_shardings=(psh, lsh) + (rep,) * 4,
                out_shardings=(rep, lsh),
            )
            self._copy = jax.jit(
                copy_pages, donate_argnums=(0,),
                in_shardings=(lsh, rep, rep), out_shardings=lsh,
            )
            # embedding extraction: batch replicated in (it is O(B·S)
            # small), params per param_specs, pooled (B, d) out replicated
            self._embed_fn = jax.jit(
                model.embed_pool,
                in_shardings=(psh, rep, rep), out_shardings=rep,
            )

    # ---------------------------------------------------------- telemetry
    def _bump(self, name: str, n: int = 1) -> None:
        """Advance a lifecycle counter in BOTH the health() dict and the
        metrics registry — one call site per transition, so the two views
        cannot drift (parity asserted across chaos plans in
        tests/test_obs.py)."""
        self.counters[name] += n
        if self._mc is not None:
            self._mc[name].inc(n)

    def _emit(self, event: str, req: Optional[Request] = None,
              ts: Optional[float] = None, **data) -> None:
        """Record one lifecycle trace event, stamped by the engine's
        injectable clock (deterministic under a fake clock)."""
        if self.trace is None:
            return
        self.trace.emit(
            event,
            ts=self._clock() if ts is None else ts,
            uid=req.uid if req is not None else -1,
            step=self.steps,
            **data,
        )

    def _observe_gauges(self) -> None:
        g = self._g
        g["queue_depth"].set(len(self.queue))
        g["active_slots"].set(sum(r is not None for r in self.slot_req))
        g["prefilling"].set(len(self._prefilling))
        if self.alloc is not None:
            g["free_pages"].set(self.alloc.free_pages)
        g["steps_since_progress"].set(self._steps_since_progress)

    # -------------------------------------------------------------- admin
    def submit(self, req: Request) -> None:
        if req.params is not None and req.params.max_new is not None:
            # v2 requests budget via params; normalize the legacy field so
            # every admission/capacity path sees one source of truth
            # (params.max_new=None inherits the request's own budget)
            req.max_new = req.params.max_new
        if req.params is not None and req.params.deadline_ms is not None:
            req.deadline_ms = req.params.deadline_ms
        if req.max_new < 1:
            raise ValueError(
                f"request {req.uid}: max_new must be >= 1 (got {req.max_new})"
            )
        if len(req.prompt) == 0 and self.n_front == 0:
            raise ValueError(
                f"request {req.uid}: empty prompt — a causal LM has no "
                f"token to condition the first logits on"
            )
        need = len(req.prompt) + self.n_front + req.max_new
        if need > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt+max_new = {need} tokens "
                f"overflows max_len {self.max_len}"
            )
        if self.alloc is not None and not self.alloc.fits_slot(need):
            raise ValueError(
                f"request {req.uid}: {need} tokens can never fit the page "
                f"pool ({self.alloc.num_pages - 1} usable pages of "
                f"{self.alloc.page_size})"
            )
        # bounded backpressure: reject instead of queueing without bound.
        # Validation errors above are NOT rejections (they can never
        # succeed on retry); this one is — the typed exception tells the
        # caller to back off and try again.  Internal re-queues (preempted
        # requests) bypass submit and may transiently exceed the bound.
        if self.max_queue and len(self.queue) >= self.max_queue:
            self._bump("rejected")
            self._emit("overload_reject", req, queue_depth=len(self.queue),
                       max_queue=self.max_queue)
            raise EngineOverloaded(req.uid, len(self.queue), self.max_queue)
        req.t_submit = self._clock()
        req._seq = self._next_seq
        self._next_seq += 1
        self._bump("submitted")
        self.queue.append(req)
        self._emit("submit", req, ts=req.t_submit,
                   prompt_tokens=len(req.prompt), max_new=req.max_new)
        self._emit("queued", req, ts=req.t_submit,
                   queue_depth=len(self.queue))

    # ---------------------------------------------------------- embedding
    def embed(self, prompts: List[List[int]]) -> np.ndarray:
        """Batched embedding extraction: token prompts -> (n, d_model)
        float32 masked-mean-pooled vectors, in input order.

        Prompts group by power-of-2 length bucket and dispatch in rows of
        up to ``slots`` per jitted call — at most O(log max_len) compiled
        shapes, reused across calls.  Every dispatch stays on device; the
        (n, d) result comes back in ONE bulk ``device_get`` at the end.
        Pooling is right-pad safe for every stack this engine serves
        (causal attention/SSM never let pads reach valid rows;
        bidirectional models see pads exactly as during training), so no
        paddable gate applies.  Lifecycle counters/trace use the standard
        vocabulary: each prompt counts submitted+completed, each dispatch
        emits a ``prefill`` event and the call one ``finish``.
        """
        cfg = self.model.cfg
        if cfg.is_encoder_decoder or self.n_front:
            raise ValueError(
                "embed() supports decoder-only text stacks — encoder-"
                "decoder and vision-frontend models have no single "
                "token-aligned hidden sequence to pool"
            )
        prompts = [np.asarray(p, np.int32) for p in prompts]
        n = len(prompts)
        if n == 0:
            return np.zeros((0, cfg.d_model), np.float32)
        for i, p in enumerate(prompts):
            if p.ndim != 1 or len(p) == 0:
                raise ValueError(f"prompt {i}: empty or non-1-D")
            if len(p) > self.max_len:
                raise ValueError(
                    f"prompt {i}: {len(p)} tokens overflows max_len "
                    f"{self.max_len}"
                )
        self._bump("submitted", n)
        groups: Dict[int, List[int]] = {}
        for i, p in enumerate(prompts):
            b = 8
            while b < len(p):
                b *= 2
            groups.setdefault(max(len(p), min(b, self.max_len)), []).append(i)
        parts = []      # (input positions, device (rows, d) slice)
        t0 = self._clock()
        for L in sorted(groups):
            idxs = groups[L]
            for s in range(0, len(idxs), self.B):
                chunk = idxs[s : s + self.B]
                # pad the row dimension to the full slot count so each
                # bucket compiles exactly one (B, L) shape
                toks = np.zeros((self.B, L), np.int32)
                lens = np.zeros((self.B,), np.int32)
                for r, gi in enumerate(chunk):
                    toks[r, : len(prompts[gi])] = prompts[gi]
                    lens[r] = len(prompts[gi])
                self._emit("prefill", None, embed=True, bucket=L,
                           rows=len(chunk))
                emb = self._embed_fn(
                    self.params,
                    {"tokens": jnp.asarray(toks)},
                    jnp.asarray(lens),
                )
                parts.append((chunk, emb[: len(chunk)]))
        host = jax.device_get([e for _, e in parts])  # ONE bulk transfer
        out = np.zeros((n, host[0].shape[-1]), np.float32)
        for (chunk, _), h in zip(parts, host):
            out[np.asarray(chunk, np.int64)] = h
        self._bump("completed", n)
        self._emit("finish", None, embed=True, embedded=n,
                   wall=self._clock() - t0)
        return out

    def _bucket(self, n: int) -> int:
        """Pad a prompt/chunk length to a power-of-2 bucket (min 8, capped
        at the longest prompt max_len admits) so prefill stops recompiling
        per unique length.  Never returns less than `n`: at the cap
        boundary (prompt exactly at max_len) the old min() could hand back
        a bucket SMALLER than the prompt and silently truncate it."""
        if not self.bucket_prompts:
            return n
        cap = max(self.max_len - self.n_front, 1)
        b = 8
        while b < n:
            b *= 2
        return max(n, min(b, cap))

    def _push_table(self) -> None:
        """Push the block table to the device cache, masking mid-prefill
        slots to the null page: the lockstep decode must neither read nor
        write their half-built pages (their writes land on page 0, which
        belongs to no sequence)."""
        tbl = self.alloc.table
        if self._prefilling:
            tbl = tbl.copy()
            tbl[self._prefilling, :] = NULL_PAGE
        self.cache["block_table"] = jnp.asarray(tbl)
        self._canon()

    def _canon(self) -> None:
        """Re-commit the cache to its canonical shardings after an eager
        (non-jitted) update — the mesh-pinned jits reject committed args
        whose placement drifted.  Identity for already-canonical leaves;
        only admission / release paths ever call it, never the
        steady-state decode loop."""
        if self.mesh is not None:
            self.cache = jax.device_put(self.cache, self._sh_cache)

    def _write_slot(self, slot: int, one_cache, pos: int) -> None:
        """Insert a batch-1 prefilled cache into slot `slot` (dense)."""

        def put(dst, src):
            # stacked leaves: (units, B, ...) — batch axis 1; scalar 'pos'
            # handled separately.
            if dst.ndim == src.ndim and dst.ndim >= 2 and src.shape[1] == 1:
                idx = (0, slot) + (0,) * (dst.ndim - 2)
                return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), idx)
            return dst

        self.cache["layers"] = jax.tree.map(
            put, self.cache["layers"], one_cache["layers"]
        )
        self.cache["pos"] = self.cache["pos"].at[slot].set(pos)
        self._canon()

    def _write_slot_paged(self, slot: int, one_cache, pos: int,
                          pages: np.ndarray, n_tiles: int) -> None:
        """Scatter a batch-1 prefilled cache into `slot`'s pool pages."""
        ids = np.full((n_tiles,), NULL_PAGE, np.int32)
        ids[: min(n_tiles, len(pages))] = pages[:n_tiles]
        self.cache["layers"] = self._insert_paged(
            self.cache["layers"], one_cache["layers"], slot,
            jnp.asarray(ids),
        )
        self._push_table()
        self.cache["pos"] = self.cache["pos"].at[slot].set(pos)
        self._canon()

    # ------------------------------------------------- sampling plumbing
    def _set_slot_params(self, slot: int, req: Request) -> None:
        """Bind a request's sampling intent to its slot (host side: the
        stop machinery, deadline, admission recency).  The device-side
        per-slot vectors are written by ``_emit_first`` in one fused
        call — nothing reads them while the slot is inactive."""
        sp = effective_params(req)
        self.slot_sp[slot] = sp
        self.slot_stop[slot] = StopChecker(sp, req.eos_id)
        self.slot_deadline[slot] = self._abs_deadline(req)
        self._admit_order[slot] = self._admit_counter
        self._admit_counter += 1
        first_admission = req.t_admit == 0.0
        req.t_admit = self._clock()
        if self.metrics is not None and first_admission:
            # queue wait = time to FIRST admission; a preempted request's
            # re-admission is scheduler churn, not queueing delay
            self._h_queue.observe(req.t_admit - req.t_submit)

    def _abs_deadline(self, req: Request) -> Optional[float]:
        if req.deadline_ms is None:
            return None
        return req.t_submit + req.deadline_ms / 1e3

    def _nan_slots(self) -> List[int]:
        if self.faults is None:
            return []
        return [s for s in self.faults.nan_slots(self.steps)
                if 0 <= s < self.B]

    def _emit_first(self, slot: int, logits) -> None:
        """Sample the next generated token from prefill logits (on
        device, at the request's generation index — 0 for a fresh prompt,
        the replay cursor for a resumed one), bind the slot's device-side
        sampling state, record the token, and flip the slot to lockstep
        decoding (or finish immediately on stop/budget/poisoned
        logits)."""
        req = self.slot_req[slot]
        sp = self.slot_sp[slot]
        gen0 = len(req.output) if req.output else 0
        inject = slot in self._nan_slots()
        tok_d, logp_d, bad_d, self._samp, self._last_tok = self._admit_slot(
            self._samp, self._last_tok, logits, np.int32(slot),
            np.float32(sp.temperature), np.int32(sp.top_k),
            np.float32(sp.top_p), np.uint32(sp.seed & 0xFFFFFFFF),
            np.uint32(gen0), np.bool_(inject),
        )
        nxt, lp, bad = jax.device_get((tok_d, logp_d, bad_d))
        if bool(bad):
            # poisoned prefill logits: quarantine this slot only
            req.finish_reason = "error"
            self._emit("quarantine", req, slot=slot, where="prefill")
            self._finish(slot)
            return
        t0 = int(nxt[0])
        if gen0 == 0:
            req.output = [t0]
            req.logprobs = [float(lp[0])] if sp.logprobs else None
            req.t_first = self._clock()
            if self.metrics is not None:
                self._h_ttft.observe(req.t_first - req.t_submit)
            self._emit("decode", req, ts=req.t_first, slot=slot,
                       ttft_s=req.t_first - req.t_submit)
        else:
            # preempted request resuming: the replayed prefill re-derived
            # the logits its next token would have seen, and gen0 keys
            # the same PRNG draw — the token stream continues exactly
            self._bump("resumed")
            self._emit("resume", req, slot=slot, replayed_tokens=gen0)
            req.output.append(t0)
            if req.logprobs is not None:
                req.logprobs.append(float(lp[0]))
        if self.metrics is not None:
            self._c_toks.inc()
        self.slot_left[slot] = req.max_new - len(req.output)
        fin = self.slot_stop[slot].check(req.output, self.slot_left[slot])
        if fin:
            req.finish_reason = fin
            self._finish(slot)

    # ------------------------------------------------------- preemption
    def _replay_prompt(self, req: Request) -> np.ndarray:
        """The token sequence a (possibly preempted) request prefills:
        prompt + generated-so-far.  For a fresh request this is just the
        prompt; for a resumed one the generated tokens become prompt
        rows, so their KV is rebuilt and decoding continues from the
        exact position it was evicted at."""
        if req.output:
            return np.concatenate(
                [req.prompt, np.asarray(req.output, np.int32)]
            )
        return req.prompt

    def _requeue(self, req: Request) -> None:
        """Re-queue a preempted request in submit order among the entries
        BEHIND the blocked head (position 0): the head keeps the front —
        putting the older victim ahead of it would only re-admit the
        victim into the pages it just freed and spin forever."""
        i = len(self.queue)
        for j in range(1, len(self.queue)):
            if self.queue[j]._seq > req._seq:
                i = j
                break
        self.queue.insert(max(i, 1) if self.queue else 0, req)

    def _preempt_slot(self, slot: int) -> None:
        """Evict an in-flight decode: deactivate the slot, release its
        pages (exclusive ones free; prefix-registered ones park in the
        evictable set, still indexed — a resumed replay may hash-hit
        them), and re-queue the request.  No sampling state needs saving:
        the generation index IS the resume cursor, and the counter-hash
        PRNG replays the remaining tokens identically."""
        req = self.slot_req[slot]
        req.preempted += 1
        self._bump("preempted")
        self._emit("preempt", req, slot=slot,
                   generated_tokens=len(req.output or []))
        self.slot_req[slot] = None
        self.slot_left[slot] = 0
        self.slot_sp[slot] = None
        self.slot_stop[slot] = None
        self.slot_deadline[slot] = None
        self._samp, self.cache["pos"] = self._release_slot(
            self._samp, self.cache["pos"], np.int32(slot)
        )
        self.alloc.release(slot)
        self._push_table()
        self._requeue(req)

    def _preempt_for(self, head: Request, need: int, pp) -> bool:
        """Make room for the blocked queue head by evicting the newest
        in-flight decode(s); True iff the head fits afterwards.  Guards:

          * off unless ``preempt=True`` (head-of-line blocking stays the
            default behavior);
          * a once-preempted request neither triggers nor suffers
            preemption — every request is evicted at most once, so the
            preempt/requeue cycle terminates;
          * prechecked: victims' exclusively-held pages plus the free
            pool must cover the head's cost, so pages are never freed
            without an admission to consume them."""
        if not self.preempt or head.preempted:
            return False
        victims = [
            s for s in range(self.B)
            if self.slot_req[s] is not None
            and s not in self._prefill_state
            and self.slot_req[s].preempted == 0
        ]
        if not victims:
            return False
        plan = self.alloc.plan(need, pp)
        avail = self.alloc.free_pages + sum(
            self.alloc.releasable(s) for s in victims
        )
        if plan.cost > avail:
            return False
        victims.sort(key=lambda s: self._admit_order[s])
        while victims:
            if self.alloc.can_admit(need, self.alloc.plan(need, pp)):
                return True
            self._preempt_slot(victims.pop())   # newest-admitted first
        return self.alloc.can_admit(need, self.alloc.plan(need, pp))

    # ------------------------------------------------------------- admit
    def _admit(self) -> None:
        if self.faults is not None and self.faults.alloc_blocked(self.steps):
            return  # injected allocator outage: no admissions this step
        for slot in range(self.B):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue[0]
            pp = self._replay_prompt(req)
            L = len(pp)
            # total budget is invariant under replay: prompt + max_new
            # (generated tokens move from budget to prompt rows)
            need = len(req.prompt) + self.n_front + req.max_new
            if self._incremental:
                plan = self.alloc.plan(need, pp)
                if not self.alloc.can_admit(need, plan):
                    if not self._preempt_for(req, need, pp):
                        break  # head-of-line blocking keeps FIFO order
                    plan = self.alloc.plan(need, pp)
                self.queue.pop(0)
                self.alloc.alloc(slot, need, plan)
                if self.alloc.last_cow is not None:
                    # the final page of a fully-cached prompt is shared:
                    # privatize it (copy-on-write) before the last-token
                    # recompute writes into it
                    src, dst = self.alloc.last_cow
                    self.cache["layers"] = self._copy(
                        self.cache["layers"],
                        jnp.asarray([src], jnp.int32),
                        jnp.asarray([dst], jnp.int32),
                    )
                self.slot_req[slot] = req
                self._set_slot_params(slot, req)
                self._emit("prefill", req, ts=req.t_admit, slot=slot,
                           prompt_tokens=L, cached_tokens=plan.cached_tokens)
                self._prefill_state[slot] = _Prefill(
                    req=req, prompt=pp, done=plan.cached_tokens
                )
                self._prefilling.append(slot)
                self._push_table()
                self._progress = True
                continue
            if self.alloc is not None and not self.alloc.can_admit(need):
                if not self._preempt_for(req, need, None):
                    # head-of-line blocking keeps FIFO order: wait for pages
                    break
            self.queue.pop(0)
            Sb = self._bucket(L)
            prompt = pp
            if Sb != L:
                prompt = np.zeros((Sb,), np.int32)
                prompt[:L] = pp
            batch = {"tokens": jnp.asarray(prompt[None, :], jnp.int32)}
            for k, v in self.extra.items():
                batch[k] = v
            Lx = L + self.n_front          # valid decoder-input tokens
            with span("engine.prefill"):
                logits, one_cache = self._prefill(self.params, batch, Lx)
            if self.alloc is not None:
                pages = self.alloc.alloc(slot, need)
                page = self.alloc.page_size
                n_tiles = pages_for(Sb + self.n_front, page)
                self._write_slot_paged(slot, one_cache, Lx, pages, n_tiles)
            else:
                self._write_slot(slot, one_cache, int(one_cache["pos"]))
            self.slot_req[slot] = req
            self._set_slot_params(slot, req)
            self._emit("prefill", req, ts=req.t_admit, slot=slot,
                       prompt_tokens=L, cached_tokens=0)
            self._progress = True
            self._emit_first(slot, logits)

    # ----------------------------------------------------- chunked prefill
    def _advance_prefill(self, slot: int) -> None:
        """Run ONE bounded prefill chunk for mid-prefill slot `slot`; on
        prompt completion emit the first token and flip the slot to
        decoding."""
        st = self._prefill_state[slot]
        L = len(st.prompt)
        remaining = L - st.done
        c = min(self.prefill_chunk or remaining, remaining)
        Cbuf = self._bucket(c)
        toks = np.zeros((1, Cbuf), np.int32)
        toks[0, :c] = st.prompt[st.done : st.done + c]
        logits, self.cache["layers"] = self._chunk(
            self.params, self.cache["layers"], jnp.asarray(toks),
            jnp.asarray(self.alloc.table[slot : slot + 1]),
            jnp.int32(st.done), jnp.int32(c),
        )
        st.done += c
        self._progress = True
        if st.done < L:
            return
        # prompt complete: register its full blocks for future sharing,
        # make the slot's pages visible to the lockstep decode, emit the
        # first generated token (sampled on device — no argmax roundtrip)
        self.alloc.register(slot, st.prompt)
        self._prefilling.remove(slot)
        del self._prefill_state[slot]
        self._push_table()
        self.cache["pos"] = self.cache["pos"].at[slot].set(L)
        self._canon()
        self._emit_first(slot, logits)

    def cancel(self, req: Request) -> None:
        """Abort a queued or in-flight request, releasing its slot/pages
        immediately (``finish_reason="cancelled"``; the request still
        lands in ``done`` with whatever tokens it produced).  Used by the
        LLM facade when a stream consumer abandons its iterator — an
        orphaned request must not keep decoding into other calls."""
        # identity, not ==: the dataclass __eq__ tuple-compares the numpy
        # prompt field, which raises on same-shape prompts
        for i, q in enumerate(self.queue):
            if q is req:
                del self.queue[i]
                req.finish_reason = "cancelled"
                req.t_done = self._clock()
                self._bump("cancelled")
                self._emit("finish", req, ts=req.t_done,
                           reason="cancelled", tokens=len(req.output or []))
                self.done.append(req)
                return
        for slot in range(self.B):
            if self.slot_req[slot] is req:
                if slot in self._prefill_state:
                    del self._prefill_state[slot]
                    self._prefilling.remove(slot)
                req.finish_reason = "cancelled"
                self._finish(slot)
                return

    def _finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        if not req.finish_reason:
            req.finish_reason = "length"
        reason = req.finish_reason
        if reason == "timeout":
            self._bump("timeouts")
        elif reason == "error":
            self._bump("errors")
        elif reason == "cancelled":
            self._bump("cancelled")
        else:
            self._bump("completed")
        req.t_done = self._clock()
        n_out = len(req.output or [])
        if self.metrics is not None:
            self._h_e2e.observe(req.t_done - req.t_submit)
            if req.t_first and n_out >= 2:
                self._h_itl.observe(
                    (req.t_done - req.t_first) / (n_out - 1)
                )
        self._emit("finish", req, ts=req.t_done, slot=slot,
                   reason=reason, tokens=n_out)
        self.done.append(req)
        self.slot_req[slot] = None
        self.slot_left[slot] = 0
        self.slot_sp[slot] = None
        self.slot_stop[slot] = None
        self.slot_deadline[slot] = None
        # one fused call: deactivate + reset pos so the slot comes back
        # with clean semantics immediately (the in-jit reset only covers
        # slots idle during a decode step)
        self._samp, self.cache["pos"] = self._release_slot(
            self._samp, self.cache["pos"], np.int32(slot)
        )
        if self.alloc is not None:
            self.alloc.release(slot)
            self._push_table()

    # ---------------------------------------------------------- deadlines
    def _expire_queued(self) -> None:
        """Finish queued requests whose deadline passed before they ever
        ran (``finish_reason="timeout"``).  A preempted request waiting to
        resume keeps its partial output."""
        if not self.queue:
            return
        now = self._clock()
        kept: List[Request] = []
        for req in self.queue:
            dl = self._abs_deadline(req)
            if dl is not None and now >= dl:
                req.finish_reason = "timeout"
                req.t_done = now
                self._bump("timeouts")
                self._emit("timeout", req, ts=now, where="queue")
                self._emit("finish", req, ts=now, reason="timeout", tokens=0)
                self.done.append(req)
            else:
                kept.append(req)
        self.queue = kept

    def _expire_in_flight(self) -> None:
        """Release in-flight requests past deadline at the step boundary
        (they keep the tokens produced so far)."""
        if all(d is None for d in self.slot_deadline):
            return
        now = self._clock()
        for s in range(self.B):
            dl = self.slot_deadline[s]
            if dl is None or self.slot_req[s] is None or now < dl:
                continue
            if s in self._prefill_state:
                del self._prefill_state[s]
                self._prefilling.remove(s)
                if self.alloc is not None:
                    # _push_table in _finish re-derives the mask
                    pass
            self.slot_req[s].finish_reason = "timeout"
            self._emit("timeout", self.slot_req[s], ts=now, where="in_flight",
                       slot=s)
            self._finish(s)

    # --------------------------------------------------------------- step
    def step(self) -> int:
        """Admit + bounded prefill chunks + one decode iteration over all
        decoding slots.  Returns the number of slots decoded.

        With in-flight decodes, only the longest-waiting mid-prefill slot
        advances — by ONE chunk — per step, so a long prompt delays each
        decode iteration by at most `prefill_chunk` tokens of compute.
        With no decodes to protect, every mid-prefill slot advances a
        chunk (there is nothing to stall, and admission ramps faster).

        Lifecycle order: queued deadline expiry -> admission (possibly
        preempting) -> prefill chunks -> lockstep decode + quarantine ->
        in-flight deadline expiry (the "next step boundary" of the
        deadline contract) -> watchdog accounting.  The step is a record
        of the step log (module docstring)."""
        self.steps += 1
        with STEP_LOG.step("engine", self.steps):
            return self._step()

    def _step(self) -> int:
        self._progress = False
        done0 = len(self.done)
        self._expire_queued()
        self._admit()
        if self._prefilling:
            decoding = any(
                self.slot_req[s] is not None and s not in self._prefill_state
                for s in range(self.B)
            )
            for slot in (self._prefilling[:1] if decoding
                         else list(self._prefilling)):
                self._advance_prefill(slot)
        active = [
            s for s in range(self.B)
            if self.slot_req[s] is not None and s not in self._prefill_state
        ]
        if active:
            # token-in/token-out: selection (and the idle-slot pos reset)
            # happens inside the jitted step; the sampled tokens feed the
            # next iteration straight from device memory, and the ONLY
            # host traffic is this one bulk device_get per step
            inject = self._no_inject
            bad_slots = self._nan_slots()
            if bad_slots:
                v = np.zeros((self.B,), bool)
                v[bad_slots] = True
                inject = jnp.asarray(v)
            with span("engine.decode"):
                tok_d, logp_d, bad_d, self.cache, self._samp = self._decode(
                    self.params, self.cache, self._last_tok, self._samp,
                    inject
                )
            self._last_tok = tok_d
            with span("engine.host_sync"):
                nxt, logps, bads = jax.device_get((tok_d, logp_d, bad_d))
            emitted = 0
            for s in active:
                req = self.slot_req[s]
                if bads[s]:
                    # non-finite logits in THIS slot only: quarantine it
                    # (drop the garbage token) and leave every other
                    # slot's sampled token untouched
                    req.finish_reason = "error"
                    self._emit("quarantine", req, slot=s, where="decode")
                    self._finish(s)
                    continue
                t = int(nxt[s])
                req.output.append(t)
                emitted += 1
                if req.logprobs is not None:
                    req.logprobs.append(float(logps[s]))
                self.slot_left[s] -= 1
                fin = self.slot_stop[s].check(req.output, self.slot_left[s])
                if fin:
                    req.finish_reason = fin
                    self._finish(s)
            if self.metrics is not None and emitted:
                self._c_toks.inc(emitted)
        self._expire_in_flight()
        if active or self._progress or len(self.done) != done0:
            self._steps_since_progress = 0
        else:
            self._steps_since_progress += 1
        if self.metrics is not None:
            self._c_steps.inc()
            self._observe_gauges()
        if self.on_step is not None:
            self.on_step(self)
        return len(active)

    # -------------------------------------------------------------- health
    def health(self) -> EngineHealth:
        """Cheap host-side liveness snapshot (no device sync)."""
        return EngineHealth(
            queue_depth=len(self.queue),
            slots=self.B,
            active_slots=sum(r is not None for r in self.slot_req),
            prefilling=len(self._prefilling),
            free_pages=self.alloc.free_pages if self.alloc else None,
            total_pages=(self.alloc.num_pages - 1) if self.alloc else None,
            steps=self.steps,
            steps_since_progress=self._steps_since_progress,
            counters=dict(self.counters),
        )

    def run(self, max_steps: int = 10_000) -> List[Request]:
        steps = 0
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.done
