"""Distributed training engine (BioNeMo/Megatron trainer analogue).

``Trainer`` owns the training vertical end-to-end:

  * sharded step — ``make_sharded_train_step`` (jit with state/batch
    in_shardings, state out_shardings, donated state), compiled ONCE ahead
    of time per batch shape; each compiled program is registered with the
    step log, which reads its scope map from the HLO only when asked
  * batch placement — host pipeline batches land on the mesh's ``data``
    axes (``jax.make_array_from_process_local_data`` when running
    multi-process, a sharded ``device_put`` on one host)
  * double-buffered device prefetch — batch N+1 transfers to device while
    step N runs
  * async metrics — per-step metrics stay on device; ONE bulk
    ``jax.device_get`` per log interval and no implicit transfers in the
    steady state (transfer-guard tested like the serving engine)
  * step log — every ``step()`` is a record of ``repro.obs.STEP_LOG``
    (a ``StepTraceAnnotation("train", step_num=i)`` on the profiler's
    clock) with host spans ``train.data`` (``next`` of the prefetcher:
    host pipeline plus placement), ``train.compile`` (and a ``compiles``
    counter), ``train.dispatch`` (the compiled call: enqueue only, the
    device runs on), ``train.flush`` (the log flush's bulk
    ``device_get``) and ``train.checkpoint``.  Always on; host-side
    only, so the transfer contract below is untouched
  * unified telemetry — pass ``metrics=MetricsRegistry()`` (``repro.obs``)
    and the log-interval flush also feeds the shared registry
    (loss positions/s, step-time histogram, grad-norm, loss, skipped
    steps, and from the step log the data wait and compiles): the
    serving engine and the trainer then report through one exposition
    surface.  Registry writes consume only the values the flush already
    fetched, so the transfer contract is untouched
  * resumable checkpoints — the FULL TrainState (params + AdamW moments +
    optimizer step) plus the data-iterator cursor; ``resume_from``
    reproduces the uninterrupted run bit-exactly
    (tests/test_trainer_distributed.py)

``run_training`` remains as the functional wrapper older call sites use.
"""
from __future__ import annotations

import collections
import itertools
import os
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.core.config import TrainConfig
from repro.models.model import Model
from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import STEP_LOG, scope_map, span
from repro.training import train_step as TS
from repro.training.train_step import TrainState

# keys of compiled step programs in the step log, unique in the process
_PROGRAM_IDS = itertools.count()


class NonFiniteLossError(RuntimeError):
    """Raised by ``Trainer`` after ``TrainConfig.max_nonfinite_skips``
    CONSECUTIVE optimizer steps were skipped for non-finite loss/grads —
    at that point the run is diverged (or the data is poisoned), not
    transiently unlucky, and silently skipping forever would burn the
    cluster while the loss curve flatlines.  Carries ``step`` (the last
    offending optimizer step) and ``skips``."""

    def __init__(self, step: int, skips: int):
        super().__init__(
            f"non-finite loss/grad-norm on {skips} consecutive steps "
            f"(last: optimizer step {step}); update was skipped each time "
            f"— aborting instead of training on garbage"
        )
        self.step = step
        self.skips = skips


class _DevicePrefetch:
    """Double-buffered host->device pipeline feeding the train step.

    Each buffered batch carries the pipeline's post-draw cursor
    (``state_dict()``, when the pipeline has one), so a checkpoint taken
    after consuming batch N records "next draw is N+1" even though the
    prefetcher has already pulled batches N+1, N+2 off the host iterator.
    """

    def __init__(self, pipeline, place, depth: int = 2):
        self.pipeline = pipeline
        self.src = iter(pipeline)
        self.place = place
        self.depth = max(int(depth), 1)
        self.buf: collections.deque = collections.deque()
        self.cursor = self._snapshot()  # state before any draw
        self.exhausted = False

    def _snapshot(self):
        sd = getattr(self.pipeline, "state_dict", None)
        return sd() if callable(sd) else None

    def _pull(self) -> None:
        try:
            b = next(self.src)
        except StopIteration:
            self.exhausted = True
            return
        self.buf.append((self.place(b), self._snapshot()))

    def __iter__(self):
        return self

    def __next__(self):
        while len(self.buf) < self.depth and not self.exhausted:
            self._pull()
        if not self.buf:
            raise StopIteration
        batch, cur = self.buf.popleft()
        if cur is not None:
            self.cursor = cur
        return batch


class Trainer:
    """Mesh-aware training engine; see module docstring.

    Drive it with ``run(batches)`` for a whole schedule, or
    ``prepare(batches)`` + repeated ``step()`` for finer control (the
    transfer-guard tests step it manually around the warmup/compile)."""

    def __init__(
        self,
        model: Model,
        tc: TrainConfig,
        *,
        hooks: Optional[List[Callable[[int, Dict[str, float]], None]]] = None,
        verbose: bool = True,
        prefetch: int = 2,
        metrics: Optional[MetricsRegistry] = None,
    ):
        self.model, self.tc = model, tc
        mesh = model.ctx.mesh
        self.mesh = None if (mesh is None or mesh.empty or mesh.size == 1) else mesh
        self.hooks = list(hooks or [])
        self.verbose = verbose
        self.prefetch = max(int(prefetch), 1)
        self._jit_step = TS.make_sharded_train_step(model, tc)
        # per-shape compile cache: size-aware batching yields a bounded
        # set of (rows, len) shapes (one per length bucket); each shape
        # AOT-compiles once and is reused, never recompiled per step
        self._compiled: Dict[Any, Dict[str, Any]] = {}
        self.state: Optional[TrainState] = None
        self.step_idx = 0            # optimizer steps completed
        self.history: List[Dict[str, float]] = []
        self._pending: List[Dict] = []  # device metrics since last log
        self._records: List = []        # step-log records since last log
        self._tokens_seen = 0.0
        # non-finite-step guard (see train_step.py): totals and the
        # current consecutive-skip streak, advanced at each log flush
        self.skipped_total = 0
        self._skip_streak = 0
        self._it: Optional[_DevicePrefetch] = None
        self._t0 = self._t_log = 0.0

        # unified telemetry (repro.obs): registry series are fed at the
        # log-interval flush from values the ONE bulk device_get already
        # fetched — no extra transfers, no per-step host work
        self.metrics = metrics
        if metrics is not None:
            self._c_steps = metrics.counter(
                "train_steps_total", "optimizer steps completed"
            )
            self._c_tokens = metrics.counter(
                "train_tokens_total",
                "loss positions consumed (under MLM the masked positions)",
            )
            self._c_wait = metrics.counter(
                "train_data_wait_seconds_total",
                "host seconds Trainer.step waited for its next device "
                "batch (span train.data)",
            )
            self._c_compiles = metrics.counter(
                "train_compiles_total",
                "step programs compiled (span train.compile)",
            )
            self._c_skipped = metrics.counter(
                "train_skipped_steps_total",
                "updates withheld for non-finite loss/grads",
            )
            self._h_step = metrics.histogram(
                "train_step_time_seconds", "mean step wall per log interval"
            )
            self._tg = {
                name: metrics.gauge(f"train_{name}", help)
                for name, help in (
                    ("loss", "last flushed total loss"),
                    ("grad_norm", "last flushed global gradient norm"),
                    ("tokens_per_sec",
                     "loss positions per second over the log interval"),
                    ("lr", "current learning rate"),
                    ("aux_loss", "router load-balance loss (MoE)"),
                    ("router_entropy", "mean router entropy (MoE)"),
                    ("router_drop_frac", "capacity-dropped slot fraction"),
                )
            }
            self._g_load = metrics.gauge(
                "train_router_load",
                "per-expert fraction of kept routed slots",
                labels=("expert",),
            )

    # ------------------------------------------------------------ placement
    def _place(self, batch):
        """Put a host batch onto the mesh's data axes (per-host placement
        on multi-process runs), or the default device off-mesh."""
        if self.mesh is None:
            return jax.device_put(batch)
        sh = TS.host_batch_sharding(self.model)
        if jax.process_count() > 1:
            return jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(
                    sh, np.asarray(x)
                ),
                batch,
            )
        return jax.device_put(batch, sh)

    def _place_state(self, state: TrainState) -> TrainState:
        if self.mesh is None:
            return jax.device_put(state)
        return jax.device_put(state, TS.state_shardings(self.model))

    # ------------------------------------------------------------ lifecycle
    def prepare(
        self,
        batches,
        *,
        state: Optional[TrainState] = None,
        resume_from: Optional[str] = None,
    ) -> "Trainer":
        if resume_from:
            self.load(resume_from, batches)
        elif state is not None:
            self.state = self._place_state(state)
        if self.state is None:
            self.state = TS.init_sharded_train_state(
                self.model, jax.random.PRNGKey(self.tc.seed), self.tc
            )
        self._it = _DevicePrefetch(batches, self._place, self.prefetch)
        self._t0 = self._t_log = time.perf_counter()
        return self

    @staticmethod
    def _batch_sig(batch) -> Any:
        """Hashable shape signature of a device batch — the compile-cache
        key.  Bucketed pipelines emit a bounded set of these."""
        if not isinstance(batch, dict):
            return None
        return tuple(
            sorted(
                (k, tuple(v.shape), str(v.dtype)) for k, v in batch.items()
            )
        )

    def _build_compiled(self, batch, sig) -> Dict[str, Any]:
        """AOT-compile the sharded step for this batch shape (avoids the
        double compile of lower-after-first-call) and register it with
        the step log under a key of its own; the log reads the program's
        scope map from its HLO text only when asked.  A compile error
        propagates: a step that does not compile must not run."""
        t0 = time.perf_counter()
        compiled = self._jit_step.lower(self.state, batch).compile()
        key = f"train.{next(_PROGRAM_IDS)}"
        STEP_LOG.add_program(key, lambda: scope_map(compiled.as_text()))
        entry: Dict[str, Any] = {"fn": compiled, "key": key,
                                 "compile_s": time.perf_counter() - t0}
        self._compiled[sig] = entry
        return entry

    # ------------------------------------------------------------ stepping
    def step(self) -> int:
        """One optimizer step: pull a prefetched device batch, run the
        sharded step, stash device metrics; log/checkpoint on schedule.
        The step and its phases are spans of the step log (module
        docstring)."""
        s = self.step_idx
        with STEP_LOG.step("train", s) as rec:
            with span("train.data"):
                batch = next(self._it)
            sig = self._batch_sig(batch)
            entry = self._compiled.get(sig)
            if entry is None:
                with span("train.compile"):
                    entry = self._build_compiled(batch, sig)
                STEP_LOG.count("compiles")
            rec.program = entry["key"]
            with span("train.dispatch"):
                self.state, metrics = entry["fn"](self.state, batch)
            self.step_idx = s + 1
            self._pending.append(metrics)
            self._records.append(rec)
            if (s % max(self.tc.log_every, 1)) == 0 or s == self.tc.total_steps - 1:
                with span("train.flush"):
                    self._flush_log(s)
            if (
                self.tc.ckpt_every
                and self.tc.ckpt_dir
                and self.step_idx % self.tc.ckpt_every == 0
            ):
                with span("train.checkpoint"):
                    self.save(
                        os.path.join(self.tc.ckpt_dir, f"step_{self.step_idx}")
                    )
        return self.step_idx

    def _flush_log(self, s: int) -> None:
        fetched = jax.device_get(self._pending)  # the ONE bulk transfer
        self._pending = []
        records, self._records = self._records, []
        now = time.perf_counter()
        dt = now - self._t_log
        self._t_log = now
        n = len(fetched)
        # a step's "tokens" is its loss denominator: loss positions (under
        # MLM the masked ~15%), not real tokens; tokens_per_sec and
        # tokens_seen count the same positions
        tokens = float(sum(m["tokens"] for m in fetched))
        self._tokens_seen += tokens
        # non-finite guard bookkeeping: the jitted step already withheld
        # the update on skipped steps; here we count them (in order, so
        # the consecutive streak is exact) and abort a diverged run
        for i, fm in enumerate(fetched):
            if float(fm.get("skipped", 0.0)) > 0.0:
                self.skipped_total += 1
                self._skip_streak += 1
                if self.metrics is not None:
                    self._c_skipped.inc()
                if self._skip_streak >= max(self.tc.max_nonfinite_skips, 1):
                    raise NonFiniteLossError(
                        s - n + 1 + i, self._skip_streak
                    )
            else:
                self._skip_streak = 0
        last = fetched[-1]
        # vector-valued metrics (per-expert router load) stay out of the
        # scalar history dict and feed the labeled gauge instead
        m = {k: float(v) for k, v in last.items() if np.ndim(v) == 0}
        step_time = dt / max(n, 1)
        m.update(
            step=s,
            wall=now - self._t0,
            step_time=step_time,
            tokens_per_sec=tokens / dt if dt > 0 else 0.0,
            tokens_seen=self._tokens_seen,
            skipped_total=self.skipped_total,
        )
        if self.metrics is not None:
            # registry feed: everything below is already host-side (the
            # single bulk fetch above, the step log's records) — zero
            # extra device traffic
            self._c_steps.inc(n)
            self._c_tokens.inc(tokens)
            self._c_wait.inc(sum(r.spans.get("train.data", 0.0)
                                 for r in records))
            self._c_compiles.inc(sum(r.counters.get("compiles", 0)
                                     for r in records))
            self._h_step.observe(step_time)
            for name in self._tg:
                if name in m:
                    self._tg[name].set(m[name])
            load = last.get("router_load")
            if load is not None and np.ndim(load) == 1:
                for e, frac in enumerate(np.asarray(load)):
                    self._g_load.labels(str(e)).set(float(frac))
        self.history.append(m)
        if self.verbose:
            skips = f"  SKIPPED {self.skipped_total}" if self.skipped_total else ""
            print(
                f"step {s:5d}  loss {m['loss']:.4f}  ce {m['ce_loss']:.4f}  "
                f"gnorm {m['grad_norm']:.2f}  lr {m['lr']:.2e}  "
                f"{m['tokens_per_sec']:.0f} tok/s  {m['wall']:.1f}s{skips}"
            )
        for h in self.hooks:
            h(s, m)

    def run(
        self,
        batches,
        *,
        state: Optional[TrainState] = None,
        resume_from: Optional[str] = None,
    ):
        """Train to ``tc.total_steps``; returns ``(state, history)``."""
        self.prepare(batches, state=state, resume_from=resume_from)
        while self.step_idx < self.tc.total_steps:
            self.step()
        if self.tc.ckpt_every and self.tc.ckpt_dir:
            final = os.path.join(
                self.tc.ckpt_dir, f"step_{self.tc.total_steps}"
            )
            if not os.path.isdir(final):
                self.save(final)
        return self.state, self.history

    # -------------------------------------------------------- checkpointing
    def save(self, ckpt_dir: str) -> None:
        """Full-state checkpoint: TrainState + data cursor + counters.

        ``tokens_seen`` must cover every completed step, including the
        ones whose metrics are still pending the next log flush (a
        checkpoint need not align with a log boundary) — fetching their
        token counts here is fine, checkpointing is a host sync anyway.
        The in-memory counter is untouched; those steps still add to it
        at their regular flush."""
        pending_tokens = float(
            sum(jax.device_get([m["tokens"] for m in self._pending]))
        ) if self._pending else 0.0
        extra = {
            "step_idx": self.step_idx,
            "tokens_seen": self._tokens_seen + pending_tokens,
            "data": self._it.cursor if self._it is not None else None,
        }
        ckpt.save_train_state(ckpt_dir, self.state, self.step_idx, extra=extra)

    def load(self, ckpt_dir: str, batches=None) -> "Trainer":
        """Sharding-aware restore of the full TrainState; rewinds the data
        pipeline to the saved cursor when it supports ``load_state_dict``."""
        shardings = (
            TS.state_shardings(self.model) if self.mesh is not None else None
        )
        state, step, extra = ckpt.restore_train_state(
            ckpt_dir, TS.abstract_train_state(self.model), shardings
        )
        self.state = state if self.mesh is not None else self._place_state(state)
        self.step_idx = int(extra.get("step_idx", step))
        self._tokens_seen = float(extra.get("tokens_seen", 0.0))
        cur = extra.get("data")
        if cur is not None and hasattr(batches, "load_state_dict"):
            batches.load_state_dict(cur)
        return self


def run_training(
    model: Model,
    tc: TrainConfig,
    batches: Iterator[Dict[str, np.ndarray]],
    *,
    state: Optional[TrainState] = None,
    hooks: Optional[List[Callable[[int, Dict[str, float]], None]]] = None,
    verbose: bool = True,
) -> tuple[TrainState, List[Dict[str, float]]]:
    """Back-compat functional wrapper over :class:`Trainer`."""
    return Trainer(model, tc, hooks=hooks, verbose=verbose).run(
        batches, state=state
    )
