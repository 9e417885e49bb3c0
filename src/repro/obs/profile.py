"""Profiling: host spans and a step log on the device trace's clock.

  * :func:`trace_ctx` — a context manager around ``jax.profiler.trace``:
    the whole serving/training run inside it lands in a TensorBoard-
    readable XPlane trace under the given directory.  No-op when the
    directory is falsy, so launchers can pass the flag through
    unconditionally; a trace that was asked for and cannot start raises.
  * :class:`StepLog` — a bounded ring of recent steps.  ``step(kind, i)``
    opens a record and a ``jax.profiler.StepTraceAnnotation(kind,
    step_num=i)``; ``span(name)`` opens a ``jax.profiler.TraceAnnotation``
    and adds its wall duration to the open record; ``count(name)`` adds
    to the open record's counters.  Each record holds the step's kind
    and index, its wall start and end, seconds per span, counters, and
    the key of the compiled program the step ran (``add_program``).
  * :data:`STEP_LOG` and :func:`span` — the process-wide log the trainer
    and the serving engine write.  It is process-wide on purpose: it is
    a flight recorder read after the fact (a launcher's report, a
    benchmark's readers after the trainer is gone).  Tests make their
    own ``StepLog`` with a fake clock.

Spans are always on.  They never touch the device: two clock reads, a
``TraceMe`` that is a no-op unless a profiler trace is running, and a
dict update, so the one-bulk-transfer contracts of the engine and the
trainer hold with them (``tests/test_obs.py``).

The device side is named by the program: the models and the train step
put ``jax.named_scope`` on their layers (:data:`SCOPES`), and the
compiled HLO carries them in each instruction's ``op_name`` metadata.
:func:`scope_map` reads that text into ``{instruction: (scope, pass)}``,
and :meth:`StepLog.device_seconds` joins it with a trace's per-
instruction device seconds.  A program's map is built only when it is
read, so an untraced run pays nothing for it.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import re
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
from jax import profiler as _jax_profiler

# the names models/ and training/train_step.py give jax.named_scope
SCOPES = ("embed", "norm", "attention", "ffn", "head", "optimizer")


def scoped(name: str):
    """Decorator: the function runs under ``jax.named_scope(name)``, a
    scope of :data:`SCOPES`.  A fresh scope per call: the decorator form
    of ``jax.named_scope`` keeps one context object per function and is
    not reentrant."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of {SCOPES}")

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner

    return wrap


@contextlib.contextmanager
def trace_ctx(log_dir: Optional[str]) -> Iterator[None]:
    """``with trace_ctx("/tmp/prof"):`` profiles the enclosed run.

    A falsy ``log_dir`` makes this a plain no-op, so call sites need no
    conditional.  Otherwise a trace that cannot start (e.g. one is
    already running) raises: a run asked to be profiled never exits 0
    without its trace."""
    if not log_dir:
        yield
        return
    _jax_profiler.start_trace(log_dir)
    try:
        yield
    finally:
        _jax_profiler.stop_trace()


class StepRecord:
    """One step: ``kind`` ("train", "engine"), ``index``, wall ``start``
    and ``end`` (``None`` while open), ``spans`` (name -> seconds, summed
    over the step), ``counters`` (name -> count) and ``program`` (the key
    of the compiled program it ran, if any)."""

    __slots__ = ("kind", "index", "start", "end", "spans", "counters",
                 "program")

    def __init__(self, kind: str, index: int, start: float) -> None:
        self.kind, self.index, self.start = kind, index, start
        self.end: Optional[float] = None
        self.spans: Dict[str, float] = {}
        self.counters: Dict[str, int] = {}
        self.program: Optional[str] = None


class StepLog:
    """Bounded ring of the last ``capacity`` step records, with the scope
    maps of the last ``programs`` compiled programs (see module
    docstring).  Written from one thread: spans and counters go to the
    innermost open step, and outside any step only annotate the trace."""

    def __init__(self, capacity: int = 1024, programs: int = 16,
                 clock: Callable[[], float] = time.perf_counter) -> None:
        self.clock = clock
        self._records: collections.deque = collections.deque(maxlen=capacity)
        self._open: List[StepRecord] = []
        self._programs: "collections.OrderedDict[str, Callable[[], Dict]]" = (
            collections.OrderedDict())
        self._max_programs = programs
        self._maps: Dict[str, Dict[str, Tuple[Optional[str], str]]] = {}

    # ------------------------------------------------------------ writing
    @contextlib.contextmanager
    def step(self, kind: str, index: int) -> Iterator[StepRecord]:
        rec = StepRecord(kind, index, self.clock())
        self._records.append(rec)
        self._open.append(rec)
        try:
            with _jax_profiler.StepTraceAnnotation(kind, step_num=index):
                yield rec
        finally:
            self._open.pop()
            rec.end = self.clock()

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        rec = self._open[-1] if self._open else None
        t0 = self.clock()
        try:
            with _jax_profiler.TraceAnnotation(name):
                yield
        finally:
            if rec is not None:
                rec.spans[name] = rec.spans.get(name, 0.0) + self.clock() - t0

    def count(self, name: str, n: int = 1) -> None:
        if self._open:
            c = self._open[-1].counters
            c[name] = c.get(name, 0) + n

    def add_program(self, key: str, build: Callable[[], Dict]) -> None:
        """Register the compiled program ``key``; ``build()`` returns its
        scope map (e.g. ``scope_map(compiled.as_text())``) and is called
        at the first read only.  The oldest program beyond the bound is
        forgotten."""
        self._programs[key] = build
        self._programs.move_to_end(key)
        self._maps.pop(key, None)
        while len(self._programs) > self._max_programs:
            old, _ = self._programs.popitem(last=False)
            self._maps.pop(old, None)

    # ------------------------------------------------------------ reading
    def records(self, kind: Optional[str] = None) -> List[StepRecord]:
        return [r for r in self._records if kind is None or r.kind == kind]

    def last(self, kind: str, n: int) -> List[StepRecord]:
        """The last ``n`` closed records of ``kind`` (fewer if the ring
        holds fewer)."""
        done = [r for r in self.records(kind) if r.end is not None]
        return done[max(len(done) - n, 0):] if n > 0 else []

    def scopes(self, key: str) -> Dict[str, Tuple[Optional[str], str]]:
        """``{instruction: (scope, pass)}`` of program ``key``, built at
        the first call; empty for a program the log does not hold."""
        if key not in self._maps:
            build = self._programs.get(key)
            if build is None:
                return {}
            self._maps[key] = {k: tuple(v) for k, v in build().items()}
        return self._maps[key]

    def device_seconds(self, op_s: Dict[str, float],
                       records: List[StepRecord]) -> Dict[Tuple, float]:
        """Device seconds of a trace's operations (``op_s``: event name ->
        seconds, the name an HLO instruction as the profiler prints it)
        by ``(scope, pass)`` of the programs ``records`` ran; an operation
        none of them names falls under ``(None, None)``.  Empty when no
        record ran a program the log holds."""
        names: Dict[str, Tuple] = {}
        for key in {r.program for r in records if r.program is not None}:
            names.update(self.scopes(key))
        if not names:
            return {}
        out: Dict[Tuple, float] = collections.Counter()
        for event, sec in op_s.items():
            out[names.get(instruction_name(event), (None, None))] += sec
        return dict(out)

    def summary(self, kind: Optional[str] = None) -> Dict[str, Dict[str, float]]:
        """Per span over the ring: steps it ran in, total and mean
        seconds per such step."""
        tot: Dict[str, List[float]] = {}
        for r in self.records(kind):
            for name, s in r.spans.items():
                cell = tot.setdefault(name, [0, 0.0])
                cell[0] += 1
                cell[1] += s
        return {name: {"count": c, "total_s": t, "mean_s": t / c}
                for name, (c, t) in sorted(tot.items())}

    def report(self, kind: Optional[str] = None) -> str:
        return "\n".join(
            f"{name}: n={v['count']} mean={v['mean_s'] * 1e3:.3f}ms "
            f"total={v['total_s']:.3f}s"
            for name, v in self.summary(kind).items()
        )


STEP_LOG = StepLog()


def span(name: str):
    """``with span("train.data"):`` — a host span in :data:`STEP_LOG`."""
    return STEP_LOG.span(name)


# ------------------------------------------------------------ scope maps
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%(\S+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
                    re.MULTILINE)
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


def instruction_name(event: str) -> str:
    """``%fusion.414 = bf16[8]{0} fusion(...)`` -> ``fusion.414``."""
    return event.partition(" = ")[0].strip().lstrip("%")


def classify(op_name: str) -> Tuple[Optional[str], str]:
    """``(scope, pass)`` of one ``op_name``: the innermost component
    that is one of :data:`SCOPES` once transforms are unwrapped
    (``transpose(jvp(attention))`` -> ``attention``), else ``None``; and
    the pass, from the transforms: ``"remat"`` (recomputed for the
    backward under ``jax.checkpoint``), ``"bwd"`` (transposed), ``"fwd"``
    (under ``jvp``), ``"step"`` (outside any derivative: the optimizer,
    or a program that takes none)."""
    scope = None
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part in SCOPES:
            scope = part
    if "rematted_computation" in op_name:
        return scope, "remat"
    if "transpose(" in op_name:
        return scope, "bwd"
    if "jvp(" in op_name:
        return scope, "fwd"
    return scope, "step"


def scope_map(hlo_text: str) -> Dict[str, Tuple[Optional[str], str]]:
    """``{instruction: (scope, pass)}`` for every instruction of a
    compiled HLO module's text that carries an ``op_name``."""
    return {m.group(1): classify(m.group(2)) for m in _INSTR.finditer(hlo_text)}
