"""Profiling hooks: opt-in ``jax.profiler`` wrappers + host step timers.

Three tools, all default-off and all zero-cost when off:

  * :func:`trace_ctx` — a context manager around ``jax.profiler.trace``:
    the whole serving/training run inside it lands in a TensorBoard-
    readable XPlane trace under the given directory.  No-op when the
    directory is falsy, so launchers can pass the flag through
    unconditionally; a trace that was asked for and cannot start raises.
  * :class:`annotate` — a named ``jax.profiler.TraceAnnotation`` scope
    marking host-side regions (the jitted decode dispatch, a train
    step) so they are attributable in the trace timeline.  Constructed
    with ``enabled=False`` it is a no-op context manager; the engine
    and trainer gate it on their ``profile`` knob so the default hot
    path pays nothing.
  * :class:`StepTimer` — a host-side per-phase timing accumulator
    (``perf_counter`` spans, plain floats).  It deliberately does NOT
    ``block_until_ready``: it measures *dispatch* wall time, which is
    what the host-side scheduling loop can actually stall on, and
    inserting syncs would break the engine's one-bulk-transfer-per-step
    contract the transfer-guard tests pin down.  Per-span cost is two
    clock reads and a dict update.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

from jax import profiler as _jax_profiler


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


class annotate:
    """Named profiler annotation scope; a no-op unless ``enabled``.

    ``with annotate("engine/decode", enabled=profile): ...`` shows up as
    a named span on the host timeline of a ``trace_ctx`` capture."""

    __slots__ = ("_ctx",)

    def __init__(self, name: str, enabled: bool = True) -> None:
        self._ctx = _jax_profiler.TraceAnnotation(name) if enabled else None

    def __enter__(self) -> "annotate":
        if self._ctx is not None:
            self._ctx.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._ctx is not None:
            self._ctx.__exit__(*exc)
        return False


class StepTimer:
    """Accumulates wall time per named phase across many steps.

    ``totals[name] = (count, total_seconds)``; ``summary()`` renders
    mean/total per phase.  Host-side only — see module docstring for why
    it never syncs the device."""

    __slots__ = ("totals", "_clock")

    def __init__(self, clock=time.perf_counter) -> None:
        self.totals: Dict[str, list] = {}
        self._clock = clock

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = self._clock()
        try:
            yield
        finally:
            dt = self._clock() - t0
            cell = self.totals.get(name)
            if cell is None:
                self.totals[name] = [1, dt]
            else:
                cell[0] += 1
                cell[1] += dt

    def mean(self, name: str) -> float:
        cell = self.totals.get(name)
        return cell[1] / cell[0] if cell else 0.0

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {
            name: {"count": c, "total_s": t, "mean_s": t / c}
            for name, (c, t) in sorted(self.totals.items())
        }

    def report(self) -> str:
        return "\n".join(
            f"{name}: n={v['count']} mean={v['mean_s'] * 1e3:.3f}ms "
            f"total={v['total_s']:.3f}s"
            for name, v in self.summary().items()
        )
