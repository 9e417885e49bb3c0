"""Reduce a profiler trace of the measured window to device metrics.

``reduce_dir(dir, span)`` reads the one ``*.xplane.pb`` that
``jax.profiler`` wrote under ``dir`` (``jax.profiler.ProfileData``) and
returns, for the interval of the host span named ``span`` (the window):

- ``window_s``: the window's length;
- ``busy_s``: per chip, the union of the intervals in which an operation
  ran on the device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane),
  averaged over the chips;
- ``op_s`` and ``op_n``: per operation, its device seconds and calls,
  each averaged over the chips. Operations are the line's leaves: an
  event that holds others (a ``while`` loop around the layers) is not
  counted, its contents are. The name is the event's, the HLO
  instruction as the profiler prints it;
- ``collective_s`` and ``exposed_collective_s``: the union of the
  collectives' intervals (leaves of ``XLA Ops`` and the events of
  ``Async XLA Ops``), and the part of it in which no other leaf ran on
  that chip, averaged over the chips;
- ``breakdown``: the ten operations that took most time (named short:
  instruction, opcode and result type), and the ten longest idle gaps,
  each named by the innermost host span open at the gap's middle.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
TOP = 10

Interval = Tuple[float, float]


def find_xplane(path: str) -> str:
    files = glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} xplane files under {path}")
    return files[0]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the disjoint sorted ``a`` outside the disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def host_events(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return out


def window_of(hosts, span: str) -> Interval:
    found = [(s, e) for name, s, e in hosts if name == span]
    if len(found) != 1:
        raise ValueError(f"{len(found)} host spans named {span!r} in the trace")
    return found[0]


def leaves(events: Sequence[Tuple[str, float, float]]) -> List[bool]:
    """For each event, whether it holds no other event of its line (an
    event of no duration holds nothing and makes no other a holder)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    leaf = [True] * len(events)
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if e <= s:
            continue
        if stack and e <= events[stack[-1]][2]:
            leaf[stack[-1]] = False
        stack.append(i)
    return leaf


def device_ops(pd, lo: float, hi: float):
    """Per chip: the events of ``OPS_LINE`` as (name, start, end, leaf),
    leaves told before clipping, and the events of ``ASYNC_LINE`` as
    (name, start, end), all clipped to [lo, hi]."""
    def clipped(events):
        return [(ev[0], max(ev[1], lo), min(ev[2], hi)) + tuple(ev[3:])
                for ev in events if min(ev[2], hi) > max(ev[1], lo)]

    chips = []
    for plane in pd.planes:
        if not DEVICE_PLANE.fullmatch(plane.name):
            continue
        ops, asyncs = [], []
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if line.name == OPS_LINE:
                ops += clipped([ev + (f,) for ev, f in zip(events, leaves(events))])
            elif line.name == ASYNC_LINE:
                asyncs += clipped(events)
        chips.append((ops, asyncs))
    if not chips:
        raise ValueError("the trace has no TPU device plane")
    return chips


def short_name(name: str) -> str:
    """``%fusion.414 = bf16[8,1024]{...} fusion(...)`` -> ``fusion.414
    fusion bf16[8,1024]``; a name not in that form is kept whole."""
    head, sep, rest = name.partition(" = ")
    op = re.search(r"[\]})] ([a-z][\w-]*)\(", rest)
    if not sep or not op:
        return name
    result = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return " ".join(x for x in (head.lstrip("%"), op.group(1),
                                result.group(1) if result else "") if x)


def innermost(hosts, t: float) -> str:
    best = None
    for name, s, e in hosts:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no host span"


def reduce(pd, span: str) -> Dict:
    hosts = host_events(pd)
    lo, hi = window_of(hosts, span)
    chips = device_ops(pd, lo, hi)
    n = len(chips)
    op_s: Dict[str, float] = collections.Counter()
    op_n: Dict[str, float] = collections.Counter()
    short: Dict[str, float] = collections.Counter()
    busy = coll = exposed = 0.0
    gaps: List[Tuple[float, float, float]] = []
    for ops, asyncs in chips:
        for name, s, e, leaf in ops:
            if leaf:
                op_s[name] += (e - s) * 1e-9 / n
                op_n[name] += 1.0 / n
                short[short_name(name)] += (e - s) * 1e-9 / n
        union = merge([(s, e) for _, s, e, _ in ops])
        busy += length(union) * 1e-9 / n
        c = merge([(s, e) for name, s, e, leaf in ops
                   if leaf and COLLECTIVE.search(name)]
                  + [(s, e) for name, s, e in asyncs if COLLECTIVE.search(name)])
        rest = merge([(s, e) for name, s, e, leaf in ops
                      if leaf and not COLLECTIVE.search(name)])
        coll += length(c) * 1e-9 / n
        exposed += length(subtract(c, rest)) * 1e-9 / n
        gaps += [(e - s, s, e) for s, e in subtract([(lo, hi)], union)]
    gaps.sort(reverse=True)
    top_ops = sorted(short.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9, "busy_s": busy, "chips": n,
        "op_s": dict(op_s), "op_n": dict(op_n),
        "collective_s": coll, "exposed_collective_s": exposed,
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[innermost(hosts, (s + e) / 2), d * 1e-9]
                          for d, s, e in gaps[:TOP]],
        },
    }


def reduce_dir(path: str, span: str) -> Dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(path)), span)
