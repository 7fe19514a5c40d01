"""Reduce a profiler trace (``.xplane.pb``) of a benchmark window to numbers.

What the TPU's trace holds (read with ``jax.profiler.ProfileData``):

* per chip a plane ``/device:TPU:<i>`` with the line ``XLA Modules`` (one
  event per program execution) and ``XLA Ops`` (one per operation);
* the host plane ``/host:CPU``, where the line of the harness's thread
  (the one holding ``bench.window``) has its ``TraceAnnotation`` spans
  (``bench.window``, ``bench.round``, ``strategy.*``, ``cohort_kernel``)
  and, per jitted dispatch, two nested ``PjitFunction(<name>)`` events
  (the outer one is kept).

All event times share one clock. The reduction:

* the window is the ``bench.window`` span;
* busy time is the union of ``XLA Ops`` intervals inside the window,
  averaged over the chips; idle gaps are the holes in that union;
* program executions (``XLA Modules``) are matched, in order, to the
  host's ``PjitFunction`` dispatches; an execution whose dispatch lies
  inside a ``cohort_kernel`` span is the cohort kernel's. When the counts
  of dispatches and executions differ the matching is not trusted and the
  kernel's time is left unknown (``kernel_s`` is None);
* each idle gap is charged to what the host was doing at its midpoint: the
  innermost harness span there and the innermost dispatch, if any.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

SPAN_PREFIXES = ("bench.", "strategy.", "cohort_kernel")
KERNEL_SPAN = "cohort_kernel"
_DISPATCH = re.compile(r"^PjitFunction\((.*)\)$")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # union of op intervals, mean over chips
    executions: int               # program executions in the window (chip 0)
    dispatches: int               # host jit dispatches in the window
    kernel_executions: int | None
    kernel_s: float | None        # device time of the cohort kernel
    other_s: float | None         # device time of every other program
    ops: dict                     # "<program>/<op>" -> seconds
    gaps: dict                    # host activity -> idle seconds
    rounds: int                   # bench.round spans in the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, k):
        return [[n, s] for n, s in sorted(self.ops.items(),
                                          key=lambda x: -x[1])[:k]]

    def top_gaps(self, k):
        return [[n, s] for n, s in sorted(self.gaps.items(),
                                          key=lambda x: -x[1])[:k]]


def _events(line):
    return [(e.start_ns, e.end_ns, e.name) for e in line.events]


def _union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _op_name(name):
    """'%fusion.3 = f32[...] fusion(...)' -> 'fusion.3'."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%")


def _program(name):
    """'jit_scatter(9919480309697685854)' -> 'jit_scatter'."""
    return name.split("(", 1)[0]


def reduce(path: str) -> Reduction:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host = data.find_plane_with_name("/host:CPU")
    py = next((ev for ev in (_events(l) for l in host.lines)
               if any(n == "bench.window" for _, _, n in ev)), None)
    if py is None:
        raise ValueError(f"{path}: no bench.window span on any host line "
                         f"({[l.name for l in host.lines]})")
    spans, dispatch = [], []
    for s, e, n in py:
        if n.startswith(SPAN_PREFIXES):
            spans.append((s, e, n))
        elif _DISPATCH.match(n):
            dispatch.append((s, e, _DISPATCH.match(n).group(1)))
    windows = [x for x in spans if x[2] == "bench.window"]
    if not windows:
        raise ValueError(f"{path}: no bench.window span")
    lo, hi = windows[0][0], windows[0][1]
    rounds = sum(1 for s, e, n in spans
                 if n == "bench.round" and s >= lo and e <= hi)
    devices = sorted((p for p in data.planes
                      if re.match(r"^/device:TPU:\d+$", p.name)),
                     key=lambda p: p.name)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")

    busy, first = [], None
    for plane in devices:
        lines = {l.name: l for l in plane.lines}
        ops = _clip([(s, e) for s, e, _ in _events(lines["XLA Ops"])],
                    lo, hi)
        merged = _union(ops)
        busy.append(sum(e - s for s, e in merged))
        if first is None:
            first = (merged, lines)
    merged, lines = first

    # program executions of chip 0, matched to the host's dispatches
    mods = sorted(_events(lines["XLA Modules"]))
    mods_in = [m for m in mods if m[0] >= lo and m[0] < hi]
    disp_in = _outermost(sorted(d for d in dispatch if lo <= d[0] < hi))
    kspans = sorted((s, e) for s, e, n in spans if n == KERNEL_SPAN)
    labels = {}
    kernel_s = other_s = kernel_n = None
    if len(mods_in) == len(disp_in):
        kernel_s = other_s = 0.0
        kernel_n = 0
        for m, d in zip(mods_in, disp_in):
            inside = _covering(kspans, d[0]) is not None
            labels[m[0]] = KERNEL_SPAN if inside else _program(m[2])
            dur = (min(m[1], hi) - m[0]) * 1e-9
            if inside:
                kernel_s += dur
                kernel_n += 1
            else:
                other_s += dur
    ops = collections.Counter()
    starts = [m[0] for m in mods_in]
    for s, e, n in _events(lines["XLA Ops"]):
        if e <= lo or s >= hi:
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = labels.get(starts[i], _program(mods_in[i][2])) \
            if i >= 0 else "?"
        ops[f"{prog}/{_op_name(n)}"] += (min(e, hi) - max(s, lo)) * 1e-9

    gaps = collections.Counter()
    disp_starts = [d[0] for d in disp_in]
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    inner = sorted(spans, key=lambda x: (x[0], -x[1]))
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        span = _innermost(inner, mid)
        disp = _innermost_sorted(disp_starts, disp_in, mid)
        what = span[2] if span else "outside any span"
        if disp:
            what += f" > {disp[2]}"
        gaps[what] += (b - a) * 1e-9
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=sum(busy) / len(busy) * 1e-9,
                     executions=len(mods_in), dispatches=len(disp_in),
                     kernel_executions=kernel_n,
                     kernel_s=kernel_s, other_s=other_s, ops=dict(ops),
                     gaps=dict(gaps), rounds=rounds)


def _outermost(events):
    """Drop events nested in an earlier one: the host records every jitted
    dispatch as two nested ``PjitFunction`` events."""
    out = []
    for ev in events:
        if out and ev[0] < out[-1][1]:
            continue
        out.append(ev)
    return out


def _covering(intervals, t):
    for s, e in intervals:
        if s <= t < e:
            return (s, e)
    return None


def _innermost(events, t):
    """The shortest event covering time ``t``."""
    best = None
    for ev in events:
        if ev[0] <= t < ev[1] and (best is None
                                   or ev[1] - ev[0] < best[1] - best[0]):
            best = ev
    return best


def _innermost_sorted(starts, events, t, look_back: int = 16):
    """:func:`_innermost` over events sorted by start that rarely nest
    (the host's dispatches): only the last few starting before ``t``."""
    i = bisect.bisect_right(starts, t)
    return _innermost(events[max(0, i - look_back):i], t)
