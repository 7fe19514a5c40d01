"""Put a traced window's idle time and the cohort kernel's device time down
to the program's phases.

Reads the same ``.xplane.pb`` as ``bench/trace_reduce.py`` and leaves that
reduction as it is; this one adds two breakdowns that need the program's
own tracing (``repro.federated.tracing``):

* idle by layer: each hole in the union of chip 0's ``XLA Ops`` inside
  ``bench.window`` is charged, at its midpoint, to the innermost host span
  there (``bench.*``, ``engine.*``, ``strategy.*``, ``cohort_kernel``), and
  the span's layer takes it: ``engine`` for ``engine.*``; ``strategy`` for
  ``strategy.*`` and ``cohort_kernel``; ``harness`` for ``bench.*`` or no
  span. Left out (None) where the window holds no ``engine.round`` span:
  a program without its own spans.
* kernel self time by scope: every op of a ``jit_cohort_kernel``
  execution on chip 0 gets its self time (its duration less the part its
  nested ops on the same line cover: a ``while`` contains its body) and
  the innermost of the kernel's name scopes in its name-scope path:
  ``client`` (``tpgf.client``), ``server`` (``tpgf.server``), ``update``
  (``cohort_kernel.update``), ``gather`` (``cohort_kernel.gather``). An op
  with no scope of its own (a ``while``, whose path the trace drops; a
  copy XLA inserted) takes the one scope its nested ops share, else its
  enclosing op's (``scoped_self_times``), else ``other``.
  Left out (None) where no op of a kernel execution has a scope.

The op's name-scope path is the ``op_name`` metadata XLA keeps per
instruction; the TPU's trace carries it as the ``tf_op`` stat of the op's
event metadata (``bench/xplane_meta.py`` reads it).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import time

from bench import xplane_meta

SPAN_PREFIXES = ("bench.", "engine.", "strategy.", "cohort_kernel")
KERNEL_PROGRAM = "jit_cohort_kernel"
SCOPES = {"tpgf.client": "client", "tpgf.server": "server",
          "cohort_kernel.update": "update", "cohort_kernel.gather": "gather"}
_SCOPE_RE = re.compile(r"(?<![\w.])(" + "|".join(
    re.escape(s) for s in SCOPES) + r")(?![\w.])")
# the stat of an op's event metadata that holds its name-scope path on a
# TPU, e.g. "jit(cohort_kernel)/while/body/closed_call/vmap(tpgf.client)/
# jvp(tpgf.client)/dot_general:"; a fusion may list several, ";"-joined
PATH_STAT = "tf_op"

TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".trace")


@dataclasses.dataclass
class Phases:
    idle: dict | None       # layer -> idle seconds in the window
    idle_spans: dict        # innermost span name -> idle seconds
    scopes: dict | None     # scope -> kernel self seconds in the window
    kernel_executions: int
    unscoped: dict          # the kernel's largest "other" ops -> seconds


def layer(span_name):
    """The layer an idle gap under ``span_name`` (None: no span) is
    charged to."""
    if span_name is None or span_name.startswith("bench."):
        return "harness"
    if span_name.startswith("engine."):
        return "engine"
    return "strategy"


def scope(paths):
    """The innermost kernel scope of the first of the ``;``-joined
    name-scope paths that has one, or None."""
    for path in (paths or "").split(";"):
        found = _SCOPE_RE.findall(path)
        if found:
            return SCOPES[found[-1]]
    return None


def scoped_self_times(events, own_scope):
    """[(start, end, name)] of one device line -> [(start, end, name,
    self ns, scope)]. Self time is the duration less the part nested
    events cover. The scope is ``own_scope(name)``; where that is None,
    the one scope that every scoped op nested in it shares (a loop whose
    body is all one phase: the TPU's trace keeps no name-scope path on a
    ``while``), else the scope of the innermost enclosing op that has one
    (a copy XLA put into that loop), else None."""
    nodes = []                  # [start, end, name, self, parent, scope]
    stack = []
    for s, e, n in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and nodes[stack[-1]][1] <= s:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            nodes[parent][3] -= min(e, nodes[parent][1]) - s
        nodes.append([s, e, n, e - s, parent, own_scope(n)])
        stack.append(len(nodes) - 1)
    nested = [set() for _ in nodes]     # scopes held inside each op
    for i in range(len(nodes) - 1, -1, -1):     # children follow parents
        if nodes[i][5]:
            nested[i].add(nodes[i][5])
        if nodes[i][4] is not None:
            nested[nodes[i][4]] |= nested[i]
    for i, node in enumerate(nodes):
        if node[5] is None and len(nested[i]) == 1:
            node[5] = next(iter(nested[i]))
        if node[5] is None and node[4] is not None:
            node[5] = nodes[node[4]][5]
    return [(s, e, n, t, sc) for s, e, n, t, _, sc in nodes]


def _innermost(spans, t):
    best = None
    for s, e, n in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best


def reduce(path: str) -> Phases:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host = data.find_plane_with_name("/host:CPU")
    py = next((ev for ev in ([(e.start_ns, e.end_ns, e.name)
                              for e in l.events] for l in host.lines)
               if any(n == "bench.window" for _, _, n in ev)), None)
    if py is None:
        raise ValueError(f"{path}: no bench.window span")
    spans = sorted(x for x in py if x[2].startswith(SPAN_PREFIXES))
    lo, hi = next((s, e) for s, e, n in spans if n == "bench.window")
    devices = sorted((p for p in data.planes
                      if re.match(r"^/device:TPU:\d+$", p.name)),
                     key=lambda p: p.name)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    chip = devices[0]
    lines = {l.name: l for l in chip.lines}
    ops = [(e.start_ns, e.end_ns, e.name) for e in lines["XLA Ops"].events
           if e.end_ns > lo and e.start_ns < hi]

    # idle by the innermost span at each gap's midpoint
    merged = []
    for s, e, _ in sorted(ops):
        s, e = max(s, lo), min(e, hi)
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    inner = [x for x in spans if x[1] > lo and x[0] < hi]
    starts = [x[0] for x in inner]
    idle, idle_spans = collections.Counter(), collections.Counter()
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        span = _innermost(inner[:bisect.bisect_right(starts, mid)], mid)
        name = span[2] if span else None
        idle[layer(name)] += (b - a) * 1e-9
        idle_spans[name or "outside any span"] += (b - a) * 1e-9
    has_program = any(n == "engine.round" for _, _, n in inner)

    # kernel self time by scope
    mods = sorted((e.start_ns, e.end_ns, e.name.split("(", 1)[0])
                  for e in lines["XLA Modules"].events
                  if lo <= e.start_ns < hi)
    kmods = [(s, e) for s, e, n in mods if n == KERNEL_PROGRAM]
    kstarts = [s for s, _ in kmods]
    meta = xplane_meta.op_metadata(path).get(chip.name, {})
    own = {}

    def own_scope(n):
        if n not in own:
            own[n] = scope(meta.get(n, {}).get(PATH_STAT))
        return own[n]

    def in_kernel(t):
        i = bisect.bisect_right(kstarts, t) - 1
        return i >= 0 and t < kmods[i][1]

    scopes, unscoped = collections.Counter(), collections.Counter()
    for s, e, n, t, sc in scoped_self_times(
            [x for x in ops if in_kernel(x[0])], own_scope):
        # clip to the window like every other device time of the trace
        sec = t * (min(e, hi) - max(s, lo)) / (e - s) * 1e-9 if e > s \
            else 0.0
        scopes[sc or "other"] += sec
        if sc is None:
            unscoped[meta.get(n, {}).get("display_name", n[:40])] += sec
    scoped = any(own.values())
    for k in ("client", "server", "update", "gather", "other"):
        scopes.setdefault(k, 0.0)
    return Phases(
        idle=({k: idle.get(k, 0.0) for k in ("engine", "strategy",
                                               "harness")}
              if has_program else None),
        idle_spans=dict(idle_spans),
        scopes=dict(scopes) if scoped else None,
        kernel_executions=len(kmods),
        unscoped=dict(unscoped.most_common(8)))


def latest(ctx):
    """The window's phases, or None where the run has no reducible trace.
    Reduced once per run and kept on ``ctx``, which the per-layer readers
    share; logs each layer's idle seconds and each scope's seconds."""
    if not hasattr(ctx, "phases"):
        ctx.phases = _reduce_latest() if getattr(ctx, "trace", None) \
            else None
    return ctx.phases


def _reduce_latest():
    files = sorted(glob.glob(os.path.join(TRACE_DIR, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        return None
    t0 = time.perf_counter()
    try:
        ph = reduce(files[-1])
    except (ValueError, KeyError, StopIteration) as e:
        print(f"bench: phases not reduced: {e!r}", flush=True)
        return None
    top = sorted(ph.idle_spans.items(), key=lambda x: -x[1])[:12]
    print(f"bench: phases reduced in {time.perf_counter() - t0:.3f} s; "
          f"idle by layer {ph.idle} s; kernel self time by scope "
          f"{ph.scopes} s over {ph.kernel_executions} {KERNEL_PROGRAM} "
          f"executions, largest unscoped ops {ph.unscoped} s; idle by "
          f"innermost span {top}", flush=True)
    return ph


def per_round_ms(ctx, part, key):
    """``part`` ("idle" or "scopes") ``key`` of the window in ms per
    round, or None where it was not read."""
    ph = latest(ctx)
    values = getattr(ph, part, None) if ph else None
    if values is None or not ctx.rounds:
        return None
    return 1e3 * values[key] / ctx.rounds
