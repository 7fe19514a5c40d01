"""Host spans of the round, written into the profiler's own trace.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: with the
profiler on, it records ``name`` on the calling thread's host line, on the
same clock as the device's ``XLA Ops``, with ``meta`` as the event's
stats (not part of its name); with the profiler off it costs about a
microsecond. The module reads no clock of its own — the round path stays
deterministic (fleetlint FL004).

Span names (``docs/architecture.md``, "Tracing"): ``engine.*`` for the
engine's own phases, ``strategy.*`` for the strategy hooks and their
phases, and ``cohort_kernel`` around exactly one cohort-kernel dispatch.
"""
from __future__ import annotations

import jax


def span(name: str, **meta):
    """A context manager that records ``name`` (with ``meta`` as stats)
    while a ``jax.profiler`` trace is running, and nothing otherwise."""
    return jax.profiler.TraceAnnotation(name, **meta)
