"""Device idle milliseconds per round whose gap midpoint has a
``strategy.*`` or ``cohort_kernel`` span as its innermost host span: the
strategy's init, split, stage, dispatch, publish, fuse, merge, fold and
aggregate (``bench/trace_phases.py``)."""

from bench import trace_phases


def read(ctx):
    return trace_phases.per_round_ms(ctx, "idle", "strategy")
