"""Device idle milliseconds per round whose gap midpoint has an
``engine.*`` span as its innermost host span: the engine's draws, cohort
loop and accounting (``bench/trace_phases.py``)."""

from bench import trace_phases


def read(ctx):
    return trace_phases.per_round_ms(ctx, "idle", "engine")
