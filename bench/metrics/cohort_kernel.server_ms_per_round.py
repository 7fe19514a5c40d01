"""Device self milliseconds per round of the cohort kernel's ops in the
name scope ``tpgf.server``: the full-width server suffix's value-and-grad
(``bench/trace_phases.py``)."""

from bench import trace_phases


def read(ctx):
    return trace_phases.per_round_ms(ctx, "scopes", "server")
