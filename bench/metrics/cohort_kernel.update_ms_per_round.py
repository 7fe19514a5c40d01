"""Device self milliseconds per round of the cohort kernel's ops in the
name scope ``cohort_kernel.update``: the pooled server mean, both
optimizer steps, the depth freezes and the frozen-server selects
(``bench/trace_phases.py``)."""

from bench import trace_phases


def read(ctx):
    return trace_phases.per_round_ms(ctx, "scopes", "update")
