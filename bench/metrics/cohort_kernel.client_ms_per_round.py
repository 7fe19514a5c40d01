"""Device self milliseconds per round of the cohort kernel's ops in the
name scope ``tpgf.client``: the client prefix forward, both prefix backward passes,
the local head, clip and client fusion (``bench/trace_phases.py``)."""

from bench import trace_phases


def read(ctx):
    return trace_phases.per_round_ms(ctx, "scopes", "client")
