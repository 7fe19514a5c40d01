"""Device milliseconds per round of every program outside the cohort
kernel: the strategy's eager fusion, scatters, broadcasts, aggregation."""


def read(ctx):
    o = ctx.trace and ctx.trace.other_s
    if o is None or not ctx.rounds:
        return None
    return 1e3 * o / ctx.rounds
