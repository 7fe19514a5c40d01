"""Device milliseconds of the cohort kernel's executions per round."""


def read(ctx):
    k = ctx.trace and ctx.trace.kernel_s
    if k is None or not ctx.rounds:
        return None
    return 1e3 * k / ctx.rounds
