"""Device program executions per round, from the trace: the cohort
kernels plus every eager op the strategy dispatches."""


def read(ctx):
    if not ctx.rounds or not ctx.trace or not ctx.trace.executions:
        return None
    return ctx.trace.executions / ctx.rounds
