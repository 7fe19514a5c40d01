"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of op intervals) / window, mean over the chips."""


def read(ctx):
    if not ctx.trace or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
