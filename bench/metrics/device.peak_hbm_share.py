"""``peak_bytes_in_use`` after the window over the chip's HBM, in %."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return 100.0 * ctx.memory_peak_bytes / ctx.peak["hbm_bytes"]
