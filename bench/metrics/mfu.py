"""Model FLOP/s utilisation of the whole round: the FLOPs the traced
window's rounds required (the family's ``round_work``,
``bench/families/``) over the window's seconds, the chips and their bf16
peak, in %."""


def read(ctx):
    if ctx.flops <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * ctx.flops / (ctx.window_s * ctx.chips
                                * ctx.peak["bf16_flops_per_s"])
