"""Share of its roofline the cohort kernel reaches, in %: the least time
the chip could take for the work the window's rounds required — the larger
of required FLOPs over the bf16 peak and required bytes over HBM bandwidth
(the family's ``round_work``, ``bench/families/``; FLOPs set it in the
benchmark's cells) — over the device time of the kernel's executions."""


def read(ctx):
    k = ctx.trace and ctx.trace.kernel_s
    if not k:
        return None
    least = max(ctx.flops / ctx.peak["bf16_flops_per_s"],
                ctx.bytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / k
