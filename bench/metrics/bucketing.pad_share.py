"""Padded bucket slots over all slots the window's cohort-kernel launches
carried, in %, counted at the harness's wrapper of ``engine.kernel_fn``."""


def read(ctx):
    if not ctx.slots:
        return None
    return 100.0 * (ctx.slots - ctx.real_slots) / ctx.slots
