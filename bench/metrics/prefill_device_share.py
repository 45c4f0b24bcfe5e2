"""Scheduler and admission: share of the traced window in which the
device ran a program other than the decode step: the eager admission
prefill and its scatters into the pool, and the block-table patches."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or t.step_count == 0:
        return None
    return 100.0 * t.other_module_busy_s / t.window_s
