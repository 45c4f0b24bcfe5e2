"""Fused step: the traced window's length over the decode steps the
device ran in it, host work between the steps included."""


def read(ctx):
    t = ctx.trace
    if t is None or t.step_count == 0:
        return None
    return 1e3 * t.window_s / t.step_count
