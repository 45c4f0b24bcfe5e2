"""Fused step: tokens committed per row and decode step over the window
(the program's acceptance counter, read at the window's open and
close)."""


def read(ctx):
    tokens, row_steps = ctx.accept
    return tokens / row_steps if row_steps > 0 else None
