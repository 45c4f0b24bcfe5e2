"""Fused step: median, over the window's decode steps, of the width of
the program's ``decode`` span: the step as the host waits for it (block
top-up, copy-on-write sweep, dispatch, and the wait for its first
readback), without the admissions that ``step_ms`` also holds."""
from bench.program_spans import window_spans
from bench.stats import nearest_rank


def read(ctx):
    widths = [s.dur for s in window_spans(ctx, "decode")]
    return 1e3 * nearest_rank(widths, 50) if widths else None
