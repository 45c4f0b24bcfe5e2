"""Scheduler and admission: median, over the admissions inside the
window, of the width of the program's ``admit`` span: the host time one
admission holds the loop (row reset, prefill dispatch, pool scatter,
drafter row, prefix registration)."""
from bench.program_spans import window_spans
from bench.stats import nearest_rank


def read(ctx):
    widths = [s.dur for s in window_spans(ctx, "admit")]
    return 1e3 * nearest_rank(widths, 50) if widths else None
