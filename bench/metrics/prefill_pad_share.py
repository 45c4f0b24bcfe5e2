"""Scheduler and admission: share of the rows the admission prefill
computes that are padding.  The serving lane pads every prompt to its
cap, so a prompt of P tokens costs ``pmax - 1`` forward rows for
``P - 1`` useful ones.  Read from the program's ``prefill`` spans in the
window: 100 x the sum of ``padded_rows`` over the sum of ``padded_rows``
and ``cold_rows``.  A program whose spans do not count padding reports
nothing."""
from bench.program_spans import window_spans


def read(ctx):
    spans = window_spans(ctx, "prefill")
    if not spans or any("padded_rows" not in s.args for s in spans):
        return None
    padded = sum(int(s.args["padded_rows"]) for s in spans)
    total = padded + sum(int(s.args.get("cold_rows", 0)) for s in spans)
    return 100.0 * padded / total if total else None
