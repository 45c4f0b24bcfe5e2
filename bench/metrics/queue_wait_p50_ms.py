"""Front end: median, over the requests due in the window, of the time
from when a request was due to when the server gave it a slot (the
program's admission stamp).  A request never admitted counts +inf."""
from bench.stats import tail_ms


def read(ctx):
    recs = [ctx.driver.records[r] for r in ctx.window.due]
    return tail_ms([r.queue_wait for r in recs], 50) if recs else None
