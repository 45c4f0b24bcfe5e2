"""Scheduler and admission: device programs one admission runs.  In the
traced stretch, the program executions on the device (line ``XLA
Modules``, the decode step left out) that start inside a host ``admit``
event, over the admissions that lie wholly inside the stretch; nothing
where none does.  Eager admission dispatches one program per operation,
so this counts what jitting admission would fold into a few."""
import bisect

from bench.trace_reduce import STEP_MODULE, TRACED_SPAN, _base, load


def per_admission(modules, host):
    """Programs per admission over the ``bench.traced`` host span of a
    trace given as its module executions and host events."""
    traced = [h for h in host if h.name == TRACED_SPAN]
    if not traced:
        return None
    lo, hi = traced[0].start, traced[0].end
    admits = [(h.start, h.end) for h in host
              if h.name == "admit" and lo <= h.start and h.end <= hi]
    if not admits:
        return None
    starts = sorted(m.start for m in modules
                    if _base(m.name) != STEP_MODULE)
    n = sum(bisect.bisect_right(starts, b) - bisect.bisect_left(starts, a)
            for a, b in admits)
    return n / len(admits)


def read(ctx):
    if ctx.trace is None or ctx.span is None:
        return None
    _, modules, host = load(ctx.span.file())
    return per_admission(modules, host)
