"""The program's own spans as per-layer readers see them.

The serving loop's tracer (``ctx.driver.loop.tracer``) keeps Chrome
trace events: a ``B`` and an ``E`` per span, stamped on its clock in
microseconds.  The harness's tracer runs on ``time.perf_counter``, the
clock of the window, so a span and the window compare directly.  A loop
without a tracer, or a program that lacks a span, gives no spans: a
reader then reports nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional


@dataclasses.dataclass
class Span:
    name: str
    t0: float           # seconds, the tracer's clock
    t1: float
    args: dict
    tid: int

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


def closed_spans(events: Iterable[dict],
                 names: Optional[set] = None) -> List[Span]:
    """Every span whose ``B`` and ``E`` are both in ``events`` (spans of
    one track nest, so an ``E`` closes the latest open ``B``), in order
    of their ends; only those named in ``names`` where it is given."""
    open_: Dict[tuple, List[dict]] = {}
    out = []
    for e in events:
        ph = e.get("ph")
        key = (e.get("pid"), e.get("tid", 0))
        if ph == "B":
            open_.setdefault(key, []).append(e)
        elif ph == "E" and open_.get(key):
            b = open_[key].pop()
            if names is None or b["name"] in names:
                out.append(Span(b["name"], b["ts"] * 1e-6, e["ts"] * 1e-6,
                                b.get("args", {}), key[1]))
    return out


def program_events(ctx) -> List[dict]:
    """The trace events of the loop's tracer (none without one)."""
    tracer = getattr(ctx.driver.loop, "tracer", None)
    return list(getattr(tracer, "events", None) or [])


def window_spans(ctx, name: str) -> List[Span]:
    """Spans ``name`` that lie wholly inside the measured window."""
    w = ctx.window
    return [s for s in closed_spans(program_events(ctx), {name})
            if w.t0 <= s.t0 and s.t1 <= w.t1]
