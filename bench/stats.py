"""Latency and rate arithmetic of the benchmark, on host-clock stamps.

Kept apart from the program's own metrics so that a change to the
program cannot change how it is judged.  Percentiles are nearest-rank:
the smallest value with at least ``ceil(q / 100 * n)`` values at or
below it.  A request that failed, was shed or never finished counts as
``+inf``, so it misses every limit.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, List, Optional, Sequence, Tuple

INF = math.inf


@dataclasses.dataclass
class Record:
    """What the harness saw of one request, all on ``time.perf_counter``.

    ``due_t`` is when the request was due (open loop) or sent (closed
    loop); ``emits`` holds ``(t, n_tokens)`` for each delta the stream
    delivered; ``admit_t`` is when the server gave it a slot."""

    due_t: float
    emits: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    admit_t: Optional[float] = None
    done: bool = False
    failed: bool = False

    @property
    def n_tokens(self) -> int:
        return sum(n for _, n in self.emits)

    @property
    def ttft(self) -> float:
        if self.failed or not self.done or not self.emits:
            return INF
        return self.emits[0][0] - self.due_t

    @property
    def tpot(self) -> float:
        """(last token - first token) / (n - 1); a one-token answer has no
        gap and reads 0."""
        if self.failed or not self.done or not self.emits:
            return INF
        n = self.n_tokens
        if n < 2:
            return 0.0
        # the first delta may carry several tokens: the first token is
        # its first one, and the gap runs from that delta's stamp
        return (self.emits[-1][0] - self.emits[0][0]) / (n - 1)

    @property
    def queue_wait(self) -> float:
        if self.failed or self.admit_t is None:
            return INF
        return self.admit_t - self.due_t


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile ``q`` (0-100) of ``values``; NaN if empty."""
    if not values:
        return math.nan
    v = sorted(values)
    k = max(1, min(len(v), math.ceil(q / 100.0 * len(v))))
    return float(v[k - 1])


def tokens_in(records: Iterable[Record], t0: float, t1: float) -> int:
    """Output tokens delivered with a stamp in ``[t0, t1)``."""
    return sum(n for r in records for t, n in r.emits if t0 <= t < t1)


def tail_ms(values: Sequence[float], q: float = 95.0) -> float:
    return nearest_rank(values, q) * 1e3
