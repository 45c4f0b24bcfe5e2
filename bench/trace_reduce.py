"""Reduce a JAX profiler trace (``.xplane.pb``) of one traced stretch of a
run to the numbers the per-layer readers use.

    python3 bench/trace_reduce.py <file.xplane.pb>     # print the structure

The stretch is the host span ``bench.traced`` that the harness opens and
closes around it.  On the device plane (``/device:TPU:0``):

* busy time is the union of the intervals of the device's operations
  (line ``XLA Ops``) inside the stretch; idle share is the rest;
* each program execution (line ``XLA Modules``) is the decode step when
  its module is the decode step's (``STEP_MODULE``), and otherwise one of
  the eager programs admission and the block-table patches dispatch;
* a kernel's calls are the custom calls whose op name or metadata names
  the kernel, inside decode-step executions;
* each idle gap is charged to the innermost host span that covers its
  middle, among the spans the program's tracer and the harness open.

Times are seconds.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

STEP_MODULE = "jit_counted"     # the serving lane's jitted decode step
KERNELS = ("flash_decode_paged", "int8_matmul", "smooth_quant")
TRACED_SPAN = "bench.traced"


@dataclasses.dataclass
class Ev:
    name: str
    start: float        # seconds, trace time
    end: float
    stats: Dict[str, str]

    @property
    def dur(self) -> float:
        return self.end - self.start


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by ``intervals``."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def idle_gaps(intervals: Iterable[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    gaps, t = [], lo
    for a, b in sorted(intervals):
        if a > t:
            gaps.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(a, b) for a, b in gaps if b > a]


def clip(evs: Sequence[Ev], lo: float, hi: float) -> List[Ev]:
    out = []
    for e in evs:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append(dataclasses.replace(e, start=a, end=b))
    return out


def _base(name: str) -> str:
    """A program's or an op's name without its instance number:
    ``jit_counted(7)`` -> ``jit_counted``; ``%fusion.12 = bf16[..] ...``
    (an HLO instruction) -> ``fusion``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[.\d]+$", "", name) or name


def kernel_of(e: Ev) -> Optional[str]:
    """The kernel a device op is a call of, or None.  A kernel call is a
    custom call whose name or metadata names one of ``KERNELS``."""
    text = " ".join([e.name, *e.stats.values()])
    if "custom" not in text and "pallas" not in text and \
            "mosaic" not in text.lower():
        return None
    for k in KERNELS:
        if k in text:
            return k
    return None


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    step_count: int
    other_module_busy_s: float
    kernels: Dict[str, Tuple[int, float]]        # name -> (calls, seconds)
    ops: List[Tuple[str, float]]                  # (label, seconds), sorted
    gaps: List[Tuple[str, float]]                 # (host span, seconds)

    def kernel_calls(self, name: str) -> int:
        return self.kernels.get(name, (0, 0.0))[0]

    def kernel_s(self, name: str) -> float:
        return self.kernels.get(name, (0, 0.0))[1]

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def reduce_events(device_ops: List[Ev], modules: List[Ev], host: List[Ev],
                  lo: float, hi: float,
                  span_names: Optional[set] = None) -> Reduced:
    """Reduce the device's ops and program executions and the host's
    spans (all in one time base) over ``[lo, hi]``."""
    ops = clip(device_ops, lo, hi)
    mods = clip(modules, lo, hi)
    busy = union_length((e.start, e.end) for e in ops)
    steps = [m for m in mods if _base(m.name) == STEP_MODULE]
    others = [m for m in mods if _base(m.name) != STEP_MODULE]
    mod_iv = sorted((m.start, m.end, _base(m.name)) for m in mods)
    starts = [a for a, _, _ in mod_iv]

    def module_of(e: Ev) -> str:
        """The program an op ran in: its ``hlo_module`` stat where the
        trace gives one, else the execution whose interval holds it."""
        mod = e.stats.get("hlo_module")
        if mod is not None:
            return _base(mod)
        i = bisect.bisect_right(starts, e.start) - 1
        return mod_iv[i][2] if i >= 0 and mod_iv[i][1] >= e.end else "?"

    kern: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    by_label: Dict[str, float] = collections.defaultdict(float)
    for e in ops:
        k = kernel_of(e)
        mod = module_of(e)
        if k is not None and mod == STEP_MODULE:
            kern[k][0] += 1
            kern[k][1] += e.dur
        label = f"{mod}/{k}" if k else f"{mod}/{_base(e.name)}"
        by_label[label] += e.dur
    gaps = idle_gaps(((e.start, e.end) for e in ops), lo, hi)
    spans = sorted((h for h in host if h.name != TRACED_SPAN
                    and (span_names is None or h.name in span_names)
                    and h.end > lo and h.start < hi), key=lambda h: h.start)
    charged: Dict[str, float] = collections.defaultdict(float)
    open_spans: List[Ev] = []        # spans begun before the current gap
    j = 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (a + b)
        while j < len(spans) and spans[j].start <= mid:
            open_spans.append(spans[j])
            j += 1
        open_spans = [h for h in open_spans if h.end >= mid]
        name = (min(open_spans, key=lambda h: h.dur).name if open_spans
                else "no span")
        charged[name] += b - a
    return Reduced(
        window_s=hi - lo, busy_s=busy, step_count=len(steps),
        other_module_busy_s=union_length((m.start, m.end) for m in others),
        kernels={k: (int(v[0]), float(v[1])) for k, v in kern.items()},
        ops=sorted(by_label.items(), key=lambda kv: -kv[1]),
        gaps=sorted(charged.items(), key=lambda kv: -kv[1]))


def _events(line) -> List[Ev]:
    out = []
    for e in line.events:
        st = {str(k): str(v) for k, v in e.stats}
        out.append(Ev(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9, st))
    return out


def load(path: Path):
    """(device ops, device program executions, host events) of the first
    TPU device plane and the host planes of ``path``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, mods, host = [], [], []
    dev = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and dev is None:
            dev = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    if dev is None:
        raise ValueError(f"{path}: no TPU device plane")
    for line in dev.lines:
        if line.name == "XLA Ops":
            ops = _events(line)
        elif line.name == "XLA Modules":
            mods = _events(line)
    return ops, mods, host


def reduce_trace(path: Path, span_names: Optional[set] = None) -> Reduced:
    """Reduce the trace at ``path`` over its ``bench.traced`` span."""
    ops, mods, host = load(path)
    traced = [h for h in host if h.name == TRACED_SPAN]
    if not traced:
        raise ValueError(f"{path}: no {TRACED_SPAN!r} host span")
    lo, hi = traced[0].start, traced[0].end
    return reduce_events(ops, mods, host, lo, hi, span_names)


def describe(path: Path) -> None:
    """Print the planes, lines and the most common events of a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = _events(line)
            c = collections.Counter(_base(e.name) for e in evs)
            print("  line", line.name, len(evs), c.most_common(8))
            for e in evs[:2]:
                print("    e.g.", e.name, round(e.dur * 1e6, 2), "us",
                      dict(list(e.stats.items())[:8]))


if __name__ == "__main__":
    describe(Path(sys.argv[1]))
