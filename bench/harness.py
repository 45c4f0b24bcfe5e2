"""Everything one run of one cell does, as functions of the cell's data.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its files are
found by name: the configuration at the path its ``configs`` entry names,
the configuration's architecture module at
``bench/architectures/<architecture>.py`` (``dense`` where the file names
none) and its plain reference at ``bench/references/<reference>.py``,
the traffic mix at ``bench/traffic/<traffic>.json``, the limits of its
correctness check at ``bench/limits/<cell>.json`` and each per-layer
metric's reader at ``bench/metrics/<metric>.py`` (a ``name.twin`` metric
uses the reader of ``name``).  A later cell, mix, metric or architecture
is new files and entries; nothing here names one.

From the program the harness takes the system under test (``ServingLoop``
over a ``SpecEngine``), its spans and its counters.  It makes the
weights and the traffic itself, stamps every token on its own clock, and
judges the served tokens against a plain reference that imports nothing
of the program.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import stats
from bench.stats import Record
from bench.traffic import Traffic, load_mix

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent

WARMUP_NEW_TOKENS = 1       # a warm-up request ends after one step
SAMPLE_REQUESTS = 4         # finished requests the reference checks
DRAIN_LIMIT_S = 120.0       # open loop: longest wait for due requests
# host spans the harness opens, so that device idle time is charged to them
HARNESS_SPANS = {"bench.wait", "bench.driver"}


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    root = Path(root)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    work = [w for w in bm["workloads"] if w["name"] == name]
    if not work:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    w = work[0]
    conf = [c for c in bm["configs"] if c["name"] == w["config"]][0]
    config = json.loads((root / conf["file"]).read_text())
    mix = load_mix(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, config=config, mix=mix, chips=int(w["chips"]),
                end_to_end=[m for m in bm["end_to_end"] if mine(m)],
                per_layer=[m for m in bm["per_layer"] if mine(m)],
                limits=limits)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def _bench_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded from its file."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def architecture(config: dict):
    """The architecture module the configuration names (``dense`` where
    it names none): its weights, its keys of ``ModelConfig`` and the
    shapes the roofline readers count (``bench/architectures/dense.py``
    lists what such a module provides)."""
    return _bench_module("architectures",
                         config.get("architecture", "dense"))


def _as_registry(base, value):
    """``value`` in the type of the registry's ``base`` value (as stated
    where either is None)."""
    return value if value is None or base is None else type(base)(value)


def program_config(config: dict):
    """The program's ModelConfig for ``config``: the registry entry it
    names, with every size the configuration file states, through the
    map of its architecture module.  The file is the source: where the
    registry holds another size (its ``registry_differs`` records which),
    the file's is served.  A key of the ``model`` block that the module
    neither maps nor lists among its other keys is an error."""
    from repro.configs import get_config
    arch = architecture(config)
    base = get_config(config["registry"])
    model = config["model"]
    unknown = set(model) - set(arch.program_fields) - set(arch.other_keys)
    if unknown:
        raise ValueError(f"{config['name']}: model keys {sorted(unknown)} "
                         f"are neither program fields nor other keys of "
                         f"its architecture")
    over = {field: _as_registry(getattr(base, field), model[key])
            for key, field in arch.program_fields.items() if key in model}
    if model.get("hidden_act", "silu") != base.act or not base.glu:
        raise ValueError(f"{config['name']}: activation differs")
    serving = config["serving"]
    return dataclasses.replace(base, kv_cache_dtype=serving["kv_cache_dtype"],
                               **over)


def build_loop(config: dict, traffic: Traffic, weights, *, tracer=None,
               verifier: Optional[str] = None):
    """A ``ServingLoop`` serving ``weights`` as ``config`` states, sized
    by ``traffic``.  ``verifier`` overrides the configuration's (the
    lower-precision control)."""
    from repro.core.config import SpecConfig
    from repro.models import Model
    from repro.serving import ServerConfig
    from repro.serving.engine import SpecEngine
    from repro.serving.server import ServingLoop
    s = config["serving"]
    engine = SpecEngine(Model(program_config(config)), SpecConfig(
        gamma=s["gamma"], temperature=s["temperature"], drafter=s["drafter"],
        verifier=verifier or s["verifier"], kv_layout="paged",
        kv_block_size=s["kv_block_size"]))
    return ServingLoop(engine, weights, ServerConfig(
        batch_slots=traffic.slots, max_prompt_len=traffic.max_prompt_len,
        max_new_tokens=traffic.max_new_tokens), tracer=tracer)


class CompileCounter:
    """Counts JAX lowerings and backend compiles in this process (jitted
    programs and eagerly dispatched operations alike)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1


# ---------------------------------------------------------------------------
# Driving the loop
# ---------------------------------------------------------------------------

def _annotate(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Driver:
    """Submits requests to a ``ServingLoop`` and stamps, on the host clock
    after each poll returns, every token delta its stream delivered."""

    def __init__(self, loop, clock: Callable[[], float] = time.perf_counter):
        self.loop = loop
        self.clock = clock
        self.records: Dict[int, Record] = {}
        self.requests: Dict[int, object] = {}
        self.handles: Dict[int, object] = {}
        self.live: Dict[int, object] = {}
        self.seen: Dict[int, int] = {}
        self.lateness: List[float] = []
        self.step_rows: List[tuple] = []   # (t0, t1, [keys per row])

    def send(self, req, due_t: float) -> int:
        from repro.serving import GenerationRequest
        now = self.clock()
        h = self.loop.submit(GenerationRequest(
            req.prompt, req.max_new_tokens, seed=req.seed))
        self.records[h.rid] = Record(due_t=due_t)
        self.requests[h.rid] = req
        self.handles[h.rid] = h
        self.live[h.rid] = h
        self.seen[h.rid] = 0
        self.lateness.append(now - due_t)
        return h.rid

    def poll(self) -> List[int]:
        """One ``loop.poll()``; returns the rids that ended in it.  Also
        logs the cache positions each row's verify window attended in the
        step (committed context plus the window): the rows that held a
        slot before the poll, and the requests it admitted."""
        rows, T = [], 1
        for lane in self.loop._lanes.values():
            T = lane.drafter.gamma + 1
            rows += [int(lane.sched._row_len[ev.slot]) - 1 + T
                     for ev in lane.sched._slots if ev is not None]
        queued = [rid for rid, h in self.live.items() if h.status == "queued"]
        t = self.clock()
        self.loop.poll()
        now = self.clock()
        rows += [self.requests[rid].prompt.size - 1 + T for rid in queued
                 if self.handles[rid].status != "queued"]
        self.step_rows.append((t, now, rows))
        with _annotate("bench.driver"):
            return self._collect(now)

    def _collect(self, now: float) -> List[int]:
        ended = []
        for rid, h in list(self.live.items()):
            n = len(h.chunks)
            if n > self.seen[rid]:
                rec = self.records[rid]
                for c in h.chunks[self.seen[rid]:]:
                    rec.emits.append((now, int(c.size)))
                self.seen[rid] = n
            if h.status in ("done", "failed", "shed"):
                rec = self.records[rid]
                rec.done = h.status == "done"
                rec.failed = not rec.done
                del self.live[rid]
                ended.append(rid)
        return ended

    def served(self, rid: int) -> np.ndarray:
        h = self.handles[rid]
        return (np.concatenate(h.chunks) if h.chunks
                else np.zeros((0,), np.int32))

    def attach_admits(self) -> None:
        """Admission stamps from the program's per-request timelines."""
        for rid, rec in self.records.items():
            tl = self.loop.metrics.timelines.get(rid)
            if tl is not None:
                rec.admit_t = tl.admit_t


def warm_up(driver: Driver, traffic: Traffic) -> None:
    """Serve the warm-up requests to completion: compiles the decode step
    and every admission shape the traffic can use."""
    for r in traffic.warmup:
        driver.send(r, driver.clock())
    while driver.live:
        driver.poll()


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    due: List[int]              # rids due (or sent) inside the window
    drained_s: float = 0.0


def run_open(driver: Driver, traffic: Traffic, seconds: float,
             on_tick: Optional[Callable[[float], None]] = None) -> Window:
    """Open loop: each request is sent when it is due (or at the first
    moment after, if a step was running); after the window, the requests
    already due are served to completion."""
    reqs = traffic.requests
    clock = driver.clock
    t0 = clock()
    t1 = t0 + seconds
    due = []
    i = 0
    while True:
        now = clock()
        if on_tick is not None:
            on_tick(now - t0)
        while i < len(reqs) and t0 + reqs[i].due_s <= now:
            due.append(driver.send(reqs[i], t0 + reqs[i].due_s))
            i += 1
        if now >= t1:
            break
        if driver.live:
            driver.poll()
        else:
            nxt = t0 + reqs[i].due_s if i < len(reqs) else t1
            with _annotate("bench.wait"):
                time.sleep(max(0.0, min(nxt - now, 0.001)))
    td = clock()
    while driver.live and clock() - td < DRAIN_LIMIT_S:
        driver.poll()
    # a due request that never finished is one that never came
    for rid in list(driver.live):
        driver.records[rid].failed = True
        del driver.live[rid]
    driver.loop.shutdown()
    return Window(t0=t0, t1=t1, due=due, drained_s=clock() - td)


def ramp_closed(driver: Driver, traffic: Traffic) -> int:
    """Closed loop, in set-up: every client sends its first request, and
    the loop polls, a finished request's client sending its next, until
    every slot is taken or no request waits.  Returns the next index."""
    reqs = traffic.requests
    n = min(traffic.clients, len(reqs))
    for r in reqs[:n]:
        driver.send(r, driver.clock())
    while True:
        for _ in driver.poll():
            driver.send(reqs[n], driver.clock())
            n += 1
        busy = sum(ev is not None for lane in driver.loop._lanes.values()
                   for ev in lane.sched._slots)
        queued = any(h.status == "queued" for h in driver.live.values())
        if busy >= traffic.slots or not queued:
            return n


def run_closed(driver: Driver, traffic: Traffic, seconds: float, nxt: int,
               on_tick: Optional[Callable[[float], None]] = None) -> Window:
    """Closed loop: a finished request's client sends its next request
    at once.  The window ends at its time; the loop is then shut down."""
    reqs = traffic.requests
    clock = driver.clock
    t0 = clock()
    t1 = t0 + seconds
    due = []
    while clock() < t1:
        if on_tick is not None:
            on_tick(clock() - t0)
        for _ in driver.poll():
            if nxt >= len(reqs):
                raise RuntimeError("closed loop ran out of requests; "
                                   "raise the mix's rounds")
            due.append(driver.send(reqs[nxt], clock()))
            nxt += 1
    # requests still running when the window closes are cut, not failed
    driver.loop.shutdown()
    for rid in list(driver.live):
        del driver.live[rid]
    return Window(t0=t0, t1=t1, due=due)


# ---------------------------------------------------------------------------
# End-to-end metrics
# ---------------------------------------------------------------------------

_TAIL = re.compile(r"(ttft|tpot)_p(\d+)_ms")


def end_to_end(driver: Driver, win: Window, names: List[str]) -> dict:
    """The end-to-end metrics ``names`` (other than ``setup_s``) of this
    window: ``tokens_per_s``, the tokens delivered inside the window over
    its length, and ``ttft_p<q>_ms`` / ``tpot_p<q>_ms``, nearest-rank
    percentiles over the requests due in it (a failed one counts +inf).
    A metric ``base.twin`` is ``base``, split off for the cells it
    names."""
    recs = driver.records
    out = {}
    for name in names:
        base = name.split(".")[0]
        if base == "tokens_per_s":
            out[name] = (stats.tokens_in(recs.values(), win.t0, win.t1)
                         / (win.t1 - win.t0))
            continue
        m = _TAIL.fullmatch(base)
        if m is None:
            raise KeyError(f"no end-to-end metric {name!r}")
        out[name] = stats.tail_ms([getattr(recs[r], m.group(1))
                                   for r in win.due], int(m.group(2)))
    return out


# ---------------------------------------------------------------------------
# Correctness: served tokens against the plain reference
# ---------------------------------------------------------------------------

def sample_finished(driver: Driver, rids: List[int], seed: int,
                    k: int = SAMPLE_REQUESTS) -> List[int]:
    """Of ``rids``, the finished request with the most served tokens and
    ``k - 1`` more finished ones drawn from ``seed``."""
    done = [r for r in rids if driver.records[r].done]
    if not done:
        return []
    longest = max(done, key=lambda r: (driver.served(r).size, -r))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[int(i)] for i in sorted(pick)]


def reference_module(config: dict):
    return _bench_module("references", config["reference"])


def served_gaps(config: dict, weights, held: dict, traffic: Traffic) -> dict:
    """For each request in ``held`` (rid -> (request, served tokens)), how
    far each served token's logit lies below the reference's best.
    Returns rid -> (gaps, logit spreads)."""
    ref = reference_module(config)
    pad = traffic.max_prompt_len + traffic.max_new_tokens
    pad = -(-pad // 512) * 512
    return {rid: ref.served_gaps(weights, config["model"], req.prompt,
                                 served, pad, traffic.max_new_tokens)
            for rid, (req, served) in held.items()}


# ---------------------------------------------------------------------------
# Per-layer metric readers
# ---------------------------------------------------------------------------

def accept_totals(loop) -> tuple:
    """(tokens committed, row-steps) so far, from the program's
    acceptance counter."""
    a = loop.metrics.acceptance.summary()
    return (sum(e["committed_tokens"] for e in a.values()),
            sum(e["accept_len"]["n"] for e in a.values()))


@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader may read: the cell, the request records
    and per-step log, the window, the reduced trace and its host span,
    and the acceptance counter's change over the window."""

    cell: Cell
    driver: Driver
    window: Window
    trace: object
    span: object
    traffic: Traffic
    device_kind: str
    accept: tuple

    def traced_steps(self) -> List[List[int]]:
        """Per step dispatched in the traced stretch: positions each row
        attended."""
        return [rows for a, _, rows in self.driver.step_rows
                if self.span.t0 <= a < self.span.t1 and rows]

    def tokens_between(self, t0: float, t1: float) -> int:
        return stats.tokens_in(self.driver.records.values(), t0, t1)

    def prefilled_between(self, t0: float, t1: float) -> List[int]:
        """Prompt lengths of the requests admitted in ``[t0, t1)``."""
        return [self.driver.requests[rid].prompt.size
                for rid, rec in self.driver.records.items()
                if rec.admit_t is not None and t0 <= rec.admit_t < t1]


def reader(metric: str) -> Callable:
    """``bench/metrics/<base>.py``'s ``read`` for ``metric``; a metric
    ``base.twin`` uses the reader of ``base``."""
    return _bench_module("metrics", metric.split(".")[0]).read
