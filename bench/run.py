"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights on the device from the seed, builds the serving
loop the cell's configuration states, and serves warm-up requests so
that every program the window uses is compiled (kept in JAX's persistent
cache in the checkout).  The window then drives the cell's traffic for
``--seconds``: an open loop sends each request when it is due, a closed
loop keeps its clients' requests outstanding.  Afterwards the program's
state is freed and a plain float32 reference checks the served tokens of
a sample of finished requests.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of a steady part of the window gives its
per-layer metrics.  Progress and the numbers compared go to standard
error; the last line of standard output is one JSON object.  The run
exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where anything compiled inside the window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_AT = 0.4           # traced part of the window: starts at this share
TRACE_SECONDS = 4.0      # ... and lasts this long (at most a third)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class TracedSpan:
    """Profiles one stretch of the window: from the first tick ``at``
    seconds or more after it opens, for ``seconds`` from when the
    profiler has started; call :meth:`tick` as the window runs.  Ticks
    fall between the loop's polls, and one poll can admit requests for
    seconds, so the stretch is timed from its own start: timed from
    ``at``, a late first tick left a stretch one poll long."""

    def __init__(self, at: float, seconds: float):
        self.at, self.seconds = at, seconds
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state = "waiting"
        self.t0 = self.t1 = None
        self._ann = None

    def tick(self, elapsed: float) -> None:
        import jax
        if self.state == "waiting" and elapsed >= self.at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans, not every call
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.traced")
            self._ann.__enter__()
            self.t0 = time.perf_counter()
            self.state = "tracing"
        elif (self.state == "tracing"
              and time.perf_counter() - self.t0 >= self.seconds):
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state != "tracing":
            return
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def file(self) -> Path:
        return sorted(Path(self.dir).rglob("*.xplane.pb"))[-1]

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def check_devices(chips: int):
    """The TPU devices this run may use; raises where there are none or
    too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell asks for {chips} chips; JAX found "
                           f"{len(devs)}")
    return devs[:chips]


def run_once(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START, verifier=None,
             after_setup=None) -> dict:
    """One run of ``cell``; returns the result object (see module doc).
    ``verifier`` replaces the configuration's (the lower-precision
    control); ``after_setup(loop)`` runs at the end of set-up (the tests
    break the timed path there)."""
    from bench import harness as H
    from bench.traffic import generate
    from bench.weights import make_weights

    counter = H.CompileCounter()
    model = cell.config["model"]
    weights = make_weights(cell.config, seed)
    traffic = generate(cell.mix, seed, model["vocab_size"], seconds,
                       warmup_new_tokens=H.WARMUP_NEW_TOKENS)
    tracer = None
    if trace:
        from repro.serving.trace import Tracer
        tracer = Tracer(annotate_device=True)
    loop = H.build_loop(cell.config, traffic, weights, tracer=tracer,
                        verifier=verifier)
    driver = H.Driver(loop)
    H.warm_up(driver, traffic)
    warm = set(driver.records)
    nxt = H.ramp_closed(driver, traffic) if traffic.kind == "closed" else 0
    if after_setup is not None:
        after_setup(loop)
    compiles0, traces0 = counter.count, loop.engine.step_traces
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.2f}s: {len(warm)} warm-up requests, "
        f"{compiles0} lowerings, {traces0} step traces")

    span = None
    if trace:
        at = TRACE_AT * seconds
        span = TracedSpan(at, min(TRACE_SECONDS, seconds / 3))
    tick = span.tick if span is not None else None
    acc0 = H.accept_totals(loop)
    if traffic.kind == "open":
        win = H.run_open(driver, traffic, seconds, on_tick=tick)
    else:
        win = H.run_closed(driver, traffic, seconds, nxt, on_tick=tick)
    if span is not None:
        span.stop()
    acc1 = H.accept_totals(loop)
    in_window = counter.count - compiles0
    step_traces = loop.engine.step_traces - traces0
    if in_window or step_traces:
        raise RuntimeError(f"{in_window} lowerings and {step_traces} step "
                           f"traces inside the window: set-up missed a "
                           f"shape")
    stats_mem = devices[0].memory_stats() or {}
    memory_peak = int(stats_mem.get("peak_bytes_in_use", 0))
    driver.attach_admits()
    window_rids = [r for r in driver.records if r not in warm]
    failed = sum(driver.records[r].failed for r in window_rids)
    lat = sorted(driver.lateness[len(warm):])
    log(f"window {seconds:.0f}s: {len(win.due)} requests due or sent, "
        f"{failed} failed, drain {win.drained_s:.2f}s; generator lateness "
        f"p50 {_q(lat, 50) * 1e3:.1f} ms, p95 {_q(lat, 95) * 1e3:.1f} ms, "
        f"max {(lat[-1] if lat else 0) * 1e3:.1f} ms")

    metrics = {}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    breakdown = ctx = None
    if trace:
        from bench.trace_reduce import reduce_trace
        names = {e["name"] for e in tracer.events if e.get("ph") == "B"}
        red = reduce_trace(span.file(), names | H.HARNESS_SPANS)
        ctx = H.MetricContext(cell=cell, driver=driver, window=win,
                              trace=red, span=span, traffic=traffic,
                              device_kind=devices[0].device_kind,
                              accept=(acc1[0] - acc0[0], acc1[1] - acc0[1]))
        for m in cell.per_layer:
            v = H.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        span.cleanup()
        log(f"traced {red.window_s:.3f}s: device busy {red.busy_s:.3f}s; "
            f"top ops {breakdown['device_ops'][:3]}")
    else:
        e2e = H.end_to_end(driver, win, [m["name"] for m in cell.end_to_end
                                         if m["name"] != "setup_s"])
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        log(f"requests due in the window: {len(win.due)}; "
            + ", ".join(f"{k} {v:.4f}" for k, v in e2e.items()))

    # the reference runs once the program's state is gone
    sample = H.sample_finished(driver, window_rids, seed)
    held = {r: (driver.requests[r], driver.served(r)) for r in sample}
    # stream handles point at the loop, and the loop at the pool
    driver.handles.clear()
    driver.loop = ctx = span = None
    del loop, driver
    import gc
    gc.collect()
    t_ref = time.perf_counter()
    gaps = H.served_gaps(cell.config, weights, held, traffic)
    n_tok = sum(g.size for g, _ in gaps.values())
    worst = max(float(g.max()) for g, _ in gaps.values()) if n_tok else None
    log(f"reference: {len(sample)} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t_ref:.2f}s")

    limit = float(cell.limits["served_gap_max"]["limit"])
    checks = {
        "served_gap_max": {"value": worst, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "checked_tokens": {"value": n_tok, "limit": 1},
    }
    correct = (n_tok >= 1 and worst <= limit and failed == 0)
    result = {"correct": bool(correct), "attempted": len(window_rids),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return result


def _finite(obj):
    """``obj`` with every non-finite number (a latency of a request that
    never came) as null, so that the line stays JSON."""
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def _q(values, q):
    from bench.stats import nearest_rank
    v = nearest_rank(values, q)
    return 0.0 if v != v else v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.harness import load_cell
    cell = load_cell(args.workload)
    try:
        devices = check_devices(cell.chips)
    except RuntimeError as exc:
        log(f"bench/run.py: {exc}")
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = run_once(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
