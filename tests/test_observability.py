"""Observability harness: tracing determinism, bounded histograms,
acceptance/KV-cache telemetry (docs/observability.md).

Four layers:

1. **Units** — ceil-based nearest-rank ``percentile`` pins; histogram
   exactness at the edges (single sample, min/max/mean) and input
   validation; tracer event-cap discipline (a capped trace stays
   structurally valid) and deterministic serialisation.
2. **Properties** (hypothesis) — histogram ``merge`` is exactly
   equivalent to single-pass ingestion of the concatenated samples, and
   quantile estimates stay within one bucket's relative width
   (``growth``) of the exact nearest-rank value.
3. **Validator** — ``tools/check_trace.py`` accepts every trace the
   serving stack emits and rejects unmatched/misnested/retrograde
   structures.
4. **End-to-end** — two identical virtual-clock ``serve_load`` replays
   over a preempting paged lane serialize **byte-identical** Perfetto
   traces containing request-lifecycle, decode, and preempt/swap spans;
   ``ServerMetrics.summary()`` carries populated ``acceptance`` and
   ``kv_cache`` sections with memory bounded in the request count; and
   generated tokens are bit-identical with tracing enabled vs disabled.
5. **Shared clock** — a paged ``ServingLoop`` served under
   ``jax.profiler`` with ``Tracer(annotate_device=True)``: every span
   lands in the profile as a host event with its name and arguments, at
   one fixed offset from the tracer's clock; admission and the decode
   tick nest as documented; the padded-prefill counter matches the
   prompts.
"""
import math
import os
import sys

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from _hypothesis_compat import given, settings, st
from repro.serving import GenerationRequest, ServerMetrics, Tracer
from repro.serving.histogram import Histogram
from repro.serving.metrics import percentile
from repro.serving.trace import NULL_TRACER
from tools.check_trace import validate


# ---------------------------------------------------------------------------
# percentile: explicit ceil-based nearest-rank
# ---------------------------------------------------------------------------

def test_percentile_nearest_rank_pins():
    # p50 of an even-length list is the n/2-th order statistic — the
    # banker's-rounding bug returned 3 here
    assert percentile([1, 2, 3, 4], 50) == 2.0
    assert percentile([4, 3, 2, 1], 50) == 2.0          # order-free
    assert percentile([1, 2, 3, 4], 99) == 4.0
    assert percentile([1, 2, 3, 4], 100) == 4.0
    assert percentile([1, 2, 3, 4], 0) == 1.0           # k clamps to 1
    assert percentile([1, 2, 3], 50) == 2.0
    assert percentile([5], 50) == 5.0
    assert percentile([1, 2], 50) == 1.0                # ceil(0.5*2)=1
    assert math.isnan(percentile([], 50))


# ---------------------------------------------------------------------------
# Histogram units
# ---------------------------------------------------------------------------

def test_histogram_single_sample_exact():
    for v in (1e-9, 0.0017, 1.0, 3.14, 9e6, 1e12):   # incl. under/overflow
        h = Histogram()
        h.add(v)
        s = h.summary()
        assert s["n"] == 1
        assert s["mean"] == pytest.approx(v)
        assert s["p50"] == pytest.approx(v)          # clamped to [vmin,vmax]
        assert s["p99"] == pytest.approx(v)
        assert s["max"] == v


def test_histogram_empty_and_invalid():
    h = Histogram()
    assert h.summary() == {"n": 0}
    assert math.isnan(h.percentile(50))
    with pytest.raises(ValueError):
        h.add(-0.5)
    with pytest.raises(ValueError):
        h.add(float("nan"))
    with pytest.raises(ValueError):
        Histogram(min_value=0.0)
    with pytest.raises(ValueError):
        h.merge(Histogram(growth=2.0))
    h.add(1.0, n=0)                                  # no-op, not an error
    assert h.count == 0


def test_histogram_bounded_buckets():
    h = Histogram()
    rng = np.random.default_rng(0)
    for v in rng.lognormal(0.0, 4.0, size=20000):
        h.add(float(v))
    assert h.count == 20000
    assert len(h) <= h.max_buckets
    d = h.to_dict()
    assert sum(d["counts"]) == 20000
    assert len(d["le"]) == len(d["counts"]) == len(h)


def test_server_metrics_memory_flat_without_timelines():
    """keep_timelines=False really is O(1) per request now: no raw
    latency lists, timelines dropped on fold, histograms bucket-bounded."""
    m = ServerMetrics(keep_timelines=False)
    rng = np.random.default_rng(1)
    n = 500
    for rid in range(n):
        t0 = float(rid)
        m.on_submit(rid, t0, deadline_t=t0 + 2.0)
        m.on_admit(rid, t0 + float(min(rng.exponential(0.1), 0.25)))
        m.on_tokens(rid, t0 + 0.3, 4)
        m.on_tokens(rid, t0 + 0.5, 4)
        m.on_finish(rid, t0 + 0.6)
    m.check_conservation()
    assert not m.timelines                       # nothing retained
    for h in (m._ttft, m._itl, m._queue, m._service):
        assert isinstance(h, Histogram) and len(h) <= h.max_buckets
    s = m.summary()
    assert s["latency"]["ttft_s"]["n"] == n
    assert s["deadlines"]["with_deadline"] == n


def test_server_metrics_single_sample_latency_exact():
    m = ServerMetrics()
    m.on_submit(0, 10.0)
    m.on_admit(0, 11.0)
    m.on_tokens(0, 11.5, 2)
    m.on_finish(0, 12.0)
    s = m.summary()
    assert s["latency"]["queue_s"]["p50"] == pytest.approx(1.0)
    assert s["latency"]["ttft_s"]["p50"] == pytest.approx(1.5)
    assert s["latency"]["service_s"]["max"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Histogram properties (hypothesis)
# ---------------------------------------------------------------------------

_vals = st.lists(st.floats(min_value=1e-3, max_value=1e6,
                           allow_nan=False, allow_infinity=False),
                 min_size=1, max_size=80)


@given(a=_vals, b=_vals)
@settings(max_examples=80, deadline=None)
def test_histogram_merge_equals_single_pass(a, b):
    h1, h2, ref = Histogram(), Histogram(), Histogram()
    h1.extend(a)
    h2.extend(b)
    ref.extend(a + b)
    h1.merge(h2)
    assert h1.buckets == ref.buckets
    assert h1.count == ref.count
    assert h1.vmin == ref.vmin and h1.vmax == ref.vmax
    assert h1.total == pytest.approx(ref.total, rel=1e-9)
    for q in (50, 99):
        assert h1.percentile(q) == ref.percentile(q)


@given(vals=_vals, q=st.integers(1, 100))
@settings(max_examples=80, deadline=None)
def test_histogram_percentile_within_one_bucket(vals, q):
    """The bucket holding the exact k-th order statistic represents it:
    the estimate is within one bucket's relative width (× growth)."""
    h = Histogram()
    h.extend(vals)
    exact = percentile(vals, q)
    est = h.percentile(q)
    assert exact / h.growth <= est <= exact * h.growth


# ---------------------------------------------------------------------------
# Tracer units
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.5
        return self.t


def _scripted_trace(tracer):
    tracer.thread_name(0, "lane0")
    tracer.begin_async("queued", 7, rid=7)
    with tracer.span("tick", tid=0, step=0):
        with tracer.span("decode", tid=0, rows=2):
            pass
        tracer.counter("occupancy", 2, tid=0)
    tracer.end_async("queued", 7)
    tracer.instant("shed", tid=0, rid=9)


def test_tracer_deterministic_dumps():
    t1, t2 = Tracer(clock=_FakeClock()), Tracer(clock=_FakeClock())
    _scripted_trace(t1)
    _scripted_trace(t2)
    assert t1.dumps() == t2.dumps()              # byte-identical
    assert validate(t1.export()) == []


def test_tracer_event_cap_keeps_structure():
    """Once full, new begins are dropped (and counted) but recorded
    spans still close: the capped trace passes structural validation."""
    t = Tracer(clock=_FakeClock(), max_events=6)
    for i in range(5):
        t.begin_async("queued", i)
        with t.span("tick", tid=0):
            with t.span("decode", tid=0):
                pass
        t.end_async("queued", i)
    assert t.dropped > 0
    assert validate(t.export()) == []
    # an end whose begin was dropped is skipped, not emitted unbalanced
    t.end_async("queued", 4999)
    assert validate(t.export()) == []


def test_null_tracer_is_inert():
    with NULL_TRACER.span("tick", tid=3, step=1):
        NULL_TRACER.counter("occupancy", 1)
    NULL_TRACER.begin_async("queued", 0)
    NULL_TRACER.end_async("queued", 0)
    NULL_TRACER.instant("shed")
    NULL_TRACER.thread_name(0, "x")
    assert not NULL_TRACER.enabled


def test_check_trace_rejects_malformed():
    base = {"pid": 1, "tid": 0}
    # E with no open B
    assert validate([{**base, "ph": "E", "name": "x", "ts": 1.0}])
    # bad nesting: E closes the wrong span
    assert validate([
        {**base, "ph": "B", "name": "a", "ts": 1.0},
        {**base, "ph": "B", "name": "b", "ts": 2.0},
        {**base, "ph": "E", "name": "a", "ts": 3.0},
        {**base, "ph": "E", "name": "b", "ts": 4.0},
    ])
    # retrograde timestamps on one track
    assert validate([
        {**base, "ph": "B", "name": "a", "ts": 5.0},
        {**base, "ph": "E", "name": "a", "ts": 1.0},
    ])
    # unclosed B at EOF
    assert validate([{**base, "ph": "B", "name": "a", "ts": 1.0}])
    # async end with no begin
    assert validate([{**base, "ph": "e", "cat": "request", "id": 3,
                      "name": "queued", "ts": 1.0}])
    # counter args must be finite numbers
    assert validate([{**base, "ph": "C", "name": "occ", "ts": 1.0,
                      "args": {"v": float("nan")}}])
    assert validate({"notTraceEvents": []})
    # and the empty trace is fine
    assert validate({"traceEvents": []}) == []


# ---------------------------------------------------------------------------
# End-to-end: serve_load replay determinism + telemetry
# ---------------------------------------------------------------------------

from benchmarks import serve_load  # noqa: E402


# victim with loose deadline fills the 10-block pool; later tight-
# deadline arrivals out-key it under EDF and must preempt it to the
# swap pool (verified: preemptions >= 1 below)
_PREEMPT_TRACE = [
    {"arrival_s": 0.0, "prompt_reps": 6, "max_new_tokens": 16,
     "deadline_s": 60.0, "seed": 1},
    {"arrival_s": 0.6, "prompt_reps": 2, "max_new_tokens": 8,
     "deadline_s": 2.0, "seed": 2},
    {"arrival_s": 0.7, "prompt_reps": 2, "max_new_tokens": 8,
     "deadline_s": 2.5, "seed": 3},
]


@pytest.fixture(scope="module")
def paged_engine():
    return serve_load._build_engine(smoke=True, paged=True)


def _traced_replay(engine, params):
    clock = serve_load.VirtualClock()
    tracer = Tracer(clock=clock.read)
    summary = serve_load.replay(engine, params, _PREEMPT_TRACE,
                                admission="edf", shed=False,
                                clock=clock, tracer=tracer)
    return summary, tracer


def test_replay_traces_byte_identical_with_preempt_spans(paged_engine):
    engine, params = paged_engine
    s1, t1 = _traced_replay(engine, params)
    s2, t2 = _traced_replay(engine, params)

    # two identical virtual-clock replays: byte-identical Perfetto JSON
    assert t1.dumps() == t2.dumps()
    assert validate(t1.export()) == []

    names = {e["name"] for e in t1.events}
    # request lifecycle + per-step + preempt/swap span taxonomy
    assert {"queued", "running", "preempted",          # lifecycle (async)
            "tick", "admit", "decode", "harvest",      # per-tick phases
            "prefill", "append_blocks",                # paged data plane
            "preempt", "swap_out", "swap_in"} <= names

    # the preempted lifecycle phase balances (ended on resume)
    opened = sum(1 for e in t1.events
                 if e["ph"] == "b" and e["name"] == "preempted")
    closed = sum(1 for e in t1.events
                 if e["ph"] == "e" and e["name"] == "preempted")
    assert opened == closed >= 1

    kv = s1["kv_cache"]
    assert kv["preemptions"] >= 1
    assert kv["swap_out_blocks"] >= 1
    assert kv["swap_in_blocks"] == kv["swap_out_blocks"]
    assert kv["swap_out_bytes"] > 0 and kv["swap_in_bytes"] > 0
    assert kv["prefix_hits"] >= 1                  # the shared family
    assert kv["cow_forks"] >= 1
    assert kv["prefix_hit_rate"] == pytest.approx(
        kv["prefix_hits"] / (kv["prefix_hits"] + kv["prefix_misses"]))
    assert kv["pools"]                             # per-lane gauges

    acc = s1["acceptance"]
    assert "ngram:bf16" in acc
    e = acc["ngram:bf16"]
    assert e["steps"] == s1["counters"]["decode_steps"]
    assert e["accept_len"]["n"] >= e["steps"]
    # every streamed token was counted as an accepted commit
    assert e["committed_tokens"] == s1["counters"]["stream_tokens"]
    # virtual clock: each step costs exactly the modeled step time
    assert e["step_s"]["max"] == pytest.approx(serve_load.STEP_COST_S)

    # the two replays agree on every aggregate, not just the trace
    assert s1 == s2


def test_generation_bit_identical_tracing_on_vs_off(paged_engine):
    engine, params = paged_engine
    rng = np.random.default_rng(3)
    pat = rng.integers(0, engine.model.cfg.vocab_size, 6)
    reqs = [GenerationRequest(np.tile(pat, 2), max_new_tokens=6, seed=i)
            for i in range(3)]
    plain = engine.generate_requests(params, reqs)
    tracer = Tracer()
    traced = engine.generate_requests(params, reqs, tracer=tracer)
    annotated = engine.generate_requests(
        params, reqs, tracer=Tracer(annotate_device=True))
    for a, b, c in zip(plain, traced, annotated):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.tokens, c.tokens)
        assert a.steps == b.steps and a.accept_len == b.accept_len
    assert validate(tracer.export()) == []
    assert {"tick", "decode", "prefill", "queued", "running",
            "step_wait", "readback", "release", *_ADMIT_CHILDREN} <= {
        e["name"] for e in tracer.events}
    # the batch path's step_s ends at its step_wait sync, so it holds
    # the step_wait span's width
    spans = _closed(tracer.events)
    waits = [s for s in spans if s["name"] == "step_wait"]
    decodes = [s for s in spans if s["name"] == "decode"]
    assert len(waits) == len(decodes) > 0
    for w, d in zip(waits, decodes):
        assert d["t0"] <= w["t0"] <= w["t1"] <= d["t1"]
        assert w["args"]["step"] == d["args"]["step"]
    # batch-path telemetry accumulated on the engine itself
    assert engine.telemetry.mean_accept("ngram:bf16") is not None


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

def test_expose_text_format():
    m = ServerMetrics()
    m.on_submit(0, 0.0)
    m.on_admit(0, 0.5)
    m.on_tokens(0, 1.0, 3)
    m.on_finish(0, 1.5)
    m.on_step(1.5, 1, 2)
    m.on_decode_step("ngram:bf16", [2, 3], 0.1)
    text = m.expose_text()
    assert text.endswith("\n")
    lines = text.splitlines()
    assert 'serve_requests_total{event="submitted"} 1' in lines
    assert 'serve_requests_total{event="completed"} 1' in lines
    assert "# TYPE serve_requests_total counter" in lines
    assert "# TYPE serve_accept_len gauge" in lines
    assert any(l.startswith('serve_accept_len{drafter="ngram",'
                            'verifier="bf16",stat="tokens"} 5')
               for l in lines)
    assert 'serve_latency_queue_s{stat="n"} 1' in lines
    assert 'serve_kv_cache_total{event="preemptions"} 0' in lines
    # None-valued samples (no SLOs, no prefix probes) are omitted, but
    # their HELP/TYPE headers still render deterministically
    assert "# TYPE serve_deadline_hit_rate gauge" in lines
    assert not any(l.startswith("serve_deadline_hit_rate ") for l in lines)
    # deterministic: a second render is byte-identical
    assert m.expose_text() == text


def test_summary_is_json_serialisable():
    import json
    m = ServerMetrics()
    m.on_submit(0, 0.0)
    m.on_shed(0, 1.0)
    m.on_decode_step("ngram:w8a8", [1], 0.01)
    out = json.loads(json.dumps(m.summary()))
    assert out["counters"]["shed"] == 1
    assert out["acceptance"]["ngram:w8a8"]["steps"] == 1


# ---------------------------------------------------------------------------
# Shared clock: tracer spans on the profiler's trace
# ---------------------------------------------------------------------------

_ADMIT_CHILDREN = ("prefill.row_init", "prefill.forward", "prefill.scatter",
                   "prefill.drafter")
_PMAX = 16


def _closed(events):
    """Tracer B/E pairs as dicts with name, args, t0, t1 (seconds)."""
    stacks, out = {}, []
    for e in events:
        key = (e.get("pid"), e.get("tid", 0))
        if e["ph"] == "B":
            stacks.setdefault(key, []).append(e)
        elif e["ph"] == "E":
            b = stacks[key].pop()
            out.append({"name": b["name"], "args": b.get("args", {}),
                        "tid": key[1], "t0": b["ts"] * 1e-6,
                        "t1": e["ts"] * 1e-6})
    return sorted(out, key=lambda s: (s["t0"], -s["t1"]))


def _serve(engine, params, tracer=None):
    """Four requests of distinct lengths (and first tokens, so no prefix
    hits) through a two-slot paged ServingLoop; (tokens, kv snapshot)."""
    from repro.serving import ServerConfig
    from repro.serving.server import ServingLoop
    loop = ServingLoop(engine, params,
                       ServerConfig(batch_slots=2, max_prompt_len=_PMAX,
                                    max_new_tokens=6),
                       tracer=tracer)
    vocab = engine.model.cfg.vocab_size
    rng = np.random.default_rng(11)
    handles = []
    for i, P in enumerate((5, 9, 12, 16)):
        prompt = rng.integers(0, vocab, P).astype(np.int32)
        prompt[0] = 10 + i
        handles.append(loop.submit(
            GenerationRequest(prompt, max_new_tokens=6, seed=i)))
    loop.drain()
    kv = loop.metrics.kv_cache_summary()
    return [h.result(timeout=0).tokens for h in handles], kv


def _profile_events(path):
    """(name, start s, stats) of every host event in a profile."""
    import warnings

    from jax.profiler import ProfileData
    out = []
    with warnings.catch_warnings():
        # the profile's stat type warns on every access in this jax
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns * 1e-9, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def profiled_serve(paged_engine, tmp_path_factory):
    engine, params = paged_engine
    plain, kv_off = _serve(engine, params)
    _serve(engine, params, Tracer())          # compile every shape first
    tracer = Tracer(annotate_device=True)
    logdir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(logdir))
    try:
        traced, kv_on = _serve(engine, params, tracer)
    finally:
        jax.profiler.stop_trace()
    (pb,) = logdir.rglob("*.xplane.pb")
    return plain, kv_off, traced, kv_on, tracer, _profile_events(pb)


def test_spans_share_the_profilers_clock(profiled_serve):
    """Every tracer span is a host event of the profile with the same
    name and arguments; tracer time minus profile time is one offset."""
    *_, tracer, host = profiled_serve
    spans = _closed(tracer.events)
    by_name = {}
    for name, t, stats in sorted(host, key=lambda h: h[1]):
        by_name.setdefault(name, []).append((t, stats))
    offsets = []
    for name in sorted({s["name"] for s in spans}):
        mine = [s for s in spans if s["name"] == name]
        theirs = by_name.get(name, [])
        assert len(theirs) == len(mine), name
        for s, (t, stats) in zip(mine, theirs):
            want = {k: int(v) if isinstance(v, bool) else v
                    for k, v in s["args"].items()}
            assert stats == want, (name, stats, want)
            offsets.append(s["t0"] - t)
    assert len(offsets) > 50
    assert max(offsets) - min(offsets) < 1e-3


def _inside(child, parent):
    return (child["tid"] == parent["tid"]
            and parent["t0"] <= child["t0"] <= child["t1"] <= parent["t1"])


def test_admission_and_tick_spans_nest(profiled_serve):
    *_, tracer, _ = profiled_serve
    spans = _closed(tracer.events)
    assert validate(tracer.export()) == []

    def named(n):
        return [s for s in spans if s["name"] == n]

    def parent_of(child, names):
        ps = [p for p in spans if p["name"] in names and p is not child
              and _inside(child, p)]
        assert ps, (child["name"], names)
        return min(ps, key=lambda p: p["t1"] - p["t0"])

    admits = named("admit")
    assert len(admits) == 4
    for pre in named("prefill"):
        assert parent_of(pre, {"admit"})["args"]["rid"] == pre["args"]["rid"]
    for name in _ADMIT_CHILDREN:
        kids = named(name)
        assert len(kids) == 4, name
        for k in kids:
            p = parent_of(k, {"prefill"})
            assert p["args"]["rid"] == k["args"]["rid"]
    for reg in named("prefix.register"):
        assert parent_of(reg, {"admit", "prefill"})["name"] == "admit"
    assert len(named("prefix.register")) == 4
    for name in ("cow_sweep", "step_wait"):
        kids = named(name)
        assert kids, name
        for k in kids:
            d = parent_of(k, {"decode"})
            assert d["args"]["step"] == k["args"]["step"]
    for name in ("readback", "stream", "release"):
        kids = named(name)
        assert kids, name
        for k in kids:
            assert parent_of(k, {"tick", "harvest", "decode"})["name"] in (
                "tick", "harvest")
    for t in named("tick"):
        assert parent_of(t, {"poll"})["name"] == "poll"
    # the decode span, and so step_s, ends after the step's first sync
    for d in named("decode"):
        waits = [w for w in named("step_wait") if _inside(w, d)]
        assert len(waits) == 1


def test_padded_prefill_rows_counted(profiled_serve):
    plain, kv_off, traced, kv_on, tracer, _ = profiled_serve
    want = sum(_PMAX - P for P in (5, 9, 12, 16))
    assert kv_off["padded_prefill_tokens"] == want
    assert kv_on["padded_prefill_tokens"] == want
    assert kv_on["cold_prefill_tokens"] == sum(P - 1 for P in (5, 9, 12, 16))
    pre = [s for s in _closed(tracer.events) if s["name"] == "prefill"]
    assert sum(s["args"]["padded_rows"] for s in pre) == want
    m = ServerMetrics()
    m.add_kv_source("lane0", lambda: {"padded_prefill_tokens": want})
    assert (f'serve_kv_cache_total{{event="padded_prefill_tokens"}} {want}'
            in m.expose_text().splitlines())


def test_tokens_identical_with_annotated_tracing(profiled_serve):
    plain, _, traced, *_ = profiled_serve
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)
