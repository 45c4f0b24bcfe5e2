"""The per-layer readers of the program's own spans, and of device
programs per admission, on hand-made events and on events recorded on a
TPU v5e (``data/``)."""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402
from bench.program_spans import closed_spans, window_spans  # noqa: E402

DATA = Path(__file__).parent / "data"


def _read(metric, ctx):
    return H.reader(metric)(ctx)


def _per_admission(modules, host):
    """``bench/metrics/admit_programs.py``'s count on given events."""
    spec = importlib.util.spec_from_file_location(
        "admit_programs", ROOT / "bench" / "metrics" / "admit_programs.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.per_admission(modules, host)


def _span(name, t0, t1, tid=0, **args):
    """B/E events of one span (seconds in, microseconds out)."""
    b = {"ph": "B", "name": name, "tid": tid, "pid": 1, "ts": t0 * 1e6}
    if args:
        b["args"] = args
    return [b, {"ph": "E", "name": name, "tid": tid, "pid": 1,
                "ts": t1 * 1e6}]


def _ctx(events, t0, t1, trace=None, span=None):
    loop = SimpleNamespace(tracer=SimpleNamespace(events=events))
    return SimpleNamespace(driver=SimpleNamespace(loop=loop),
                           window=SimpleNamespace(t0=t0, t1=t1),
                           trace=trace, span=span)


def _tick(step, t0, admit=None, decode=(0.0, 0.05), padded=0, cold=10):
    """One tick at ``t0``: an optional admission of width ``admit``, then
    a decode step of ``decode`` width."""
    t = t0
    out = _span("tick", t0, t0 + 10.0, step=step)[:1]
    if admit is not None:
        a0, a1 = t, t + admit
        out += _span("admit", a0, a1, rid=step, slot=0)[:1]
        out += _span("prefill", a0 + 0.001, a1 - 0.002, rid=step,
                     shared_rows=0, cold_rows=cold, padded_rows=padded)
        out += _span("admit", a0, a1)[1:]
        t = a1
    out += _span("decode", t + decode[0], t + decode[0] + decode[1],
                 step=step, rows=2)
    t = t + decode[0] + decode[1] + 0.001
    out += [{"ph": "E", "name": "tick", "tid": 0, "pid": 1, "ts": t * 1e6}]
    return out, t


def _run(ticks, t=0.0):
    events = []
    for k, kw in enumerate(ticks):
        evs, t = _tick(k, t, **kw)
        events += evs
    return events, t


def test_closed_spans_pair_nested_events_and_skip_orphans():
    events = ([{"ph": "E", "name": "tick", "tid": 0, "pid": 1, "ts": 1.0}]
              + _run([{"admit": 0.5}])[0]
              + [{"ph": "B", "name": "tick", "tid": 0, "pid": 1,
                  "ts": 9e6}])                # still open at the end
    spans = closed_spans(events)
    assert [s.name for s in spans] == ["prefill", "admit", "decode", "tick"]
    pre = spans[0]
    assert pre.args["padded_rows"] == 0 and pre.dur == pytest.approx(0.497)
    assert [s.name for s in closed_spans(events, {"decode"})] == ["decode"]


def test_window_spans_keep_what_lies_wholly_inside():
    events, _ = _run([{"admit": 0.5}, {}, {"admit": 0.7}, {}])
    ctx = _ctx(events, 0.0, 1.31)
    assert [round(s.dur, 3) for s in window_spans(ctx, "admit")] == [0.5, 0.7]
    ctx = _ctx(events, 0.1, 10.0)
    assert [round(s.dur, 3) for s in window_spans(ctx, "admit")] == [0.7]


def test_admit_host_ms_and_step_service_ms_are_medians():
    events, t1 = _run([{"admit": 0.5, "decode": (0.0, 0.040)},
                       {"decode": (0.0, 0.060)},
                       {"admit": 0.9, "decode": (0.0, 0.050)},
                       {"admit": 0.6, "decode": (0.0, 0.045)}])
    ctx = _ctx(events, 0.0, t1)
    assert _read("admit_host_ms.batch", ctx) == pytest.approx(600.0)
    # nearest rank: the 2nd of [40, 45, 50, 60]
    assert _read("step_service_ms.chat", ctx) == pytest.approx(45.0)


def test_prefill_pad_share_sums_rows():
    events, t1 = _run([{"admit": 0.5, "padded": 30, "cold": 10},
                       {"admit": 0.5, "padded": 10, "cold": 50}])
    ctx = _ctx(events, 0.0, t1)
    assert _read("prefill_pad_share.batch", ctx) == pytest.approx(40.0)


def test_readers_report_nothing_without_the_spans():
    """A loop without a tracer, or a program whose prefill spans do not
    count padding (the parent of this reader), gives no value."""
    no_tracer = SimpleNamespace(
        driver=SimpleNamespace(loop=SimpleNamespace()),
        window=SimpleNamespace(t0=0.0, t1=10.0), trace=None, span=None)
    for m in ("admit_host_ms", "step_service_ms", "prefill_pad_share",
              "admit_programs"):
        assert _read(m, no_tracer) is None
    events, t1 = _run([{"admit": 0.5}])
    for e in events:
        e.get("args", {}).pop("padded_rows", None)
    assert _read("prefill_pad_share.chat", _ctx(events, 0.0, t1)) is None
    assert _read("admit_host_ms.chat", _ctx(events, 0.0, t1)) is not None


def _ev(name, a, b, **stats):
    return TR.Ev(name, a, b, {k: str(v) for k, v in stats.items()})


def test_admit_programs_counts_module_starts_inside_whole_admissions():
    host = [_ev("bench.traced", 0.0, 10.0), _ev("admit", -1.0, 0.5),
            _ev("admit", 1.0, 2.0), _ev("admit", 4.0, 6.0),
            _ev("admit", 9.5, 10.5), _ev("decode", 2.0, 3.0)]
    mods = ([_ev("jit_add(1)", 0.1, 0.2)]                 # cut admission
            + [_ev(f"jit_mul({i})", 1.0 + 0.1 * i, 1.05 + 0.1 * i)
               for i in range(6)]
            + [_ev("jit_counted(9)", 2.0, 2.9)]           # decode step
            + [_ev("jit_scatter(2)", 4.0 + 0.5 * i, 4.1 + 0.5 * i)
               for i in range(4)]
            + [_ev("jit_take(3)", 6.5, 6.6)])             # after admit
    # 6 + 4 programs over the two admissions wholly inside [0, 10]
    assert _per_admission(mods, host) == pytest.approx(5.0)
    assert _per_admission(mods, host[:2]) is None      # none whole
    assert _per_admission(mods, host[1:]) is None      # no stretch


def _recorded_events():
    return json.loads((DATA / "tracer_events.json").read_text())["events"]


def test_readers_on_recorded_tracer_events():
    """2.5 s of a traced `codeqwen7b-decode` run: 25 decode ticks and one
    whole admission.  The spans nest as the program emits them, and the
    readers give what the spans say."""
    events = _recorded_events()
    spans = closed_spans(events)
    ticks = [s for s in spans if s.name == "tick"]
    assert len(ticks) == 25
    for d in (s for s in spans if s.name == "decode"):
        tick = [t for t in ticks if t.t0 <= d.t0 and d.t1 <= t.t1]
        assert len(tick) == 1 and tick[0].args["step"] == d.args["step"]
        inner = [s for s in spans if s.name in ("cow_sweep", "step_wait")
                 and d.t0 <= s.t0 and s.t1 <= d.t1]
        assert sorted(s.name for s in inner) == ["cow_sweep", "step_wait"]
        assert all(s.args["step"] == d.args["step"] for s in inner)
    ctx = _ctx(events, ticks[0].t0, ticks[-1].t1)
    widths = sorted(s.dur for s in spans if s.name == "decode")
    assert _read("step_service_ms.batch", ctx) == pytest.approx(
        1e3 * widths[(len(widths) - 1) // 2])
    (admit,) = window_spans(ctx, "admit")
    assert _read("admit_host_ms.batch", ctx) == pytest.approx(1e3 * admit.dur)
    (pre,) = window_spans(ctx, "prefill")
    padded, cold = pre.args["padded_rows"], pre.args["cold_rows"]
    assert padded + cold + 1 == 1024            # the cell's prompt cap
    assert _read("prefill_pad_share.batch", ctx) == pytest.approx(
        100.0 * padded / (padded + cold))
    kids = [s for s in spans if s.name.startswith("prefill.")
            and pre.t0 <= s.t0 and s.t1 <= pre.t1]
    assert sorted(s.name for s in kids) == [
        "prefill.drafter", "prefill.forward", "prefill.row_init",
        "prefill.scatter"]


def test_admit_programs_on_recorded_admission():
    """One admission of `codeqwen7b-decode` on a TPU v5e: about two
    thousand eager programs start inside its host `admit` event, and
    none of them is the decode step."""
    d = json.loads((DATA / "admit_slice.json").read_text())
    mods = [TR.Ev(n, a * 1e-6, b * 1e-6, {}) for n, a, b in d["modules"]]
    host = [TR.Ev(n, a * 1e-6, b * 1e-6, st) for n, a, b, st in d["host"]]
    traced = TR.Ev(TR.TRACED_SPAN, d["lo"] * 1e-6, d["hi"] * 1e-6, {})
    got = _per_admission(mods, host + [traced])
    (admit,) = [h for h in host if h.name == "admit"]
    inside = [m for m in mods if admit.start <= m.start <= admit.end]
    assert got == len(inside) > 1000
    assert not any(TR._base(m.name) == TR.STEP_MODULE for m in inside)
    # the stretch must hold the whole admission
    cut = TR.Ev(TR.TRACED_SPAN, admit.start + 1e-3, d["hi"] * 1e-6, {})
    assert _per_admission(mods, host + [cut]) is None
