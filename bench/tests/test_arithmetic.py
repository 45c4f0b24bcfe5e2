"""The yardstick's arithmetic: latencies and rates on synthetic
timelines, the roofline formulas on hand-counted shapes, and the trace
reduction on a small hand-made trace and on a slice of a recorded one."""
import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import roofline as RF  # noqa: E402
from bench import stats  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402
from bench.stats import Record  # noqa: E402
from bench.traffic import gamma_gaps, lognormal_quantiles  # noqa: E402


# -- latency and rate ------------------------------------------------------

def test_nearest_rank():
    v = [5, 1, 4, 2, 3]
    assert stats.nearest_rank(v, 0) == 1
    assert stats.nearest_rank(v, 50) == 3
    assert stats.nearest_rank(v, 95) == 5
    assert stats.nearest_rank([1, 2, 3, 4], 50) == 2
    assert math.isnan(stats.nearest_rank([], 50))


def test_ttft_tpot_from_due_time():
    r = Record(due_t=10.0, emits=[(10.5, 3), (10.7, 1), (11.1, 2)],
               admit_t=10.2, done=True)
    assert r.n_tokens == 6
    assert r.ttft == pytest.approx(0.5)
    assert r.tpot == pytest.approx((11.1 - 10.5) / 5)
    assert r.queue_wait == pytest.approx(0.2)


def test_failed_or_unfinished_request_counts_infinite():
    ok = [Record(due_t=0.0, emits=[(0.1 * (i + 1), 1), (1.0, 1)],
                 admit_t=0.0, done=True) for i in range(19)]
    failed = Record(due_t=0.0, emits=[(0.05, 1)], failed=True)
    cut = Record(due_t=0.0, emits=[(0.05, 1)])       # never finished
    for bad in (failed, cut):
        assert bad.ttft == math.inf and bad.tpot == math.inf
    assert failed.queue_wait == math.inf
    # 21 requests: the p95 is the 20th value, so one +inf does not show
    # and two do
    assert stats.tail_ms([r.ttft for r in ok + [failed, cut]]) == math.inf
    assert stats.tail_ms([r.ttft for r in ok + [failed]]) == \
        pytest.approx(1900.0)


def test_tokens_in_window_counts_stamps_inside_only():
    recs = [Record(due_t=0.0, emits=[(0.5, 4), (1.0, 2), (2.0, 3)]),
            Record(due_t=0.0, emits=[(1.99, 5), (2.5, 7)])]
    assert stats.tokens_in(recs, 1.0, 2.0) == 2 + 5


def test_end_to_end_gives_the_named_metrics_only():
    from types import SimpleNamespace
    from bench.harness import Window, end_to_end
    recs = {1: Record(due_t=0.0, emits=[(0.5, 4), (1.0, 2)], done=True),
            2: Record(due_t=1.0, emits=[(1.2, 1), (2.5, 3)], done=True),
            3: Record(due_t=1.5, failed=True)}
    drv = SimpleNamespace(records=recs)
    win = Window(t0=0.0, t1=2.0, due=[1, 2, 3])
    got = end_to_end(drv, win, ["tokens_per_s.chat", "ttft_p50_ms",
                                "tpot_p95_ms"])
    assert set(got) == {"tokens_per_s.chat", "ttft_p50_ms", "tpot_p95_ms"}
    assert got["tokens_per_s.chat"] == pytest.approx((4 + 2 + 1) / 2.0)
    assert got["ttft_p50_ms"] == pytest.approx(500.0)
    assert got["tpot_p95_ms"] == math.inf          # the failed request
    with pytest.raises(KeyError):
        end_to_end(drv, win, ["ttft_p95"])


def test_length_and_gap_grids():
    x = lognormal_quantiles(33, 256, 0.7, 64, 1024)
    assert x[16] == 256 and x.min() >= 64 and x.max() <= 1024
    assert (x[1:] >= x[:-1]).all()
    g = gamma_gaps(200, 4.0, 2.0)
    assert g.sum() == pytest.approx(50.0)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.15)


# -- roofline formulas -------------------------------------------------------

def test_int8_matmul_cost_by_hand():
    ops, nbytes = RF.int8_matmul_cost(96, 4096, 13440)
    assert ops == 2 * 96 * 4096 * 13440
    assert nbytes == (96 * 4096 + 4096 * 13440 + 4 * 96 + 4 * 13440
                      + 2 * 96 * 13440)


def test_flash_cost_counts_live_positions_only():
    # two rows attending 100 and 300 positions, int8 K/V with f32 scales,
    # 32 query heads of 128 over 32 KV heads, windows of 6
    flops, nbytes = RF.flash_decode_paged_cost(6, 32, 32, 128, [100, 300],
                                               1, True)
    per_pos = 2 * 32 * 128 * 1 + 2 * 32 * 4
    assert nbytes == 400 * per_pos + 2 * 2 * 2 * 6 * 32 * 128
    assert flops == 4 * 6 * 32 * 128 * 400
    bf = RF.flash_decode_paged_cost(6, 9, 3, 64, [10], 2, False)[1]
    assert bf == 10 * 2 * 3 * 64 * 2 + 2 * 2 * 6 * 9 * 64


def test_least_seconds_and_peaks():
    p = RF.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    t, bound = RF.least_seconds(*RF.int8_matmul_cost(96, 4096, 4096),
                                p["int8_ops_per_s"], p["hbm_bytes_per_s"])
    assert bound == "memory"
    assert t == pytest.approx(RF.int8_matmul_cost(96, 4096, 4096)[1] / 819e9)
    with pytest.raises(KeyError):
        RF.peaks("TPU v9 imaginary")


def test_step_calls_and_model_flops():
    d = {"D": 4096, "F": 13440, "H": 32, "Hkv": 32, "dh": 128, "V": 92416,
         "L": 8}
    calls = RF.step_int8_matmul_calls(d, 96, True)
    assert len(calls) == 8 * 7 + 1
    assert calls[-1] == (96, 4096, 92416) and calls[6] == (96, 13440, 4096)
    per_layer = 4 * 4096 * 4096 + 3 * 4096 * 13440
    assert RF.model_flops_per_token(d) == 2 * (8 * per_layer
                                               + 4096 * 92416)
    assert RF.attention_flops(d, [10, 20]) == 4 * 8 * 32 * 128 * 30


# -- trace reduction -----------------------------------------------------------

def _ev(name, a, b, **stats):
    return TR.Ev(name, a, b, {k: str(v) for k, v in stats.items()})


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert TR.union_length(iv) == pytest.approx(3.0)
    assert TR.idle_gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]


def test_reduce_small_trace():
    """Window [0, 10]: two decode steps with kernels inside, one eager
    admission program, idle gaps under host spans."""
    mods = [_ev("jit_counted(1)", 1.0, 3.0), _ev("jit_counted(1)", 6.0, 7.0),
            _ev("jit_int8_matmul(2)", 4.0, 5.0)]
    ops = [
        _ev("custom-call.1", 1.0, 1.5, hlo_module="jit_counted",
            tf_op="jit(counted)/verify/jit(int8_matmul)/pallas_call"),
        _ev("fusion.3", 1.5, 3.0, hlo_module="jit_counted"),
        _ev("custom-call.2", 6.0, 6.25, hlo_module="jit_counted",
            tf_op="jit(counted)/verify/jit(flash_decode_paged)/pallas_call"),
        _ev("custom-call.1", 6.25, 7.0, hlo_module="jit_counted",
            tf_op="jit(counted)/verify/jit(int8_matmul)/pallas_call"),
        # the eager prefill's kernel is not a decode-step call
        _ev("custom-call.9", 4.0, 5.0, hlo_module="jit_int8_matmul",
            tf_op="jit(int8_matmul)/pallas_call"),
        _ev("fusion.4", 11.0, 12.0, hlo_module="jit_counted"),  # outside
    ]
    host = [_ev("bench.traced", 0.0, 10.0), _ev("admit", 3.0, 4.5),
            _ev("prefill", 3.2, 4.4), _ev("bench.wait", 7.0, 10.0),
            _ev("decode", 5.0, 6.0), _ev("PjitFunction", 0.0, 10.0)]
    r = TR.reduce_events(ops, mods, host, 0.0, 10.0,
                         {"admit", "prefill", "decode", "bench.wait"})
    assert r.window_s == 10.0
    assert r.busy_s == pytest.approx(4.0)
    assert r.step_count == 2
    assert r.other_module_busy_s == pytest.approx(1.0)
    assert r.kernel_calls("int8_matmul") == 2
    assert r.kernel_s("int8_matmul") == pytest.approx(1.25)
    assert r.kernel_calls("flash_decode_paged") == 1
    gaps = dict(r.gaps)
    # gaps: [0,1] no span, [3,4] prefill (innermost), [5,6] decode,
    # [7,10] bench.wait
    assert gaps == pytest.approx({"no span": 1.0, "prefill": 1.0,
                                  "decode": 1.0, "bench.wait": 3.0})
    b = r.breakdown()
    assert b["device_ops"][0][0] == "jit_counted/fusion"
    assert len(b["idle_gaps"]) == 4


def test_reduce_recorded_trace_slice():
    """A 0.1 s slice of a trace recorded on a TPU v5e during one admission
    of `codeqwen7b-decode`: the eager prefill runs one small program per
    operation, no decode step runs, and the host is inside `prefill` for
    every idle gap."""
    d = json.loads((Path(__file__).parent / "data" /
                    "trace_slice.json").read_text())

    def evs(rows):
        return [TR.Ev(n, a, b, st) for n, a, b, st in rows]

    r = TR.reduce_events(evs(d["ops"]), evs(d["modules"]), evs(d["host"]),
                         d["lo"], d["hi"], set(d["span_names"]))
    assert r.window_s == pytest.approx(0.1)
    assert 0.0 < r.busy_s < r.window_s
    assert r.step_count == 0 and r.kernels == {}
    assert r.other_module_busy_s == pytest.approx(r.busy_s, rel=0.01)
    assert r.ops[0][0] == "jit_int8_matmul/int8_matmul"
    assert [n for n, _ in r.gaps] == ["prefill"]
    assert r.gaps[0][1] == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
