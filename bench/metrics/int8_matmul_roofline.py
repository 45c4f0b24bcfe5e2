"""Kernels: the W8A8 GEMMs of the decode step against their roofline.

The least time of each call, max(int8 ops / int8 peak, bytes / HBM
bandwidth), from its shapes (``bench/roofline.py``), summed over the
calls the trace shows inside decode-step executions, over the device
time of those calls.  The prefill's calls are not counted here."""
from bench.roofline import (int8_matmul_cost, least_seconds, peaks,
                            step_int8_matmul_calls)
from bench.weights import dims


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    n, busy = t.kernel_calls("int8_matmul"), t.kernel_s("int8_matmul")
    if n == 0 or busy <= 0:
        return None
    model = ctx.cell.config["model"]
    s = ctx.cell.config["serving"]
    p = peaks(ctx.device_kind)
    rows = ctx.traffic.slots * (s["gamma"] + 1)
    calls = step_int8_matmul_calls(
        dims(model), rows, not model.get("tie_word_embeddings", False))
    least = sum(least_seconds(*int8_matmul_cost(*c), p["int8_ops_per_s"],
                              p["hbm_bytes_per_s"])[0] for c in calls)
    return 100.0 * least * n / len(calls) / busy
