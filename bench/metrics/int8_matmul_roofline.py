"""Kernels: the W8A8 GEMMs of the decode step against their roofline.

The least time of each call, max(int8 ops / int8 peak, bytes / HBM
bandwidth), from its shapes (the calls the configuration's architecture
module lists, costed by ``bench/roofline.py``), summed over the calls
the trace shows inside decode-step executions, over the device time of
those calls.  The prefill's calls are not counted here."""
from bench.harness import architecture
from bench.roofline import int8_matmul_cost, least_seconds, peaks


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    n, busy = t.kernel_calls("int8_matmul"), t.kernel_s("int8_matmul")
    if n == 0 or busy <= 0:
        return None
    config = ctx.cell.config
    p = peaks(ctx.device_kind)
    rows = ctx.traffic.slots * (config["serving"]["gamma"] + 1)
    calls = architecture(config).int8_matmul_calls(config["model"], rows)
    least = sum(least_seconds(*int8_matmul_cost(*c), p["int8_ops_per_s"],
                              p["hbm_bytes_per_s"])[0] for c in calls)
    return 100.0 * least * n / len(calls) / busy
