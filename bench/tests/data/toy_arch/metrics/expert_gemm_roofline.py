"""Kernels: the toy architecture's grouped expert GEMMs (``expert_gemm``)
against their roofline: int8 operands, every expert's weight read once,
over the device time of the calls inside decode steps."""
from bench.harness import architecture
from bench.roofline import least_seconds, peaks


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    n, busy = t.kernel_calls("expert_gemm"), t.kernel_s("expert_gemm")
    if n == 0 or busy <= 0:
        return None
    config = ctx.cell.config
    p = peaks(ctx.device_kind)
    rows = ctx.traffic.slots * (config["serving"]["gamma"] + 1)
    calls = architecture(config).expert_gemm_calls(config["model"], rows)
    least = sum(least_seconds(2 * M * K * N, E * K * N + M * K + 2 * M * N,
                              p["int8_ops_per_s"], p["hbm_bytes_per_s"])[0]
                for E, M, K, N in calls)
    return 100.0 * least * n / len(calls) / busy
