"""Kernels: the paged verify attention of the decode step against its
roofline.  Each call's least time comes from the live positions each row
attends (logged by the harness before every step), not the blocks the
kernel walks, costed by the configuration's architecture module; it is
averaged over the traced steps and set against the device time of the
calls the trace shows inside decode steps."""
from bench.harness import architecture
from bench.roofline import least_seconds, peaks


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    n = t.kernel_calls("flash_decode_paged")
    busy = t.kernel_s("flash_decode_paged")
    steps = ctx.traced_steps()
    if n == 0 or busy <= 0 or not steps:
        return None
    config = ctx.cell.config
    s = config["serving"]
    cost = architecture(config).verify_attention_cost
    p = peaks(ctx.device_kind)
    bf16 = p["bf16_flops_per_s"]
    least = 0.0
    for rows in steps:
        flops, nbytes = cost(config["model"], s, s["gamma"] + 1, rows)
        least += least_seconds(flops, nbytes, bf16, p["hbm_bytes_per_s"])[0]
    per_call = least / len(steps)
    return 100.0 * per_call * n / busy
