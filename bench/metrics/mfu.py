"""Device: model flops over the traced stretch against the chip's bf16
peak.  Flops are 2 x the parameters a token multiplies by, for every
token committed and every prompt token prefilled in the stretch, plus
attention: each committed token over its context (the live positions
logged per step times the window's mean tokens per row-step) and each
prefilled prompt over its causal square."""
from bench.roofline import (attention_flops, model_flops_per_token,
                            peaks)
from bench.weights import dims


def read(ctx):
    if ctx.trace is None or ctx.span.t1 is None:
        return None
    d = dims(ctx.cell.config["model"])
    t0, t1 = ctx.span.t0, ctx.span.t1
    committed = ctx.tokens_between(t0, t1)
    prompts = ctx.prefilled_between(t0, t1)
    tokens, row_steps = ctx.accept
    per_row_step = tokens / row_steps if row_steps else 0.0
    ctx_lens = [k * per_row_step for rows in ctx.traced_steps()
                for k in rows]
    ctx_lens += [(p - 1) / 2.0 * (p - 1) for p in prompts]
    flops = (model_flops_per_token(d) * (committed + sum(p - 1 for p in prompts))
             + attention_flops(d, ctx_lens))
    return 100.0 * flops / ((t1 - t0) * peaks(ctx.device_kind)["bf16_flops_per_s"])
