"""Model FLOPs of every prompt token prefilled and every token decoded in
the traced window (the family's ``prefill_flops`` and ``token_flops``),
over the window times the chip's bf16 peak."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0 or not ctx.trace.ops:
        return None
    flops = sum(ctx.family.prefill_flops(ctx.dims, p)
                for p in ctx.traced["prefill"])
    flops += sum(ctx.family.token_flops(ctx.dims, c)
                 for step in ctx.traced["decode"] for c in step)
    if not flops:
        return None
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peak["bf16_flops_per_s"])
