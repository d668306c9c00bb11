"""Share of its roofline that the decode program reaches: the least time
of the decode steps dispatched in the traced window (the family's
``decode_step`` work for the lanes and live contexts of each step, then
``costs.least_time_s``: the larger of FLOPs over the FLOP peak and bytes
over the HBM peak), averaged, over the mean device time of one decode
execution."""
import costs


def read(ctx):
    t = ctx.program_times("decode")
    steps = ctx.traced["decode"]
    if not t or not steps:
        return None
    least = [costs.least_time_s(*ctx.family.decode_step(ctx.dims, c),
                                ctx.peak)
             for c in steps]
    return 100.0 * (sum(least) / len(least)) / (sum(t) / len(t))
