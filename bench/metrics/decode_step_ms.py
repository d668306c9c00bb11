"""Device time of one execution of the decode program, averaged over
its executions in the traced window."""


def read(ctx):
    t = ctx.program_times("decode")
    return 1e3 * sum(t) / len(t) if t else None
