"""Requests served per decode step over the measured window: decode
tokens (tokens out less first tokens) over decode steps, from the
engine's ``stats()`` counters."""


def read(ctx):
    c = ctx.counters
    if not c["decode_steps"]:
        return None
    return c["decode_tokens"] / c["decode_steps"]
