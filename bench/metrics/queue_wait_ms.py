"""Median, over the requests whose prefill starts in the traced window,
of the milliseconds from ``submit`` to admission, as the engine stamps
them (``queue_ms`` on each ``engine.prefill`` or ``engine.chunk`` span,
``enginespans.py``). The median resists the few requests held while the
profiler starts and stops."""
import statistics

import enginespans


def read(ctx):
    wait = {}
    for s in enginespans.in_window(ctx, "engine.prefill", "engine.chunk"):
        wait.setdefault(s.args["rid"], s.args["queue_ms"])
    return statistics.median(wait.values()) if wait else None
