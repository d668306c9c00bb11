"""Host milliseconds of one engine scheduler round, less its blocking
device-to-host reads and its calls into the bus: the traced window's
change in the engine's ``host_s`` counter over its change in
``rounds``, from the counters each ``engine.round`` span carries as of
its start (``enginespans.py``)."""
import enginespans


def read(ctx):
    rounds = enginespans.in_window(ctx, "engine.round")
    if len(rounds) < 2:
        return None
    a, b = rounds[0].args, rounds[-1].args
    n = b["rounds"] - a["rounds"]
    return 1e3 * (b["host_s"] - a["host_s"]) / n if n > 0 else None
