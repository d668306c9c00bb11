"""Host seconds the engine spent in the first call of each of its step
programs (trace, compile or load from the cache, dispatch), all of them
in set-up: the ``first_call_s`` counter carried by the first
``engine.round`` span of the traced window (``enginespans.py``)."""
import enginespans


def read(ctx):
    rounds = enginespans.in_window(ctx, "engine.round")
    return rounds[0].args["first_call_s"] if rounds else None
