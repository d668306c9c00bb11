"""Host spans and counters of the serving engine, on the profiler's clock.

Each span is a :class:`jax.profiler.TraceAnnotation`: under a running
profiler it lands on the host plane of the same trace as the device's
programs and operations, with its keyword arguments as event stats;
with no profiler running it costs a C++ check. ``timed`` adds a
``perf_counter`` pair, so the engine's counters are kept whether or not
a profile is taken.

Python's collector runs on whichever thread allocates; one module-level
``gc.callbacks`` entry, added once at import, puts every collection
under an ``engine.gc`` span and sums its seconds for ``gc_seconds()``.
"""
from __future__ import annotations

import gc
import time

from jax.profiler import TraceAnnotation

__all__ = ["timed", "gc_seconds"]


class timed:
    """``with timed(name, **args) as t:`` runs the block under a profiler
    span; afterwards ``t.s`` holds its host seconds. ``t.set(**args)``
    adds arguments known only inside the block."""
    __slots__ = ("_span", "_t0", "s")

    def __init__(self, name: str, **args):
        self._span = TraceAnnotation(name, **args)
        self.s = 0.0

    def __enter__(self) -> "timed":
        self._t0 = time.perf_counter()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self.s = time.perf_counter() - self._t0
        return False

    def set(self, **args):
        self._span.set_metadata(**args)


_gc = {"s": 0.0, "t0": 0.0, "span": None}


def _on_gc(phase: str, info: dict):
    if phase == "start":
        _gc["span"] = TraceAnnotation("engine.gc",
                                      generation=info["generation"])
        _gc["span"].__enter__()
        _gc["t0"] = time.perf_counter()
    elif _gc["span"] is not None:
        _gc["s"] += time.perf_counter() - _gc["t0"]
        _gc["span"].__exit__(None, None, None)
        _gc["span"] = None


def gc_seconds() -> float:
    """Seconds this process has spent in Python's collector since this
    module was imported."""
    return _gc["s"]


if not any(getattr(cb, "__module__", None) == __name__
           and getattr(cb, "__name__", None) == "_on_gc"
           for cb in gc.callbacks):
    gc.callbacks.append(_on_gc)
