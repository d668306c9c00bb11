"""Every use the benchmark makes of the program under test.

The benchmark drives ``repro.engine.InferenceEngine`` through its public
surface: ``submit``, ``run``, ``drain``, ``stats()``, the ``bus=`` hook
(a ``repro.telemetry.TelemetryBus`` whose ``publish_phase`` runs after
each step is dispatched, and ``publish_request`` as a request finishes)
and the ``Request`` objects. The engine has no per-token callback, so
this file reaches into it at exactly these places, each listed in
PERF.md; a change to any of them breaks the benchmark here first:

- ``InferenceEngine._waiting``: the queue ``submit`` appends to; its
  last element is the ``Request`` just submitted.
- ``Request.out_tokens``: replaced, before the request runs, by a list
  that stamps the host clock on each ``append`` (the engine appends each
  token once it is on the host).
- ``InferenceEngine._active`` / ``_prefilling``: with ``_waiting``, the
  requests the engine still holds (a check that none is left behind).
- Every ``jax.Array`` made since set-up began (the weights, the KV pool
  ``pool_k``/``pool_v``, any state a later family adds beside it) is
  deleted in ``release``, wherever the engine holds it, so that the
  reference finds the chip's memory free.
- ``Model.abstract_params()``: the parameter tree the weights must fill
  (called by each family's ``program_params``).

Model families (``family.py``) configure the program's model themselves.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path
from typing import Callable, Dict, List

import jax

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.engine import EngineConfig, InferenceEngine  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.telemetry import TelemetryBus  # noqa: E402


def engine_config(spec: Dict) -> EngineConfig:
    """``EngineConfig`` from a cell file's ``engine`` section."""
    e = dict(spec)
    lanes = e.pop("lanes_in_pool")
    e["buckets"] = tuple(e["buckets"])
    e["pool_pages"] = lanes * e["max_pages"] + 1     # + the null page
    return EngineConfig(**e)


class HookBus(TelemetryBus):
    """The engine's telemetry bus with a callback after each event:
    ``hook("phase", phase)`` after a step is dispatched and
    ``hook("request", rid)`` as a request finishes."""

    def __init__(self, hook: Callable[[str, object], None]):
        super().__init__()
        self.hook = hook

    def publish_phase(self, phase, **kw):
        super().publish_phase(phase, **kw)
        self.hook("phase", phase)

    def publish_request(self, info):
        super().publish_request(info)
        self.hook("request", info["rid"])


class TimedTokens(list):
    """An output-token list that records when each token reached the
    host (``time.perf_counter`` at the engine's ``append``)."""

    def __init__(self):
        super().__init__()
        self.times: List[float] = []

    def append(self, tok):
        self.times.append(time.perf_counter())
        super().append(tok)


def build_engine(model: Model, params, spec: Dict,
                 hook: Callable[[str, object], None]) -> InferenceEngine:
    return InferenceEngine(model, params, engine_config(spec),
                           bus=HookBus(hook))


def submit(engine: InferenceEngine, prompt: List[int], max_new: int):
    """Queue one request; returns its ``Request`` with timed tokens."""
    rid = engine.submit(prompt, max_new)
    req = engine._waiting[-1]
    if req.rid != rid:
        raise RuntimeError("InferenceEngine._waiting no longer ends with "
                           "the request just submitted")
    req.out_tokens = TimedTokens()
    return req


def queued(engine: InferenceEngine) -> int:
    """Requests the engine still holds (waiting, prefilling, decoding)."""
    return len(engine._waiting) + len(engine._active) + \
        len(engine._prefilling)


def decode_steps(stats: Dict) -> int:
    return stats["phases"]["decode"]["steps"]


def release(engine: InferenceEngine, before: List[jax.Array]):
    """Close the engine and free on the device every array made since
    ``before`` (``jax.live_arrays()`` as set-up began): the weights, the
    KV pool and any state a family keeps beside it, wherever it is
    held."""
    engine.close()
    kept = {id(a) for a in before}
    for a in jax.live_arrays():
        if id(a) not in kept and not a.is_deleted():
            a.delete()
