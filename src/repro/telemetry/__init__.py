"""Telemetry control plane: live export of the in-process probe data.

``bus`` is the pub/sub hub every session/engine publishes decode-side
aggregates to; ``server`` exposes it over HTTP (``/status``,
``/probes``, ``/mesh/skew``, ``/engine/phases``, ``/alerts``,
``/metrics``); ``sentinel`` watches the window stream for online drift
(p99 regressions, histogram shifts, straggler devices) and can trigger
a background DSE re-tune; ``spans`` puts the serving engine's host work
under profiler spans and keeps its host-time counters.  See
docs/telemetry.md.
"""
from repro.telemetry.bus import (ProbeStream, TelemetryBus, WindowFrame,
                                 hist_quantile)
from repro.telemetry.sentinel import (DriftEvent, DriftSentinel,
                                      SentinelConfig, make_retune_hook)
from repro.telemetry.server import (ControlPlane, StatusServer,
                                    render_metrics)

__all__ = [
    "TelemetryBus", "ProbeStream", "WindowFrame", "hist_quantile",
    "DriftSentinel", "DriftEvent", "SentinelConfig", "make_retune_hook",
    "ControlPlane", "StatusServer", "render_metrics",
]
