"""The engine's own spans in a traced window.

The engine puts its scheduler rounds, admissions, prefills, decode
dispatches, blocking reads, token emission, bus calls, first calls of
its step programs and Python's collections under profiler spans named
``engine.*``, on the host plane of the same trace as the device's
operations, with their arguments as event stats. Each ``engine.round``
carries the engine's cumulative counters (``rounds``, ``host_s``,
``first_call_s``, ...) as of its start, and each ``engine.prefill`` or
``engine.chunk`` its request's ``queue_ms``.

``devtrace.load`` keeps only the harness's ``host.*`` spans; ``of(ctx)``
reads the engine's from the same file, in the same window. A program
without such spans gives an empty list, and readers then find nothing.

    python bench/enginespans.py [trace dir]

prints, as JSON, ``summary`` of the newest trace: its longest idle gaps,
each named by the innermost engine span open at its midpoint (else by
the harness's host span, as ``devtrace`` names it), idle seconds by
that name, engine spans per round and the counters' change.
"""
from __future__ import annotations

import functools
import json
import os
import statistics
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import devtrace

# where run.py's traced window writes its profile
TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_out" / "trace"
PREFIX = "engine."


@dataclass(frozen=True)
class Span:
    """Seconds from the start of the traced window; ``args`` the span's
    arguments."""
    name: str
    start: float
    end: float
    args: Dict


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int):
    from jax.profiler import ProfileData
    window, found = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == devtrace.WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(PREFIX):
                    found.append((ev.name, ev.start_ns, ev.end_ns,
                                  {k: v for k, v in ev.stats}))
    if window is None:
        return None, []
    t0, t1 = window
    spans = sorted((Span(n, (s - t0) / 1e9, (e - t0) / 1e9, a)
                    for n, s, e, a in found if e > t0 and s < t1),
                   key=lambda sp: sp.start)
    return max(0.0, (t1 - t0) / 1e9), spans


def load(path: str):
    """(window seconds, engine spans overlapping it) of one trace file;
    the window is the harness's ``host.trace_window`` span, as in
    ``devtrace.load``, and ``(None, [])`` where there is none."""
    return _load(path, os.stat(path).st_mtime_ns)


def of(ctx, trace_dir: Path = TRACE_DIR) -> List[Span]:
    """The engine spans of the window that ``ctx.trace`` reduces, or
    none where there is no trace or no such window."""
    if ctx.trace is None:
        return []
    try:
        path = devtrace.latest_xplane(str(trace_dir))
    except FileNotFoundError:
        return []
    window_s, spans = load(path)
    if window_s != ctx.trace.window_s:
        return []            # another trace than the one ctx reduces
    return spans


def in_window(ctx, *names: str) -> List[Span]:
    """The engine spans named ``names`` that start inside the window."""
    return [s for s in of(ctx)
            if s.name in names and 0 <= s.start <= ctx.trace.window_s]


def _gaps(trace: devtrace.Trace) -> List:
    merged = devtrace._merge(trace.ops[0]) if trace.ops else []
    gaps, prev = [], 0.0
    for s, e in merged + [(trace.window_s, trace.window_s)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def _name_at(t: float, trace: devtrace.Trace, spans: List[Span]) -> str:
    """The innermost engine span open at ``t``, else the harness's host
    span, as ``devtrace`` names a gap."""
    open_ = [sp for sp in spans if sp.start <= t <= sp.end]
    if open_:
        return max(open_, key=lambda sp: sp.start).name
    host = [h for h, hs, he in trace.host if hs <= t <= he]
    return host[-1] if host else "host.none"


def idle_gaps(trace: devtrace.Trace, spans: List[Span],
              n: int = 10) -> List[List]:
    """The ``n`` longest spans with no operation on device 0, each as
    [name at its midpoint, seconds, start]."""
    gaps = sorted(_gaps(trace), key=lambda g: -(g[1] - g[0]))[:n]
    return [[_name_at((s + e) / 2, trace, spans), e - s, s] for s, e in gaps]


def summary(path: str) -> Dict:
    """What a traced window shows of the engine: its longest idle gaps
    (named at the midpoint, and at both edges) and all idle seconds by
    the span at each gap's midpoint; engine spans by name (count,
    longest, median self time, that is less the spans nested in it) and
    per round; and the counters' change over the window's rounds."""
    trace = devtrace.load(path)
    _, spans = load(path)
    idle: Dict[str, float] = {}
    for s, e in _gaps(trace):
        name = _name_at((s + e) / 2, trace, spans)
        idle[name] = idle.get(name, 0.0) + (e - s)
    inside = [sp for sp in spans if 0 <= sp.start <= trace.window_s]
    counts: Dict[str, int] = {}
    longest: Dict[str, float] = {}
    for sp in inside:
        counts[sp.name] = counts.get(sp.name, 0) + 1
        longest[sp.name] = max(longest.get(sp.name, 0.0), sp.end - sp.start)
    self_s: Dict[str, List[float]] = {}
    for sp in inside:
        kids = [(k.start, k.end) for k in inside if k is not sp
                and sp.start <= k.start and k.end <= sp.end]
        self_s.setdefault(sp.name, []).append(
            sp.end - sp.start - sum(e - s for s, e in devtrace._merge(
                [("", s, e) for s, e in kids])))
    rounds = [sp for sp in inside if sp.name == "engine.round"]
    delta = {}
    if len(rounds) > 1:
        a, b = rounds[0].args, rounds[-1].args
        delta = {k: b[k] - a[k] for k in a
                 if k not in ("active", "waiting")}
    return {"trace": path, "window_s": trace.window_s,
            "busy_s": trace.busy_s(), "idle_gaps": idle_gaps(trace, spans),
            "idle_s_by_span": idle, "spans": counts,
            "longest_s_by_span": longest,
            "median_self_s_by_span": {k: statistics.median(v)
                                      for k, v in self_s.items()},
            "gap_edges": [[_name_at(s + 1e-6, trace, spans),
                           _name_at(s + d - 1e-6, trace, spans)]
                          for _, d, s in idle_gaps(trace, spans)],
            "spans_per_round": len(inside) / max(1, len(rounds)),
            "counters_over_rounds": delta}


def main(argv: Optional[List[str]] = None):
    argv = sys.argv[1:] if argv is None else argv
    path = devtrace.latest_xplane(argv[0] if argv else str(TRACE_DIR))
    print(json.dumps(summary(path)))


if __name__ == "__main__":
    main()
