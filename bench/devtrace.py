"""Reduction of one profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are those named ``/device:TPU:<n>``. On each, the
``XLA Modules`` line holds one event per program execution, named by
the program (``jit_decode(…)``), and the ``XLA Ops`` line one event per
operation. Host spans are the ``TraceAnnotation``s the harness writes
(names starting with ``host.``) on the host plane. All times are the
trace's own nanoseconds, one clock for host and device.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "host."
WINDOW_SPAN = "host.trace_window"   # the harness's span around the window


@dataclass
class Trace:
    """Intervals in seconds from the start of the traced window."""
    window_s: float
    ops: List[List[Tuple[str, float, float]]]        # per device
    modules: List[List[Tuple[str, float, float]]]    # per device
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def busy_s(self) -> float:
        """Union of operation intervals, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(_union(iv) for iv in self.ops) / len(self.ops)

    def program_times(self, pattern: str) -> List[float]:
        """Device seconds of each execution (device 0) of the programs
        whose name matches ``pattern``."""
        if not self.modules:
            return []
        rx = re.compile(pattern)
        return [e - s for n, s, e in self.modules[0] if rx.search(n)]

    def top_ops(self, n: int = 10) -> List[List]:
        tot: Dict[str, float] = {}
        for name, s, e in (self.ops[0] if self.ops else []):
            tot[name] = tot.get(name, 0.0) + (e - s)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest spans with no operation on device 0, each named by
        the host span under its midpoint (``host.none`` if none)."""
        merged = _merge(self.ops[0]) if self.ops else []
        gaps, prev = [], 0.0
        for s, e in merged + [(self.window_s, self.window_s)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            names = [h for h, hs, he in self.host if hs <= mid <= he]
            out.append([names[-1] if names else "host.none", e - s])
        return out


def _merge(iv):
    out = []
    for s, e in sorted((s, e) for _, s, e in iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _union(iv) -> float:
    return sum(e - s for s, e in _merge(iv))


def _stat(ev, key) -> Optional[str]:
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return None


def latest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, t0_ns: Optional[float] = None,
         t1_ns: Optional[float] = None) -> Trace:
    """Read ``path``. The window runs from ``t0_ns`` to ``t1_ns`` (trace
    clock); by default over the harness's ``host.trace_window`` span, or
    from the first to the last event where there is none."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            o, m = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        o.append((_op_name(ev), ev.start_ns, ev.end_ns))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        m.append((ev.name, ev.start_ns, ev.end_ns))
            ops.append(o)
            modules.append(m)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns, ev.end_ns))
    for iv in ops + [host]:
        for _, s, e in iv:
            lo, hi = min(lo, s), max(hi, e)
    span = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if span:
        lo, hi = span[0]
    t0 = lo if t0_ns is None else t0_ns
    t1 = hi if t1_ns is None else t1_ns
    host = [h for h in host if h[0] != WINDOW_SPAN]

    def clip(iv):
        return [(n, (max(s, t0) - t0) / 1e9, (min(e, t1) - t0) / 1e9)
                for n, s, e in iv if e > t0 and s < t1]

    return Trace(window_s=max(0.0, (t1 - t0) / 1e9),
                 ops=[clip(o) for o in ops],
                 modules=[clip(m) for m in modules], host=clip(host))


def _op_name(ev) -> str:
    """An operation's program and name scope, when the trace gives them."""
    scope = _stat(ev, "tf_op") or _stat(ev, "long_name") or ev.name
    module = _stat(ev, "hlo_module")
    return f"{module}:{scope}" if module else scope
