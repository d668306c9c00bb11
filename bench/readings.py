"""What a per-layer metric's reader is given, and how readers are found.

A reader is ``bench/metrics/<metric name>.py`` with ``read(ctx)``
returning a number, or ``None`` where it finds nothing to read (the
metric is then left out of the run's line). ``ctx`` is a ``Readings``.
A reader that counts the model's work asks the configuration's family
(``ctx.family``, ``family.py``), never one family's counts directly.
"""
from __future__ import annotations

import importlib.util
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

from devtrace import Trace

BENCH = Path(__file__).resolve().parent


@dataclass
class Readings:
    family: ModuleType                # families/<reference>.py (family.py)
    dims: Dict                        # family.dims of the config
    peak: Dict                        # costs.peaks(device_kind)
    programs: Dict                    # phase -> regex of its program names
    counters: Dict[str, int]          # engine counters over the window
    trace: Optional[Trace] = None
    # host records of the steps dispatched inside the traced window:
    # "decode": one list of lane contexts per step; "prefill": prompt
    # lengths, one per prefilled request
    traced: Dict[str, List] = field(
        default_factory=lambda: {"decode": [], "prefill": []})

    def program_times(self, phase: str) -> List[float]:
        if self.trace is None or phase not in self.programs:
            return []
        return self.trace.program_times(self.programs[phase])


def prefill_ms_per_req(ctx: Readings) -> Optional[float]:
    pf = ctx.program_times("prefill")
    if not pf:
        return None
    return 1e3 * (sum(pf) + sum(ctx.program_times("cache"))) / len(pf)


def reader(name: str, bench_dir: Path = BENCH):
    """The ``read`` function of ``<bench_dir>/metrics/<name>.py``."""
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name):
        raise ValueError(f"bad metric name {name!r}")
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
