"""Automated design-space exploration (paper §IV-E, Fig 13).

Two DSE loops live here:

**run_dse** explores profiling configurations — storage class
(register-like shallow rings, BRAM-like deep rings, hybrid) x DRAM dump
ratio (0/25/50/75%) — and scores each on the paper's three metrics:

  1) resource overhead      on-device state bytes + extra HLO equations
                            (weighted, relative to the base program),
  2) DRAM bandwidth         measured offloaded bytes / profiled span,
  3) latency impact         measured wall-time of the instrumented step
                            relative to the unprobed step (Fmax analogue).

It returns all points plus the Pareto-optimal subset. Incremental
re-instrumentation (cached trace/hierarchy) is what makes the sweep
cheap — each point only rebuilds the probe layer, like the paper's
incremental synthesis.

**DSEEngine** closes the paper's second loop: probe telemetry driving
*kernel-configuration* search under device resource budgets. Given a
:class:`SearchSpace` (tile sizes / pipeline depth per Pallas kernel) it

  1) enumerates candidate configs,
  2) prunes statically with the cost model against a
     :class:`~repro.core.costmodel.DeviceBudget` (VMEM bytes, HBM
     traffic, FLOPs — the LUT/FF/BRAM-constraint analogue),
  3) measures survivors with ``ProbeSession`` cycle telemetry under
     successive halving (cheap configs get few steps, finalists many),
  4) memoizes every measurement in the on-disk
     :class:`~repro.core.incremental.EvalCache` keyed by (kernel id,
     config, lowered-IR hash, device kind) — re-running after an
     unrelated edit re-measures nothing.
"""
from __future__ import annotations

import itertools
import math
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.buffer import state_bytes
from repro.core.costmodel import (CLOCK_HZ, DeviceBudget, KernelResources,
                                  jaxpr_kernel_resources)
from repro.core.incremental import (EvalCache, device_kind,
                                    fingerprint_closed)
from repro.core.instrument import decode_record
from repro.core.pragma import ProbeConfig, probe

STORAGE_DEPTH = {"registers": 4, "hybrid": 16, "bram": 64}


@dataclass
class DSEPoint:
    storage: str
    depth: int
    offload_ratio: float
    n_probes: int
    state_bytes: int
    extra_eqns: int
    dram_bytes: int
    dram_bandwidth_bps: float        # modeled at the TPU clock
    latency_overhead: float          # measured wall-time ratio - 1
    weighted_resource: float

    def dominates(self, o: "DSEPoint") -> bool:
        a = (self.weighted_resource, self.dram_bandwidth_bps,
             self.latency_overhead)
        b = (o.weighted_resource, o.dram_bandwidth_bps, o.latency_overhead)
        return all(x <= y for x, y in zip(a, b)) and a != b


@dataclass
class DSEResult:
    points: List[DSEPoint]
    pareto: List[DSEPoint]

    def best(self) -> Optional[DSEPoint]:
        return min(self.pareto,
                   key=lambda p: p.weighted_resource + p.latency_overhead,
                   default=None)

    def table(self) -> str:
        hdr = (f"{'storage':<10}{'depth':>6}{'dump%':>7}{'probes':>8}"
               f"{'state_B':>9}{'xeqns':>7}{'dram_B':>8}{'bw_MBps':>9}"
               f"{'lat_ovh':>9}  pareto")
        lines = [hdr]
        ps = {id(p) for p in self.pareto}
        for p in self.points:
            lines.append(
                f"{p.storage:<10}{p.depth:>6}{p.offload_ratio * 100:>6.0f}%"
                f"{p.n_probes:>8}{p.state_bytes:>9}{p.extra_eqns:>7}"
                f"{p.dram_bytes:>8}{p.dram_bandwidth_bps / 1e6:>9.3f}"
                f"{p.latency_overhead * 100:>8.2f}%"
                f"  {'*' if id(p) in ps else ''}")
        return "\n".join(lines)


def _timeit(f, *args, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = f(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def run_dse(fn: Callable, args: Sequence[Any],
            base_cfg: ProbeConfig = ProbeConfig(),
            storages: Sequence[str] = ("registers", "hybrid", "bram"),
            offload_ratios: Sequence[float] = (0.0, 0.25, 0.5, 0.75),
            resource_weights: Tuple[float, float] = (1.0, 1.0),
            repeats: int = 3) -> DSEResult:
    from repro.core.overhead import measure_overhead

    base_jit = jax.jit(fn)
    base_jit(*args)                       # compile
    t_base = _timeit(base_jit, *args, repeats=repeats)
    base_eqns = None

    pf = probe(fn, base_cfg)              # shared trace across the sweep
    pf.trace(*args)

    points: List[DSEPoint] = []
    for storage in storages:
        depth = STORAGE_DEPTH[storage]
        for ratio in offload_ratios:
            cfg = base_cfg.replace(buffer_depth=depth, offload=ratio)
            pf.retarget(cfg)
            pf.sink.reset()
            out, rec = pf(*args)          # compile + run
            t_inst = _timeit(pf, *args, repeats=repeats)
            span = decode_record(jax.device_get(rec))["cycle"]
            span_s = max(span / CLOCK_HZ, 1e-12)
            ov = measure_overhead(fn, args, cfg)
            if base_eqns is None:
                base_eqns = ov["base_eqns"]
            sbytes = state_bytes(pf.assignment.n, depth)
            wres = (resource_weights[0] * sbytes / 1024.0 +
                    resource_weights[1] * ov["extra_eqns"] /
                    max(ov["base_eqns"], 1))
            points.append(DSEPoint(
                storage=storage, depth=depth, offload_ratio=ratio,
                n_probes=pf.assignment.n, state_bytes=sbytes,
                extra_eqns=ov["extra_eqns"],
                dram_bytes=pf.sink.bytes_received,
                dram_bandwidth_bps=pf.sink.bytes_received / span_s,
                latency_overhead=max(t_inst / max(t_base, 1e-12) - 1.0, 0.0),
                weighted_resource=wres))
    pareto = [p for p in points
              if not any(o.dominates(p) for o in points)]
    return DSEResult(points=points, pareto=pareto)


# ===================================================================
# Kernel-configuration autotuning (probe-guided, budget-constrained)
# ===================================================================

@dataclass
class SearchSpace:
    """Declarative candidate space for one kernel.

    ``axes`` maps axis name -> allowed values; candidates are the
    cartesian product filtered through ``is_valid``. ``bind(config)``
    returns a callable taking ``args`` (example inputs at the shapes
    being tuned) that executes the kernel under that config.
    ``default`` is the untuned baseline the leaderboard compares
    against.
    """
    kernel_id: str
    axes: Dict[str, Tuple[Any, ...]]
    bind: Callable[[Dict[str, Any]], Callable]
    args: Tuple[Any, ...]
    default: Dict[str, Any]
    is_valid: Optional[Callable[[Dict[str, Any]], bool]] = None

    def candidates(self) -> List[Dict[str, Any]]:
        names = sorted(self.axes)
        out = []
        for combo in itertools.product(*(self.axes[n] for n in names)):
            cfg = dict(zip(names, combo))
            if self.is_valid is None or self.is_valid(cfg):
                out.append(cfg)
        return out


@dataclass
class Trial:
    """One candidate's journey through the engine."""
    config: Dict[str, Any]
    resources: Optional[KernelResources] = None
    fingerprint: str = ""
    pruned: Optional[str] = None          # reason, when statically rejected
    cycles_per_step: Optional[float] = None
    steps: int = 0                        # largest rung this trial ran at
    cache_hits: int = 0
    measurements: int = 0
    is_default: bool = False
    # grid-step calibration (``DSEEngine.measure_tiles``): per-tile
    # cycles from the kernel-probed counters vs the cost model's static
    # per-tile estimate; residual = static − measured (positive = the
    # model over-prices tiles, e.g. causal skips it cannot see).
    # tile_dma is the per-step block-DMA term, identical in both, so
    # the calibration ratio is taken over the body term alone.
    tile_static: Optional[float] = None
    tile_measured: Optional[float] = None
    tile_residual: Optional[float] = None
    tile_dma: Optional[float] = None

    @property
    def measured(self) -> bool:
        return self.cycles_per_step is not None


@dataclass
class TuneResult:
    kernel_id: str
    trials: List[Trial]
    best: Optional[Trial]
    default: Optional[Trial]
    n_candidates: int
    n_pruned: int
    n_measurements: int                   # ProbeSession runs performed
    n_cache_hits: int
    measured_steps: int                   # total steps across measurements
    wall_s: float
    device: str = ""

    @property
    def speedup(self) -> float:
        """Default cycles/step over best cycles/step (>1 = tuned wins)."""
        if (self.best is None or self.default is None
                or not self.default.measured or not self.best.measured):
            return 1.0
        return self.default.cycles_per_step / max(self.best.cycles_per_step,
                                                  1e-12)

    def leaderboard(self, top: int = 10) -> str:
        from repro.core import report as report_mod
        return report_mod.dse_leaderboard(self, top=top)

    def to_dict(self) -> Dict[str, Any]:
        def trial(t: Optional[Trial]):
            if t is None:
                return None
            return {"config": t.config, "pruned": t.pruned,
                    "cycles_per_step": t.cycles_per_step, "steps": t.steps,
                    "cache_hits": t.cache_hits,
                    "measurements": t.measurements,
                    "is_default": t.is_default,
                    "tile_residual": t.tile_residual}
        return {
            "kernel": self.kernel_id, "device": self.device,
            "n_candidates": self.n_candidates, "n_pruned": self.n_pruned,
            "n_measurements": self.n_measurements,
            "n_cache_hits": self.n_cache_hits,
            "measured_steps": self.measured_steps,
            "speedup": round(self.speedup, 4),
            "best": trial(self.best), "default": trial(self.default),
            "trials": [trial(t) for t in self.trials],
        }


class DSEEngine:
    """Probe-guided autotuner for Pallas kernel configurations.

    ``tune()`` runs enumerate -> static-prune -> successive-halving
    measurement -> cache, and returns a :class:`TuneResult`. The
    baseline (``space.default``) is always measured alongside the
    survivors so the leaderboard's speedup is honest.

    Successive halving: every surviving candidate runs ``r0`` probed
    steps; the best ``1/eta`` fraction advances with ``eta``x the steps,
    until one remains or ``max_steps`` is reached. All measurements go
    through the :class:`EvalCache`, so a warm re-run performs zero new
    measurements.
    """

    def __init__(self, space: SearchSpace, *,
                 budget: Optional[DeviceBudget] = DeviceBudget(),
                 cache: Optional[EvalCache] = None,
                 cache_dir: Optional[str] = None,
                 cycle_source: str = "model",
                 r0: int = 1, eta: int = 2, max_steps: int = 4,
                 static_prune_ratio: Optional[float] = None):
        if r0 < 1 or eta < 2 or max_steps < r0:
            raise ValueError(f"bad halving schedule r0={r0} eta={eta} "
                             f"max_steps={max_steps}")
        self.space = space
        self.budget = budget
        self.cache = cache if cache is not None else EvalCache(cache_dir)
        self.cycle_source = cycle_source
        self.r0, self.eta, self.max_steps = r0, eta, max_steps
        self.static_prune_ratio = static_prune_ratio
        self.device = device_kind()
        # kernel body names observed by measure_tiles (calibrate targets)
        self._tile_kernels: set = set()
        # run accounting (reset per tune())
        self.n_measurements = 0
        self.n_cache_hits = 0
        self.measured_steps = 0

    # -- stage 1+2: enumerate & statically analyze ----------------------
    def analyze(self, config: Dict[str, Any]) -> Trial:
        """Trace one candidate; attach its IR hash and the cost-model
        resource footprint (no execution)."""
        fn = self.space.bind(config)
        closed = jax.make_jaxpr(fn)(*self.space.args)
        fp = fingerprint_closed(closed)
        res = jaxpr_kernel_resources(closed.jaxpr)
        return Trial(config=dict(config), resources=res, fingerprint=fp)

    def prune(self, trials: Sequence[Trial]) -> List[Trial]:
        """Static rejection against the device budget; optionally also
        drop candidates whose cost-model estimate exceeds
        ``static_prune_ratio`` x the best static estimate. Hard budget
        checks can never discard a config that actually fits the device,
        so the measured-best always survives default pruning."""
        alive = []
        for t in trials:
            if self.budget is not None and t.resources is not None:
                v = self.budget.violations(t.resources)
                if v:
                    t.pruned = "; ".join(v)
                    continue
            alive.append(t)
        if self.static_prune_ratio is not None and alive:
            floor = min(t.resources.static_cycles for t in alive
                        if t.resources is not None)
            kept = []
            for t in alive:
                if (t.resources is not None and floor > 0 and
                        t.resources.static_cycles >
                        self.static_prune_ratio * floor):
                    t.pruned = (f"static {t.resources.static_cycles} cyc > "
                                f"{self.static_prune_ratio:g}x floor {floor}")
                else:
                    kept.append(t)
            alive = kept
        return alive

    # -- stage 3: probed measurement ------------------------------------
    def _measure(self, config: Dict[str, Any], steps: int) -> float:
        """Run ``steps`` probed steps of the candidate under a
        ``ProbeSession``; returns mean cycles/step from the session's
        device span counter."""
        from repro.core.streaming import ProbeSession
        fn = self.space.bind(config)
        cfg = ProbeConfig(targets=("",), max_probes=4, buffer_depth=2,
                          cycle_source=self.cycle_source)
        with ProbeSession(fn, cfg, window_steps=steps + 1) as s:
            for _ in range(steps):
                jax.block_until_ready(s.step(*self.space.args))
            snap = s.snapshot()
        self.n_measurements += 1
        self.measured_steps += steps
        return snap.span / max(steps, 1)

    def _eval_fingerprint(self, t: Trial) -> str:
        """Trial fingerprint extended with the installed kernel-
        calibration state: measured cycles come from the model clock,
        whose pallas pricing is scaled by ``costmodel``'s process-
        global calibration — cycles measured under different
        calibrations must never collide under one cache key. The
        uncalibrated state leaves the key unchanged (existing caches
        stay warm)."""
        from repro.core.costmodel import kernel_calibration_state
        state = kernel_calibration_state()
        if not state:
            return t.fingerprint
        tag = ";".join(f"{k}={v:.6f}" for k, v in state)
        return f"{t.fingerprint}|calib[{tag}]"

    def evaluate(self, t: Trial, steps: int) -> float:
        """Cache-through evaluation at a rung of ``steps`` steps."""
        fp = self._eval_fingerprint(t)
        hit = self.cache.get(self.space.kernel_id, t.config, fp,
                             self.device, min_steps=steps)
        if hit is not None:
            t.cache_hits += 1
            self.n_cache_hits += 1
            t.cycles_per_step = float(hit["cycles_per_step"])
            t.steps = max(t.steps, int(hit["steps"]))
            return t.cycles_per_step
        cps = self._measure(t.config, steps)
        t.measurements += 1
        t.cycles_per_step = cps
        t.steps = steps
        self.cache.put(self.space.kernel_id, t.config, fp,
                       self.device, cycles_per_step=cps, steps=steps)
        return cps

    # -- grid-step calibration (measured per-tile cycles) ----------------
    def measure_tiles(self, t: Trial) -> Trial:
        """Probe the candidate with intra-kernel grid-step counters and
        record per-tile cycles on the trial.

        ``tile_measured`` is the mean measured cycles per grid step
        (sum of grid-probe totals over grid-probe calls — exact model-
        clock counters that see ``pl.when`` skips), ``tile_static`` the
        cost model's flat per-step estimate, ``tile_residual`` their
        gap. The kernel body names observed are remembered as
        ``calibrate()`` targets."""
        from repro.core.pragma import probe as _probe

        from repro.core import costmodel as _cm
        from repro.core import kernelprobe as _kp

        fn = self.space.bind(t.config)
        cfg = ProbeConfig(targets=("",), max_probes=16, buffer_depth=2,
                          cycle_source=self.cycle_source,
                          kernel_probes=("*",), inline="off_all")
        pf = _probe(fn, cfg)
        # retarget onto the kernel subtrees so deep grid probes can
        # never be crowded out of the probe budget by shallow wrapper
        # scopes (selection is preorder/shallow-first)
        h = pf.trace(*self.space.args)
        kpaths = tuple(n.path for n in h.root.walk() if n.kind == "kernel")
        if not kpaths:
            raise ValueError(
                f"measure_tiles({t.config}): the bound function has no "
                f"statically-gridded pallas kernels to probe")
        pf.retarget(cfg.replace(targets=kpaths))
        _, rec = pf(*self.space.args)
        dec = decode_record(jax.device_get(rec))
        grid_total = grid_calls = 0
        for i, path in enumerate(pf.probe_paths()):
            if path.endswith("/grid"):
                grid_total += int(dec["totals"][i])
                grid_calls += int(dec["calls"][i])
                # <scope>/kernel/<name>#i/grid -> <name>
                self._tile_kernels.add(
                    path.rsplit("/", 2)[-2].split("#")[0])
        if grid_calls:
            t.tile_measured = grid_total / grid_calls
        # per-step DMA term (shared by measured and static tiles): from
        # the traced pallas equations, steps-weighted across kernels
        dma_total = steps_total = 0
        for pe in _cm._walk_pallas_eqns(pf.hierarchy.closed_jaxpr.jaxpr):
            g = _kp.static_grid(pe)
            if g is None:
                continue
            s = int(np.prod(g))
            dma_total += _kp.dma_cycles(pe) * s
            steps_total += s
        if steps_total:
            t.tile_dma = dma_total / steps_total
        if t.resources is not None and t.resources.grid_steps:
            t.tile_static = (t.resources.static_cycles /
                             t.resources.grid_steps)
        if t.tile_measured is not None and t.tile_static is not None:
            t.tile_residual = t.tile_static - t.tile_measured
        return t

    def calibration(self, trials: Optional[Sequence[Trial]] = None
                    ) -> Optional[float]:
        """measured/static ratio of the per-tile BODY term (the DMA
        term is identical on both sides and is not scaled by
        ``costmodel._pallas_cost``, so it is subtracted before the
        ratio — otherwise calibration could not converge even on the
        trial it was measured from)."""
        ratios = []
        for t in (trials if trials is not None else []):
            if t.tile_measured is None or not t.tile_static:
                continue
            dma = t.tile_dma or 0.0
            body_static = t.tile_static - dma
            if body_static <= 0:
                continue
            ratios.append(max(t.tile_measured - dma, 0.0) / body_static)
        if not ratios:
            return None
        return float(np.mean(ratios))

    def calibrate(self, trials: Sequence[Trial]) -> Optional[float]:
        """Install the measured per-tile ratio into the cost model's
        block-level body term (``costmodel.set_kernel_calibration``)
        for every kernel body seen by ``measure_tiles``. Subsequent
        ``analyze()`` / prune passes then price tiles with measured
        grid-step cycles. Returns the scale (None without tile data);
        undo with ``costmodel.clear_kernel_calibration()``."""
        from repro.core import costmodel as _cm

        scale = self.calibration(trials)
        if scale is None:
            return None
        for kname in sorted(self._tile_kernels):
            _cm.set_kernel_calibration(kname, scale)
        return scale

    def successive_halving(self, trials: List[Trial]) -> Optional[Trial]:
        active = list(trials)
        r = self.r0
        while active:
            for t in active:
                self.evaluate(t, r)
            active.sort(key=lambda t: t.cycles_per_step)
            if len(active) == 1 or r >= self.max_steps:
                return active[0]
            keep = max(1, math.ceil(len(active) / self.eta))
            active = active[:keep]
            r = min(r * self.eta, self.max_steps)
        return None

    # -- the whole loop --------------------------------------------------
    def tune(self) -> TuneResult:
        self.n_measurements = self.n_cache_hits = self.measured_steps = 0
        t0 = time.perf_counter()
        configs = self.space.candidates()
        trials = [self.analyze(c) for c in configs]
        default_trial = None
        for t in trials:
            if t.config == self.space.default:
                t.is_default = True
                default_trial = t
        survivors = self.prune(trials)
        best = self.successive_halving(survivors)
        # always measure the baseline (even if pruned / not in the space),
        # at the SAME rung as the finalist — comparing a 1-step sample
        # against a max_steps mean is meaningless under wallclock noise
        if default_trial is None:
            default_trial = self.analyze(self.space.default)
            default_trial.is_default = True
            trials.append(default_trial)
        base_steps = best.steps if (best is not None and best.measured) \
            else self.r0
        if not default_trial.measured or default_trial.steps < base_steps:
            self.evaluate(default_trial, base_steps)
        if best is None or (default_trial.measured and best.measured and
                            default_trial.cycles_per_step
                            <= best.cycles_per_step):
            best = default_trial
        if best is not None and best.measured:
            shape = str([(tuple(getattr(a, "shape", ())),
                          str(getattr(a, "dtype", "?")))
                         for a in jax.tree_util.tree_leaves(self.space.args)])
            self.cache.set_winner(self.space.kernel_id, self.device,
                                  best.config,
                                  cycles_per_step=best.cycles_per_step,
                                  shape=shape)
        return TuneResult(
            kernel_id=self.space.kernel_id, trials=trials, best=best,
            default=default_trial, n_candidates=len(configs),
            n_pruned=sum(1 for t in trials if t.pruned is not None),
            n_measurements=self.n_measurements,
            n_cache_hits=self.n_cache_hits,
            measured_steps=self.measured_steps,
            wall_s=time.perf_counter() - t0, device=self.device)


# ===================================================================
# Trace-once sweep farm (simulator-first, multi-process, shared cache)
# ===================================================================
#
# Successive halving measures tens of candidates; the sweep farm covers
# thousands. The phases:
#
#   1. capture  — workers trace each missing (config, shape) once and
#                 merge the KernelTrace artifacts into the shared
#                 TraceStore (no device execution);
#   2. calibrate — one kernel-probed device run on the first shape
#                 installs the measured/static body ratio
#                 (``DSEEngine.measure_tiles`` + ``calibrate``), which
#                 transfers to every other shape through the artifacts;
#   3. simulate — the parent re-prices EVERY candidate from the
#                 artifacts in microseconds (flat mode: the same clock
#                 device measurement produces), prunes against the
#                 budget, and ranks;
#   4. measure  — only the per-shape finalists (default + top priced)
#                 run on the device, in the parent, through the shared
#                 EvalCache.
#
# Capture workers run in *spawned* processes pinned to the CPU backend:
# a chip belongs to one process, and the parent holds it for phases 2
# and 4. Tasks carry only plain data, spaces are rebuilt by name via
# ``search_spaces.sweep_space`` (bind closures don't pickle), and the
# installed calibration state is re-applied inside the worker.

@dataclass
class SweepShapeOutcome:
    shape: Dict[str, Any]
    n_candidates: int
    n_pruned: int
    best_config: Optional[Dict[str, Any]] = None
    best_cycles: Optional[float] = None
    default_config: Optional[Dict[str, Any]] = None
    default_cycles: Optional[float] = None

    @property
    def speedup(self) -> float:
        if not self.best_cycles or not self.default_cycles:
            return 1.0
        return self.default_cycles / max(self.best_cycles, 1e-12)


@dataclass
class SweepResult:
    kernel_id: str
    device: str
    shapes: List[SweepShapeOutcome]
    n_candidates: int             # configs x shapes enumerated
    n_captured: int               # traces captured this run (rest reused)
    n_pruned: int
    n_priced: int                 # simulator-priced candidates
    n_finalists: int
    n_measured: int               # ProbeSession device runs performed
    n_cache_hits: int
    n_calibration_runs: int
    calibration_scale: Optional[float]
    workers: int
    top_k: int
    price_wall_s: float           # capture phase
    sim_wall_s: float             # pure artifact re-pricing
    measure_wall_s: float
    wall_s: float

    @property
    def sim_us_per_config(self) -> float:
        return 1e6 * self.sim_wall_s / max(self.n_candidates, 1)

    def summary(self) -> str:
        lines = [
            f"sweep {self.kernel_id} on {self.device}: "
            f"{self.n_candidates} candidates over {len(self.shapes)} "
            f"shapes, {self.n_pruned} pruned, {self.n_finalists} "
            f"finalists, {self.n_measured} device measurements "
            f"({self.n_cache_hits} cache hits)",
            f"  capture {self.price_wall_s:.2f}s "
            f"({self.n_captured} traced, rest reused) | simulate "
            f"{self.sim_wall_s * 1e3:.1f}ms "
            f"({self.sim_us_per_config:.1f}us/config) | measure "
            f"{self.measure_wall_s:.2f}s",
        ]
        if self.calibration_scale is not None:
            lines.append(f"  calibration scale {self.calibration_scale:.4f} "
                         f"(transferred to all shapes)")
        for o in self.shapes:
            lines.append(
                f"  {o.shape}: best {o.best_config} "
                f"{o.best_cycles if o.best_cycles is not None else float('nan'):.0f} cyc/step, "
                f"{o.speedup:.2f}x vs default")
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kernel": self.kernel_id, "device": self.device,
            "n_candidates": self.n_candidates,
            "n_captured": self.n_captured, "n_pruned": self.n_pruned,
            "n_priced": self.n_priced, "n_finalists": self.n_finalists,
            "n_measured": self.n_measured,
            "n_cache_hits": self.n_cache_hits,
            "n_calibration_runs": self.n_calibration_runs,
            "calibration_scale": self.calibration_scale,
            "workers": self.workers, "top_k": self.top_k,
            "sim_us_per_config": round(self.sim_us_per_config, 3),
            "shapes": [{
                "shape": o.shape, "n_candidates": o.n_candidates,
                "n_pruned": o.n_pruned, "best": o.best_config,
                "best_cycles": o.best_cycles, "default": o.default_config,
                "default_cycles": o.default_cycles,
                "speedup": round(o.speedup, 4)} for o in self.shapes],
        }


def _sweep_worker(task: Dict[str, Any]) -> Dict[str, Any]:
    """One farm work unit; must stay module-level and take/return plain
    data only (it crosses the spawn pickle boundary)."""
    from repro.core import costmodel as _cm
    from repro.core import tracesim as _ts
    from repro.kernels import search_spaces as _ss

    _cm.clear_kernel_calibration()
    for kname, scale in task.get("calibration", ()):
        _cm.set_kernel_calibration(kname, float(scale))
    space = _ss.sweep_space(task["kernel"], **task["shape"])
    out: Dict[str, Any] = {"shape_idx": task["shape_idx"], "rows": [],
                           "measurements": 0, "cache_hits": 0}
    if task["phase"] == "capture":
        trace = _ts.KernelTrace(kernel_id=space.kernel_id,
                                shape=_ts.shape_signature(space.args),
                                space_fingerprint=task["space_fp"])
        for cfg in task["configs"]:
            trace.entries[_ts.config_key(cfg)] = _ts.capture_entry(
                space, cfg, walk=task.get("walk", False))
        _ts.TraceStore(task["cache_dir"]).merge(trace)
        out["captured"] = len(task["configs"])
        return out
    # phase == "measure": probed device runs through the shared cache
    engine = DSEEngine(space, budget=None,
                       cache=EvalCache(task["cache_dir"]),
                       cycle_source=task.get("cycle_source", "model"),
                       r0=task["steps"], max_steps=task["steps"])
    for cfg in task["configs"]:
        t = engine.analyze(cfg)
        cps = engine.evaluate(t, task["steps"])
        out["rows"].append({"config": cfg, "cycles": float(cps),
                            "steps": int(t.steps)})
    out["measurements"] = engine.n_measurements
    out["cache_hits"] = engine.n_cache_hits
    return out


def _cpu_worker_init():
    """Capture workers only trace: keep them off the accelerator, which
    the parent process holds."""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def _run_captures(tasks: List[Dict[str, Any]], workers: int) -> List[Dict]:
    if workers > 1 and len(tasks) > 1:
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor
        ctx = mp.get_context("spawn")
        with ProcessPoolExecutor(max_workers=workers, mp_context=ctx,
                                 initializer=_cpu_worker_init) as ex:
            return list(ex.map(_sweep_worker, tasks))
    return [_sweep_worker(t) for t in tasks]


def _chunked(seq: List[Any], size: int) -> List[List[Any]]:
    return [seq[i:i + size] for i in range(0, len(seq), max(size, 1))]


def run_sweep(kernel_id: str,
              shapes: Optional[Sequence[Dict[str, Any]]] = None, *,
              workers: int = 2, top_k: int = 16, steps: int = 4,
              budget: Optional[DeviceBudget] = DeviceBudget(),
              cache: Optional[EvalCache] = None,
              cache_dir: Optional[str] = None,
              calibrate: bool = False, walk: bool = False,
              chunk: int = 64, cycle_source: str = "model",
              reuse_traces: bool = True) -> SweepResult:
    """Simulator-first DSE over configs x shapes (see the phase map
    above). Device measurement is reserved for at most
    ``max(2, top_k // n_shapes)`` finalists per shape — the default
    config plus the top simulator-priced survivors — no matter how many
    candidates the sweep enumerates."""
    from repro.core import costmodel as _cm
    from repro.core import tracesim as ts
    from repro.kernels import search_spaces as ss

    t_start = time.perf_counter()
    shape_list = [dict(s) for s in
                  (shapes if shapes is not None
                   else ss.sweep_shapes(kernel_id))]
    cache = cache if cache is not None else EvalCache(cache_dir)
    store = ts.TraceStore(cache.root)
    device = device_kind()

    spaces = [ss.sweep_space(kernel_id, **sh) for sh in shape_list]
    space_fps = [ts.space_fingerprint(sp) for sp in spaces]
    shape_sigs = [ts.shape_signature(sp.args) for sp in spaces]
    cand_lists = [sp.candidates() for sp in spaces]
    for sp, cands in zip(spaces, cand_lists):
        if sp.default not in cands:
            cands.append(sp.default)
    n_candidates = sum(len(c) for c in cand_lists)

    # -- phase 1: capture missing traces (workers, no device) ----------
    t0 = time.perf_counter()
    tasks = []
    for i, (sh, sig, sfp, cands) in enumerate(
            zip(shape_list, shape_sigs, space_fps, cand_lists)):
        stored = (store.load(kernel_id, sig, sfp)
                  if reuse_traces else None)
        have = set(stored.entries) if stored is not None else set()
        missing = [c for c in cands if ts.config_key(c) not in have]
        for part in _chunked(missing, chunk):
            tasks.append({"phase": "capture", "kernel": kernel_id,
                          "shape": sh, "shape_idx": i, "configs": part,
                          "walk": walk, "cache_dir": cache.root,
                          "space_fp": sfp, "calibration": ()})
    n_captured = sum(r.get("captured", 0)
                     for r in _run_captures(tasks, workers))
    price_wall = time.perf_counter() - t0
    traces = [store.load(kernel_id, sig, sfp)
              for sig, sfp in zip(shape_sigs, space_fps)]
    for i, tr in enumerate(traces):
        if tr is None:
            raise RuntimeError(
                f"sweep capture produced no trace for shape "
                f"{shape_list[i]} (store {store.root})")

    # -- phase 2: one calibration run, transferred to every shape ------
    scale = None
    calib_runs = 0
    if calibrate:
        sp0, tr0 = spaces[0], traces[0]
        # calibrate on the unpruned candidate with the MOST grid steps:
        # fine tiles see the most pl.when causal-skip structure, which
        # is exactly the signal the flat estimate cannot price
        pick = min(
            (c for c in cand_lists[0]
             if budget is None or not budget.violations(
                 ts.entry_resources(tr0.entries[ts.config_key(c)]))),
            key=lambda c: (-tr0.entries[ts.config_key(c)].grid_steps,
                           ts.price(tr0, c, mode="flat"),
                           ts.config_key(c)),
            default=sp0.default)
        engine = DSEEngine(sp0, budget=None, cache=cache,
                           cycle_source=cycle_source, r0=steps,
                           max_steps=steps)
        trial = engine.analyze(pick)
        engine.measure_tiles(trial)
        calib_runs = 1
        scale = engine.calibrate([trial])

    # -- phase 3: simulate every candidate from the artifacts ----------
    t0 = time.perf_counter()
    ranked: List[List[Tuple[int, Dict[str, Any]]]] = []
    outcomes: List[SweepShapeOutcome] = []
    n_pruned = n_priced = 0
    for sh, sp, tr, cands in zip(shape_list, spaces, traces, cand_lists):
        rows = []
        pruned_here = 0
        for cfg in cands:
            entry = tr.entries[ts.config_key(cfg)]
            if budget is not None and budget.violations(
                    ts.entry_resources(entry)):
                pruned_here += 1
                continue
            rows.append((ts.price(entry, mode="flat"), cfg))
        rows.sort(key=lambda rc: (rc[0], ts.config_key(rc[1])))
        ranked.append(rows)
        n_pruned += pruned_here
        n_priced += len(rows)
        outcomes.append(SweepShapeOutcome(
            shape=sh, n_candidates=len(cands), n_pruned=pruned_here,
            default_config=dict(sp.default)))
    sim_wall = time.perf_counter() - t0

    # -- phase 4: measure only the finalists (parent, shared cache) ----
    per_shape = max(2, top_k // max(len(shape_list), 1))
    t0 = time.perf_counter()
    tasks = []
    finalists_per_shape: List[List[Dict[str, Any]]] = []
    calib_state = [(k, v) for k, v in _cm.kernel_calibration_state()]
    for i, (sp, rows) in enumerate(zip(spaces, ranked)):
        finalists = [dict(sp.default)]
        for _, cfg in rows:
            if len(finalists) >= per_shape:
                break
            if cfg != sp.default:
                finalists.append(cfg)
        finalists_per_shape.append(finalists)
        tasks.append({"phase": "measure", "kernel": kernel_id,
                      "shape": shape_list[i], "shape_idx": i,
                      "configs": finalists, "steps": steps,
                      "cache_dir": cache.root,
                      "cycle_source": cycle_source,
                      "calibration": calib_state})
    n_measured = n_cache_hits = 0
    measured: List[Dict[str, List]] = [{"rows": []} for _ in shape_list]
    for res in map(_sweep_worker, tasks):
        n_measured += res["measurements"]
        n_cache_hits += res["cache_hits"]
        measured[res["shape_idx"]]["rows"].extend(res["rows"])
    measure_wall = time.perf_counter() - t0

    for i, (sp, o) in enumerate(zip(spaces, outcomes)):
        rows = measured[i]["rows"]
        if not rows:
            continue
        best = min(rows, key=lambda r: (r["cycles"],
                                        ts.config_key(r["config"])))
        o.best_config, o.best_cycles = dict(best["config"]), best["cycles"]
        for r in rows:
            if r["config"] == sp.default:
                o.default_cycles = r["cycles"]
                break
    # the primary (first) shape declares the kernel@device winner
    o0 = outcomes[0]
    if o0.best_config is not None and o0.best_cycles is not None:
        cache.set_winner(kernel_id, device, o0.best_config,
                         cycles_per_step=o0.best_cycles,
                         shape=shape_sigs[0])

    return SweepResult(
        kernel_id=kernel_id, device=device, shapes=outcomes,
        n_candidates=n_candidates, n_captured=n_captured,
        n_pruned=n_pruned, n_priced=n_priced,
        n_finalists=sum(len(f) for f in finalists_per_shape),
        n_measured=n_measured, n_cache_hits=n_cache_hits,
        n_calibration_runs=calib_runs, calibration_scale=scale,
        workers=workers, top_k=top_k, price_wall_s=price_wall,
        sim_wall_s=sim_wall, measure_wall_s=measure_wall,
        wall_s=time.perf_counter() - t_start)
