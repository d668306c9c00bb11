"""Mesh-aware probing: per-device cycle records for sharded programs.

``probe()`` observes ONE device. Production workloads run SPMD over a
``Mesh`` — and a hierarchy profile is only trustworthy when *every*
parallel instance is observed (a straggler device is invisible in a
single-device record, and communication time is invisible in a
compute-only cost model). This module extends the RealProbe pipeline to
``shard_map``-style sharded programs:

- ``mesh_probe(fn, mesh, in_specs, out_specs)`` instruments the
  *per-shard* body once (one trace, zero retraces afterwards) and runs
  the instrumented evaluator under ``shard_map``, with the whole
  ``ProbeState`` carried as a **device-sharded buffer**: every state
  leaf grows a leading device axis sharded over all mesh axes, so row
  ``d`` holds the counters of the device at mesh coordinate
  ``unravel_index(d, mesh_shape)``. Counters never touch model values,
  so outputs stay bit-identical with probing on or off — the same
  non-intrusiveness guarantee as the single-device path, now per shard.
- cycle counts use the deterministic model clock with the **collective
  term** enabled (``costmodel.collective_axis_sizes``): a ``psum`` over
  a G-device axis costs its ring-model wire bytes, so per-device cycles
  respond to the mesh shape.
- ``CycleRecord`` decodes the sharded state into per-device arrays with
  cross-device reductions (``max`` / ``mean`` / ``per-device``) and the
  straggler signal ``skew = max - min``.
- ``MeshProbedFunction.collectives()`` joins the probe hierarchy
  against the ring wire-byte model (``launch.collectives``), so reports
  split compute vs. communication per module.
- ``ShardOracle`` replays one shard with plain Python integer counters
  (collectives stubbed shape-faithfully, ``axis_index`` resolved from
  the replayed device's mesh coordinate); device rows must equal it
  EXACTLY — the paper's 100%-accuracy check, per device.
- ``MeshProbeSession`` keeps the sharded counters running across a
  serving/training loop (constant memory, no retrace), feeding
  per-window per-device cycle deltas into a device-major
  ``StreamAggregator``.

Shard spills (DRAM offload) are disabled under a mesh — host callbacks
from inside ``shard_map`` are not portable — so per-call history is
limited to each probe's ring depth; the counters themselves stay exact.
Only ``cycle_source="model"`` is supported (wallclock needs the same
callbacks).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import costmodel as cm
from repro.core import report as report_mod
from repro.core.hierarchy import Hierarchy, extract
from repro.core.instrument import (Instrumenter, ProbeAssignment,
                                   decode_record, init_state)
from repro.core.oracle import Oracle, OracleCounters
from repro.core.pragma import ProbeConfig, _select_probes
from repro.core.streaming import StreamAggregator
from repro.launch.collectives import (PRIMITIVE_KINDS, CollectiveSite,
                                      jaxpr_collectives)


@contextlib.contextmanager
def extend_axis_env(sizes: Dict[str, int]):
    """Bind mesh axis names for tracing outside ``shard_map``, so
    ``jax.make_jaxpr`` can trace a per-shard function that uses
    collectives (``lax.psum(x, "dev")`` …) — the mesh-probe builder
    traces the shard body once this way."""
    from jax._src.core import extend_axis_env_nd
    with extend_axis_env_nd(list(sizes.items())):
        yield


def _is_spec_leaf(x) -> bool:
    return x is None or isinstance(x, P)


def _flat_specs(spec_tree, arg_tree, what: str) -> List[Optional[P]]:
    """Broadcast a (possibly prefix) spec pytree over ``arg_tree``,
    returning one spec per argument leaf — the shard_map convention."""
    treedef = jax.tree_util.tree_structure(spec_tree, is_leaf=_is_spec_leaf)
    leaves = jax.tree_util.tree_leaves(spec_tree, is_leaf=_is_spec_leaf)
    try:
        subtrees = treedef.flatten_up_to(arg_tree)
    except ValueError as e:
        raise ValueError(f"{what} is not a prefix of the argument "
                         f"structure: {e}") from None
    out: List[Optional[P]] = []
    for spec, sub in zip(leaves, subtrees):
        out.extend([spec] * len(jax.tree_util.tree_leaves(sub)))
    return out


def _spec_axes(spec: Optional[P], ndim: int) -> Tuple[Tuple[str, ...], ...]:
    """Per-dimension mesh axes of a PartitionSpec, padded to ``ndim``."""
    entries = tuple(spec) if spec is not None else ()
    out = []
    for i in range(ndim):
        e = entries[i] if i < len(entries) else None
        if e is None:
            out.append(())
        elif isinstance(e, str):
            out.append((e,))
        else:
            out.append(tuple(e))
    return tuple(out)


def _shard_shape(shape: Tuple[int, ...], spec: Optional[P],
                 sizes: Dict[str, int]) -> Tuple[int, ...]:
    out = []
    for dim, axes in zip(shape, _spec_axes(spec, len(shape))):
        k = 1
        for a in axes:
            k *= int(sizes.get(a, 1))
        if k > 1 and dim % k != 0:
            raise ValueError(f"dimension {dim} not divisible by mesh axes "
                             f"{axes} (size {k}) — spec {spec} on {shape}")
        out.append(dim // k)
    return tuple(out)


def _shard_slice(x, spec: Optional[P], sizes: Dict[str, int],
                 coords: Dict[str, int]):
    """The shard of global array ``x`` owned by the device at ``coords``."""
    x = np.asarray(x)
    idx: List[slice] = []
    for dim, axes in zip(x.shape, _spec_axes(spec, x.ndim)):
        k = 1
        block = 0
        for a in axes:
            k *= int(sizes.get(a, 1))
            block = block * int(sizes.get(a, 1)) + int(coords.get(a, 0))
        bs = dim // max(k, 1)
        idx.append(slice(block * bs, (block + 1) * bs))
    return x[tuple(idx)]


# ------------------------------------------------------- decoded record

@dataclass
class CycleRecord:
    """Per-device decoded counter state of one mesh-probed program.

    Row ``d`` of every array belongs to the device at mesh coordinate
    ``np.unravel_index(d, mesh_shape)`` (mesh axes in order) — the
    device-sharded counter buffer, brought to the host.
    """
    mesh_axes: Tuple[str, ...]
    mesh_shape: Tuple[int, ...]
    paths: Tuple[str, ...]
    cycle: np.ndarray             # (D,)      global span per device
    starts: np.ndarray            # (D, n)
    ends: np.ndarray              # (D, n)
    totals: np.ndarray            # (D, n)
    calls: np.ndarray             # (D, n)
    ring: np.ndarray              # (D, n, depth, 2)

    REDUCTIONS = ("per-device", "max", "mean")

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.mesh_shape))

    def coords(self, device: int) -> Tuple[int, ...]:
        return tuple(int(c) for c in
                     np.unravel_index(device, self.mesh_shape))

    def device(self, device: int) -> Dict[str, Any]:
        """Single-device view, shaped like ``decode_record``'s output."""
        return {"cycle": int(self.cycle[device]),
                "starts": self.starts[device], "ends": self.ends[device],
                "totals": self.totals[device], "calls": self.calls[device],
                "ring": self.ring[device]}

    def reduce(self, mode: str = "max") -> np.ndarray:
        """Cross-device reduction of per-probe total cycles."""
        if mode == "per-device":
            return self.totals
        if mode == "max":
            return self.totals.max(axis=0)
        if mode == "mean":
            return self.totals.mean(axis=0)
        raise ValueError(f"unknown reduction {mode!r}; "
                         f"expected one of {self.REDUCTIONS}")

    def skew(self) -> np.ndarray:
        """Per-probe max−min total cycles across devices — the
        straggler signal (0 everywhere = perfectly balanced)."""
        return self.totals.max(axis=0) - self.totals.min(axis=0)

    def straggler(self) -> Tuple[int, str]:
        """(device, probe path) of the worst cell by total cycles.
        ``(0, "")`` when no probes were selected."""
        if self.totals.size == 0:
            return 0, ""
        d, p = np.unravel_index(int(self.totals.argmax()),
                                self.totals.shape)
        return int(d), self.paths[int(p)]

    def row(self, path: str, device: Optional[int] = None):
        pid = self.paths.index(path)
        col = self.totals[:, pid]
        return col if device is None else int(col[device])


def decode_mesh_record(state: Dict[str, Any], mesh_axes: Sequence[str],
                       mesh_shape: Sequence[int],
                       paths: Sequence[str]) -> CycleRecord:
    """Decode a device-sharded ProbeState (leading device axis) into a
    host-side :class:`CycleRecord`. Goes through ``decode_record`` row
    by row — the single place that knows the counter layout."""
    state = jax.device_get(state)
    n_dev = int(np.prod(tuple(mesh_shape)))
    per_dev = [decode_record({k: np.asarray(v)[d] for k, v in state.items()})
               for d in range(n_dev)]
    return CycleRecord(
        mesh_axes=tuple(mesh_axes), mesh_shape=tuple(mesh_shape),
        paths=tuple(paths),
        cycle=np.array([r["cycle"] for r in per_dev], np.int64),
        starts=np.stack([r["starts"] for r in per_dev]),
        ends=np.stack([r["ends"] for r in per_dev]),
        totals=np.stack([r["totals"] for r in per_dev]),
        calls=np.stack([r["calls"] for r in per_dev]),
        ring=np.stack([r["ring"] for r in per_dev]))


# ------------------------------------------------------- shard oracle

class ShardOracle(Oracle):
    """Replay ONE device's shard with Python integer counters.

    Collectives cannot execute outside their mesh, so they are stubbed
    shape-faithfully: shape-preserving ones (psum/pmax/pmin/ppermute)
    pass their operands through, the rest return zeros of the output
    aval, and ``axis_index`` resolves to the replayed device's mesh
    coordinate. Cycle advances always use the hierarchy's precomputed
    per-eqn costs, so the replayed counters are exact as long as control
    flow does not branch on collective *values*.
    """

    _PASSTHROUGH = {"psum", "pmax", "pmin", "ppermute", "pbroadcast"}

    def __init__(self, hierarchy: Hierarchy, assignment: ProbeAssignment,
                 coords: Dict[str, int]):
        super().__init__(hierarchy, assignment)
        self.coords = dict(coords)

    def _bind(self, eqn, invals):
        name = eqn.primitive.name
        if name == "axis_index":
            axis = eqn.params.get("axis_name")
            return [np.int32(self.coords.get(str(axis), 0))]
        if name in self._PASSTHROUGH:
            return list(invals)
        if name in PRIMITIVE_KINDS:
            return [np.zeros(v.aval.shape, v.aval.dtype)
                    for v in eqn.outvars]
        return super()._bind(eqn, invals)


# ------------------------------------------------- mesh-probed function

class MeshProbedFunction:
    """Instrumented wrapper around a per-shard (shard_map-style) body.

    Mirrors ``ProbedFunction``'s surface — ``__call__`` returns
    ``(outputs, sharded_state)``, ``stateful_call`` threads the caller's
    state, ``report``/``oracle`` verify — but every counter exists once
    per device. Positional arguments only (the shard_map convention).
    """

    def __init__(self, fn: Callable, mesh, in_specs, out_specs,
                 config: ProbeConfig = ProbeConfig(), *,
                 check_specs: bool = False):
        if config.cycle_source != "model":
            raise ValueError("mesh_probe supports cycle_source='model' only "
                             "(wallclock needs host callbacks, which cannot "
                             "cross shard_map)")
        if config.offload:
            config = config.replace(offload=0.0)   # no host spill in-mesh
        # shard_map's replication check. Off by default: probe workloads
        # legitimately return device-varying values (skew demos, per-
        # device loop counts) under replicated out_specs. Turn it on to
        # have misdeclared out_specs diagnosed at trace time instead of
        # silently yielding one device's value.
        self.check_specs = bool(check_specs)
        self.fn = fn
        self.mesh = mesh
        self.config = config
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.mesh_axes: Tuple[str, ...] = tuple(mesh.axis_names)
        self.axis_sizes: Dict[str, int] = {a: int(s) for a, s in
                                           dict(mesh.shape).items()}
        self.mesh_shape: Tuple[int, ...] = tuple(self.axis_sizes[a]
                                                 for a in self.mesh_axes)
        self.n_devices = int(np.prod(self.mesh_shape))
        self._hierarchy: Optional[Hierarchy] = None
        self._trace_key = None
        self._assignment: Optional[ProbeAssignment] = None
        self._closed = None
        self._out_tree = None
        self._flat_in_specs: Optional[List[Optional[P]]] = None
        self._flat_out_specs: Optional[List[Optional[P]]] = None
        self._jitted = None
        self._jitted_stateful = None
        self.timings: Dict[str, float] = {}

    # -- stage 2: per-shard trace + extraction --------------------------
    def trace(self, *args) -> Hierarchy:
        flat, in_tree = jax.tree_util.tree_flatten(args)
        key = (in_tree, tuple((a.shape, str(a.dtype)) for a in flat
                              if hasattr(a, "shape")))
        if self._hierarchy is not None and key == self._trace_key:
            return self._hierarchy
        t0 = time.perf_counter()
        self._flat_in_specs = _flat_specs(self.in_specs, args, "in_specs")
        shard_avals = [jax.ShapeDtypeStruct(
            _shard_shape(tuple(np.shape(a)), s, self.axis_sizes),
            jnp.result_type(a))
            for a, s in zip(flat, self._flat_in_specs)]
        store: Dict[str, Any] = {}

        def flat_fn(*flat_args):
            out = self.fn(*jax.tree_util.tree_unflatten(in_tree, flat_args))
            flat_out, out_tree = jax.tree_util.tree_flatten(out)
            store["out_tree"] = out_tree
            return flat_out

        with extend_axis_env(self.axis_sizes), \
                cm.collective_axis_sizes(self.axis_sizes):
            self._closed = jax.make_jaxpr(flat_fn)(*shard_avals)
            t1 = time.perf_counter()
            self._hierarchy = extract(
                self._closed,
                kernel_probes=tuple(self.config.kernel_probes))
        self._out_tree = store["out_tree"]
        out_template = jax.tree_util.tree_unflatten(
            self._out_tree, [v.aval for v in self._closed.jaxpr.outvars])
        self._flat_out_specs = _flat_specs(self.out_specs, out_template,
                                           "out_specs")
        self._in_tree = in_tree
        self._trace_key = key
        self._jitted = None
        self.timings["trace_s"] = t1 - t0
        self.timings["extract_s"] = time.perf_counter() - t1
        return self._hierarchy

    @property
    def hierarchy(self) -> Hierarchy:
        if self._hierarchy is None:
            raise RuntimeError("call .trace(*args) or the function first")
        return self._hierarchy

    # -- stage 3: IP generation under shard_map -------------------------
    def _build(self, *args):
        h = self.trace(*args)
        t0 = time.perf_counter()
        paths = _select_probes(h, self.config)
        self._assignment = ProbeAssignment(
            paths=paths, depth=self.config.buffer_depth,
            spill=(False,) * len(paths))
        interp = Instrumenter(h, self._assignment, cycle_source="model",
                              sink=None, layout=self.config.layout)
        state_specs = jax.tree_util.tree_map(
            lambda _: P(self.mesh_axes),
            init_state(self._assignment.n, self.config.buffer_depth,
                       layout=self.config.layout))
        axis_sizes = self.axis_sizes
        closed, out_tree = self._closed, self._out_tree

        def shard_body(state, *flat_args):
            st = {k: v[0] for k, v in state.items()}    # drop device dim
            with cm.collective_axis_sizes(axis_sizes):
                outs, st = interp.run(closed, list(flat_args), st)
            return tuple(outs), {k: v[None] for k, v in st.items()}

        sm = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(state_specs,) + tuple(self._flat_in_specs),
            out_specs=(tuple(self._flat_out_specs), state_specs),
            check_vma=self.check_specs)

        def stateful(state, *flat_args):
            outs, state = sm(state, *flat_args)
            return jax.tree_util.tree_unflatten(out_tree, list(outs)), state

        def oneshot(*flat_args):
            return stateful(self._zero_state(), *flat_args)

        self._jitted_stateful = jax.jit(stateful)
        self._jitted = jax.jit(oneshot)
        self.timings["instrument_s"] = time.perf_counter() - t0

    def _zero_state(self):
        # placed with the session-steady sharding (leading device axis
        # over the whole mesh) so the first stateful call compiles the
        # same specialization every later step reuses — zero retraces
        from jax.sharding import NamedSharding
        sh = NamedSharding(self.mesh, P(self.mesh_axes))
        base = init_state(self._assignment.n, self.config.buffer_depth,
                          layout=self.config.layout)
        return {k: jax.device_put(
                    jnp.zeros((self.n_devices,) + v.shape, v.dtype), sh)
                for k, v in base.items()}

    # -- public ----------------------------------------------------------
    def ensure_built(self, *args) -> "MeshProbedFunction":
        if self._jitted is None:
            self._build(*args)
        return self

    def __call__(self, *args):
        self.ensure_built(*args)
        return self._jitted(*jax.tree_util.tree_leaves(args))

    def init_state(self):
        """Fresh zeroed device-sharded counter state (one row/device)."""
        if self._assignment is None:
            raise RuntimeError("not built yet")
        return self._zero_state()

    def stateful_call(self, state, *args):
        """One step with caller-owned sharded counter state (the
        ``MeshProbeSession`` substrate; no retrace per step)."""
        self.ensure_built(*args)
        return self._jitted_stateful(state, *jax.tree_util.tree_leaves(args))

    def unprobed(self) -> Callable:
        """The reference executable: same shard_map, no instrumentation
        (for bit-identity checks and overhead measurement)."""
        def flat_fn(*flat_args):
            out = self.fn(*jax.tree_util.tree_unflatten(self._in_tree,
                                                        flat_args))
            return tuple(jax.tree_util.tree_leaves(out))
        sm = jax.shard_map(
            flat_fn, mesh=self.mesh, in_specs=tuple(self._flat_in_specs),
            out_specs=tuple(self._flat_out_specs),
            check_vma=self.check_specs)

        def run(*args):
            outs = sm(*jax.tree_util.tree_leaves(args))
            return jax.tree_util.tree_unflatten(self._out_tree, list(outs))
        return jax.jit(run)

    @property
    def assignment(self) -> ProbeAssignment:
        if self._assignment is None:
            raise RuntimeError("not built yet")
        return self._assignment

    def probe_paths(self) -> Tuple[str, ...]:
        return self.assignment.paths

    # -- verification / reporting ---------------------------------------
    def decode(self, state) -> CycleRecord:
        return decode_mesh_record(state, self.mesh_axes, self.mesh_shape,
                                  self.assignment.paths)

    def oracle(self, *args, device: int = 0) -> OracleCounters:
        """Independent per-shard replay for one device (the ILA check):
        slices each global argument to that device's shard and replays
        the per-shard jaxpr with its mesh coordinate bound."""
        self.ensure_built(*args)
        coords = dict(zip(self.mesh_axes,
                          np.unravel_index(device, self.mesh_shape)))
        flat = jax.tree_util.tree_leaves(args)
        shard_args = [_shard_slice(a, s, self.axis_sizes, coords)
                      for a, s in zip(flat, self._flat_in_specs)]
        with cm.collective_axis_sizes(self.axis_sizes):
            return ShardOracle(self.hierarchy, self._assignment,
                               coords).run(self._closed, shard_args)

    def collectives(self) -> List[CollectiveSite]:
        """Collective sites of the per-shard program, joined to scope
        paths (the hierarchy ↔ wire-byte model join)."""
        h = self.hierarchy
        eqn_paths = {eid: info.path for eid, info in h.eqn_info.items()}
        with cm.collective_axis_sizes(self.axis_sizes):
            return jaxpr_collectives(self._closed.jaxpr, self.axis_sizes,
                                     eqn_paths)

    def report(self, state) -> "MeshReport":
        rec = state if isinstance(state, CycleRecord) else self.decode(state)
        return MeshReport(record=rec, hierarchy=self.hierarchy,
                          comm=self.collectives())


def mesh_probe(fn: Callable, mesh, in_specs, out_specs,
               config: ProbeConfig = ProbeConfig(), *,
               check_specs: bool = False) -> MeshProbedFunction:
    """Single-directive activation for sharded programs (the pragma,
    per device): ``fn`` is the per-shard body you would hand to
    ``shard_map(fn, mesh, in_specs, out_specs)``. ``check_specs=True``
    turns shard_map's replication check on (both the probed and the
    ``unprobed()`` executable), diagnosing misdeclared ``out_specs`` at
    trace time."""
    return MeshProbedFunction(fn, mesh, in_specs, out_specs, config,
                              check_specs=check_specs)


# ------------------------------------------------------------- report

@dataclass
class MeshReport:
    """Per-device result view: device table, mesh heat map, reductions,
    and the compute-vs-communication split per module."""
    record: CycleRecord
    hierarchy: Hierarchy
    comm: List[CollectiveSite] = field(default_factory=list)

    def device_table(self) -> str:
        return report_mod.mesh_device_table(self.record)

    def heat(self, path: Optional[str] = None) -> str:
        return report_mod.mesh_heat(self.record, path)

    def comm_table(self) -> str:
        return report_mod.mesh_comm_table(self.record, self.hierarchy,
                                          self.comm)

    def reduce(self, mode: str = "max") -> np.ndarray:
        return self.record.reduce(mode)

    def skew(self) -> np.ndarray:
        return self.record.skew()


# ------------------------------------------------------------- session

@dataclass
class MeshSnapshot:
    """Point-in-time view of a live mesh session (constant-size)."""
    steps: int
    wall_s: float
    record: CycleRecord
    stats: StreamAggregator       # device-major rows: (device, probe)
    state_nbytes: int

    @property
    def span(self) -> int:
        """Worst-device cumulative cycle span since session start."""
        return int(self.record.cycle.max(initial=0))

    def table(self, reduce: str = "max") -> str:
        return report_mod.mesh_session_table(self, reduce=reduce)

    def device_table(self) -> str:
        return report_mod.mesh_device_table(self.record)

    def heat(self, path: Optional[str] = None) -> str:
        return report_mod.mesh_heat(self.record, path)

    def skew(self) -> np.ndarray:
        return self.record.skew()


class MeshProbeSession:
    """Continuous mesh-wide profiling over a sharded step function.

    The per-device counter state is threaded across steps on-device
    (``stateful_call`` — no retrace, totals accumulate per device); at
    window boundaries one host read folds the per-window per-device
    cycle deltas into a device-major :class:`StreamAggregator`, whose
    ``reduce``/``skew`` expose the cross-device modes. Memory is
    constant in step count.
    """

    def __init__(self, fn, mesh=None, in_specs=None, out_specs=None,
                 config: Optional[ProbeConfig] = None, *,
                 window_steps: int = 16, ema_alpha: float = 0.1,
                 bus=None, source: str = "mesh"):
        if isinstance(fn, MeshProbedFunction):
            self.mpf = fn
        else:
            if mesh is None:
                raise ValueError("MeshProbeSession(fn, mesh, in_specs, "
                                 "out_specs) needs a mesh for a plain fn")
            self.mpf = mesh_probe(fn, mesh, in_specs, out_specs,
                                  config or ProbeConfig())
        self.window_steps = int(window_steps)
        self.ema_alpha = float(ema_alpha)
        self.bus = bus
        self.source = source
        self._stream = None
        self.stats: Optional[StreamAggregator] = None
        self._state = None
        self._steps = 0
        self._closed = False
        self._t0 = 0.0
        self._prev_totals: Optional[np.ndarray] = None
        self._win_start = 0

    def __enter__(self) -> "MeshProbeSession":
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    @property
    def steps(self) -> int:
        return self._steps

    @property
    def paths(self) -> Tuple[str, ...]:
        return self.mpf.assignment.paths

    @property
    def n_devices(self) -> int:
        return self.mpf.n_devices

    def step(self, *args):
        if self._closed:
            raise RuntimeError("session is closed")
        if self._state is None:
            self.mpf.ensure_built(*args)
            self._state = self.mpf.init_state()
            n = self.mpf.assignment.n
            # per-window per-device deltas publish through the bus
            # abstraction (device-major stream); `stats` stays the
            # stream's aggregator, as before the telemetry refactor
            from repro.telemetry.bus import ProbeStream
            paths = self.mpf.assignment.paths
            if self.bus is not None:
                self._stream = self.bus.stream(
                    self.source, paths, n_devices=self.mpf.n_devices,
                    ema_alpha=self.ema_alpha)
            else:
                self._stream = ProbeStream(
                    self.source, paths, n_devices=self.mpf.n_devices,
                    ema_alpha=self.ema_alpha)
            self.stats = self._stream.agg
            self._prev_totals = np.zeros(self.mpf.n_devices * n, np.int64)
            self._t0 = time.perf_counter()
        out, self._state = self.mpf.stateful_call(self._state, *args)
        self._steps += 1
        if self._steps - self._win_start >= self.window_steps:
            self._roll_window()
        return out

    def _read_totals(self) -> np.ndarray:
        from repro.core.counters import c64_to_int
        from repro.core.instrument import TOTALS
        st = jax.device_get(self._state)
        if "cnt" in st:                            # packed: (D, 3, n, 2)
            t = c64_to_int(np.asarray(st["cnt"])[:, TOTALS])
        else:
            t = c64_to_int(np.asarray(st["totals"]))
        return np.atleast_2d(t).reshape(-1)       # device-major (D*n,)

    def _roll_window(self):
        totals = self._read_totals()
        delta = totals - self._prev_totals
        for row in np.nonzero(delta)[0]:
            self._stream.add(int(row), np.array([delta[row]]))
        self._stream.roll(self._win_start, self._steps,
                          exact_totals=delta)
        self._prev_totals = totals
        self._win_start = self._steps

    def snapshot(self) -> MeshSnapshot:
        if self._state is None:
            raise RuntimeError("no steps executed yet")
        if self._steps > self._win_start:
            self._roll_window()                    # fold the partial window
        rec = self.mpf.decode(self._state)
        return MeshSnapshot(steps=self._steps,
                            wall_s=time.perf_counter() - self._t0,
                            record=rec, stats=self.stats.copy(),
                            state_nbytes=self.state_nbytes())

    def state_nbytes(self) -> int:
        host = self.stats.nbytes if self.stats is not None else 0
        if self._prev_totals is not None:
            host += self._prev_totals.nbytes
        from repro.core.buffer import state_bytes
        dev = (self.mpf.n_devices *
               state_bytes(self.mpf.assignment.n,
                           self.mpf.config.buffer_depth,
                           layout=self.mpf.config.layout)
               if self._state is not None else 0)
        return host + dev

    def close(self) -> Optional[MeshSnapshot]:
        if self._closed:
            return None
        snap = self.snapshot() if self._state is not None else None
        self._closed = True
        return snap
