#!/usr/bin/env python3
"""Run one cell of the serving benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its pieces
are found by name: ``bench/configs/<config>.json`` (the model),
``bench/traffic/<traffic>.json`` (the mix), ``bench/cells/<cell>.json``
(the engine's settings, the correctness limit, the traced window) and
``bench/metrics/<metric>.py`` (one reader per per-layer metric).

Set-up makes the weights on the device from the seed, builds the engine
and warms every shape the seed's traffic uses. Then the engine serves
the traffic for ``--seconds``. With ``--trace 0`` the last line of
standard output reports the cell's end-to-end metrics; with
``--trace 1`` a few seconds in the middle of the window are traced and
the line reports the per-layer metrics. Either way the window's served
tokens are then held to the plain reference (``correct.py``);
``--control 1`` holds the fp8 control's tokens to it instead, to show
that the comparison fails a lower precision. The run
exits nonzero, with no result, where JAX finds no accelerator or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

import correct  # noqa: E402
import costs  # noqa: E402
import devtrace  # noqa: E402
import engine_adapter as adapter  # noqa: E402
import weights as W  # noqa: E402
from readings import Readings, reader  # noqa: E402
from traffic import Traffic  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"        # traces, rewritten by each traced run


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, root: Path = ROOT) -> Dict:
    """The workload entry and every file it names, plus BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    bdir = root / "bench"
    return {"bench": bench, "workload": w,
            "config": json.loads((bdir / "configs" / f"{w['config']}.json")
                                 .read_text()),
            "traffic": json.loads((bdir / "traffic" / f"{w['traffic']}.json")
                                  .read_text()),
            "cell": json.loads((bdir / "cells" / f"{name}.json").read_text())}


def metrics_of(bench: Dict, cell: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache, written for every program that can be."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Programs compiled or loaded, sorted by what the persistent cache
    did with each (JAX's monitoring events; a program's cache events
    come before its compile-duration event)."""

    def __init__(self):
        self.programs: List = []
        self._seen = set()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event: str, **_):
        self._seen.add(event.rsplit("/", 1)[-1])

    def _duration(self, event: str, secs: float, **_):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        seen, self._seen = self._seen, set()
        kind = ("hit" if "cache_hits" in seen
                else "written" if "cache_misses" in seen
                else "not_written" if "compile_requests_use_cache" in seen
                else "not_looked_up")
        self.programs.append((kind, secs))

    def summary(self) -> Dict[str, List]:
        out: Dict[str, List] = {}
        for kind, secs in self.programs:
            n, s = out.get(kind, (0, 0.0))
            out[kind] = (n + 1, s + secs)
        return {k: [n, round(s, 3)] for k, (n, s) in out.items()}


def quantile(xs: List[float], q: float) -> Optional[float]:
    return float(np.percentile(xs, 100 * q)) if xs else None


@dataclass
class Window:
    """The engine serving one cell's traffic for ``seconds``.

    An open loop submits each request when it is due, from inside the
    engine's bus hook while the engine runs and by sleeping while it is
    idle; a closed loop submits a client's next request as its last one
    finishes. With a trace plan the profiler runs from ``trace_at`` to
    ``trace_at + trace_s`` seconds into the window, and host spans name
    what the host did between the engine's events."""
    engine: object
    traffic: object
    seconds: float
    trace_at: Optional[float] = None
    trace_s: float = 0.0
    live: bool = False
    reqs: List = field(default_factory=list)
    inflight: Dict = field(default_factory=dict)
    late: List[float] = field(default_factory=list)
    traced: Dict = field(default_factory=lambda: {"decode": [],
                                                  "prefill": []})
    trace_bounds: List[float] = field(default_factory=list)

    def __post_init__(self):
        self._span = None
        self._tracing = False
        self._to_prefill: List = []

    # -- host spans and the profiler --------------------------------------
    def _mark(self, name: str):
        if not self._tracing:
            return
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._span = jax.profiler.TraceAnnotation(name)
        self._span.__enter__()

    def _poll_trace(self, now: float):
        if self.trace_at is None:
            return
        rel = now - self.t0
        if not self._tracing and not self.trace_bounds and \
                rel >= self.trace_at:
            shutil.rmtree(OUT / "trace", ignore_errors=True)
            jax.profiler.start_trace(str(OUT / "trace"))
            self._window_span = jax.profiler.TraceAnnotation(
                "host.trace_window")
            self._window_span.__enter__()
            self._tracing = True
            self.trace_bounds.append(time.perf_counter())
        elif self._tracing and rel >= self.trace_at + self.trace_s:
            self.stop_trace()

    def stop_trace(self):
        if not self._tracing:
            return
        self.trace_bounds.append(time.perf_counter())
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        self._window_span.__exit__(None, None, None)
        self._tracing = False
        jax.profiler.stop_trace()

    # -- the engine's bus hook --------------------------------------------
    def hook(self, kind: str, what):
        if not self.live:
            return
        now = time.perf_counter()
        if kind == "phase":
            if self._tracing:
                self._record_step(what)
            if what == "prefill" and self._to_prefill:
                self._to_prefill.pop(0)
            self._mark(f"host.after_{what}")
        else:
            self.inflight.pop(what, None)
            if self.traffic.loop == "closed" and now - self.t0 < self.seconds:
                self._submit(next(self._source), now)
        self._due(now)
        self._poll_trace(now)

    def _record_step(self, phase: str):
        if phase == "decode":
            self.traced["decode"].append(
                [len(r.prompt) + len(r.out_tokens)
                 for r in self.inflight.values()
                 if 1 <= len(r.out_tokens) < r.max_new])
        elif phase == "prefill" and self._to_prefill:
            self.traced["prefill"].append(len(self._to_prefill[0].prompt))

    def _submit(self, req, now: float, due: Optional[float] = None):
        r = adapter.submit(self.engine, req.prompt, req.max_new)
        r.submitted = now
        r.due = now if due is None else due
        self.reqs.append(r)
        self.inflight[r.rid] = r
        self._to_prefill.append(r)
        if due is not None:
            self.late.append(now - due)

    def engine_busy(self) -> bool:
        return bool(adapter.queued(self.engine))

    def _due(self, now: float):
        if self.traffic.loop != "open":
            return
        while self._next < len(self._sched) and \
                self.t0 + self._sched[self._next].arrival_s <= now:
            req = self._sched[self._next]
            self._submit(req, now, self.t0 + req.arrival_s)
            self._next += 1

    # -- the window ---------------------------------------------------------
    def run(self):
        self.live = True
        self.t0 = time.perf_counter()
        if self.traffic.loop == "open":
            self._sched = self.traffic.schedule()
            self._next = 0
            while self._next < len(self._sched) or self.inflight:
                now = time.perf_counter()
                self._due(now)
                self._poll_trace(now)
                if self.inflight:
                    self.engine.run()
                elif self._next < len(self._sched):
                    self._mark("host.wait_arrival")
                    wake = self.t0 + self._sched[self._next].arrival_s
                    if self.trace_at is not None and \
                            len(self.trace_bounds) < 2:
                        edge = self.trace_at + (self.trace_s
                                                if self.trace_bounds else 0)
                        wake = min(wake, self.t0 + edge)
                    time.sleep(max(0.0, wake - time.perf_counter()))
                if self.inflight and not self.engine_busy():
                    raise RuntimeError(
                        f"engine idle with {len(self.inflight)} requests "
                        "unfinished")
        else:
            self._source = self.traffic.closed_requests()
            now = time.perf_counter()
            for _ in range(self.traffic.spec["clients"]):
                self._submit(next(self._source), now)
            self.engine.run()
            if self.inflight:
                raise RuntimeError(f"engine idle with {len(self.inflight)} "
                                   "requests unfinished")
        self.stop_trace()
        self.live = False
        self.t1 = time.perf_counter()


def e2e_metrics(win: Window, setup_s: float) -> Dict[str, float]:
    t0, t1 = win.t0, win.t0 + win.seconds
    toks = [t for r in win.reqs for t in r.out_tokens.times if t0 <= t <= t1]
    ttft = [r.out_tokens.times[0] - r.due for r in win.reqs
            if r.out_tokens.times and t0 <= r.out_tokens.times[0] <= t1]
    itl = [b - a for r in win.reqs
           for a, b in zip(r.out_tokens.times, r.out_tokens.times[1:])
           if t0 <= b <= t1]
    return {"tokens_per_s": len(toks) / win.seconds,
            "ttft_p95_ms": 1e3 * quantile(ttft, 0.95) if ttft else None,
            "itl_p95_ms": 1e3 * quantile(itl, 0.95) if itl else None,
            "setup_s": setup_s,
            "_counts": {"requests_first_token_in_window": len(ttft),
                        "gaps_in_window": len(itl),
                        "tokens_in_window": len(toks)}}


def warm(engine, prompt_lens: List[int], buckets: List[int],
         page_size: int, vocab: int, seed: int):
    """Every prompt page count, then every decode bucket, through
    submit/run: all of one kind submitted before a single ``run``, so
    the engine batches their decode steps. Then release the prefix
    cache."""
    rng = np.random.default_rng([seed, 0x3A7])
    pages = sorted({-(-p // page_size) for p in prompt_lens})
    for pp in pages:
        adapter.submit(engine, rng.integers(0, vocab, pp * page_size
                                            - page_size // 2).tolist(), 2)
    engine.run()
    short = min(prompt_lens)
    for b in buckets:
        for _ in range(b):
            adapter.submit(engine, rng.integers(0, vocab, short).tolist(), 2)
        engine.run()
    engine.drain()
    return pages


def device_info(chips: int) -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "chips": chips}


def require_accelerator(chips: int) -> Dict:
    info = device_info(chips)
    if info["platform"] == "cpu" or info["count"] < chips:
        log(f"error: the cell needs {chips} accelerator chip(s); JAX finds "
            f"{info['count']} {info['platform']} device(s)")
        raise SystemExit(3)
    return info


@dataclass
class Setup:
    """One cell built and warmed: the engine, its weights and traffic."""
    name: str
    seed: int
    spec: Dict
    info: Dict
    peak: Dict
    dims: Dict
    traffic: object
    engine: object
    params: object
    compiles: CompileLog
    setup_s: float
    hook: Dict = field(default_factory=dict)


def set_up(name: str, seed: int, seconds: float, root: Path = ROOT,
           need_chip: bool = True, peak: Optional[Dict] = None,
           t_start: float = T_START,
           prompt_lens: Optional[List[int]] = None) -> Setup:
    """Weights from the seed, the engine, and a warm-up of every shape
    the seed's traffic uses (``prompt_lens`` widens it)."""
    spec = load_cell(name, root)
    w = spec["workload"]
    info = require_accelerator(w["chips"]) if need_chip \
        else device_info(w["chips"])
    peak = peak or costs.peaks(info["kind"])
    cache_dir = enable_compile_cache(root)
    compiles = CompileLog()
    conf, eng_spec = spec["config"], spec["cell"]["engine"]
    dims = W.dense_dims(conf["config"])
    model = adapter.make_model(conf)
    traffic = Traffic(spec["traffic"], seed, seconds, dims["V"])

    t = time.perf_counter()
    params = adapter.program_params(model, dims, seed)
    weights_s = time.perf_counter() - t
    hook: Dict = {}
    engine = adapter.build_engine(
        model, params, eng_spec,
        lambda kind, what: hook["fn"](kind, what) if "fn" in hook else None)
    t = time.perf_counter()
    pages = warm(engine, prompt_lens or traffic.prompt_lengths(),
                 eng_spec["buckets"], eng_spec["page_size"], dims["V"], seed)
    warm_s = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {setup_s:.3f} s: weights {weights_s:.3f} s, warm calls "
        f"{warm_s:.3f} s over {len(pages)} prompt page counts and "
        f"{len(eng_spec['buckets'])} decode buckets; programs by cache "
        f"outcome [count, compile s] {json.dumps(compiles.summary())}; "
        f"cache {cache_dir}")
    return Setup(name, seed, spec, info, peak, dims, traffic, engine,
                 params, compiles, setup_s, hook)


def serve(su: Setup, seconds: float, trace: bool = False,
          traffic=None) -> Window:
    """Serve ``traffic`` (the cell's by default) for one window."""
    tr = su.spec["cell"]["trace"]
    win = Window(su.engine, traffic or su.traffic, seconds,
                 trace_at=tr["at_s"] if trace else None,
                 trace_s=tr["seconds"])
    su.hook["fn"] = win.hook
    n0 = len(su.compiles.programs)
    win.stats0 = su.engine.stats()
    win.run()
    win.stats1 = su.engine.stats()
    win.compiled = len(su.compiles.programs) - n0
    late = sorted(win.late)
    if late:
        log(f"[generator] {len(late)} requests submitted late by p50 "
            f"{1e3 * quantile(late, 0.5):.3f} ms, p95 "
            f"{1e3 * quantile(late, 0.95):.3f} ms, max {1e3 * late[-1]:.3f}"
            f" ms")
    win.finished = [r for r in win.reqs if len(r.out_tokens) == r.max_new]
    s0, s1 = win.stats0, win.stats1
    log(f"[window] {seconds} s: {len(win.reqs)} requests, "
        f"{len(win.reqs) - len(win.finished)} failed, "
        f"{s1['tokens_out'] - s0['tokens_out']} tokens; engine retraces "
        f"{s1['retraces']}, buckets {s1['buckets']}, programs compiled or "
        f"loaded in the window {win.compiled}; drained "
        f"{win.t1 - win.t0 - seconds:.3f} s after the window")
    return win


def per_layer(su: Setup, win: Window, root: Path):
    """(per-layer metrics, busy_s, window_s, breakdown) of a traced run."""
    tpath = devtrace.latest_xplane(str(OUT / "trace"))
    tr = devtrace.load(tpath)
    s0, s1 = win.stats0, win.stats1
    prefills = s1["phases"]["prefill"]["steps"] - \
        s0["phases"]["prefill"]["steps"]
    ctx = Readings(
        dims=su.dims, peak=su.peak, programs=su.spec["cell"]["programs"],
        counters={"decode_steps": adapter.decode_steps(s1)
                  - adapter.decode_steps(s0),
                  "decode_tokens": s1["tokens_out"] - s0["tokens_out"]
                  - prefills},
        trace=tr, traced=win.traced)
    metrics = {}
    for m in metrics_of(su.spec["bench"], su.name, "per_layer"):
        v = reader(m["name"], root / "bench")(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    names = sorted({n.split("(")[0] for n, _, _ in
                    (tr.modules[0] if tr.modules else [])})
    log(f"[trace] {tpath}: window {tr.window_s:.3f} s, busy "
        f"{tr.busy_s():.3f} s, {len(win.traced['decode'])} decode and "
        f"{len(win.traced['prefill'])} prefill dispatches; programs {names}")
    return metrics, tr.busy_s(), tr.window_s, {
        "device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}


def check(su: Setup, win: Window, root: Path, control: bool = False):
    """Free the program's state, then hold a sample of the served tokens
    to the reference. Returns the gaps (``correct.served_gaps``)."""
    adapter.release(su.engine, su.params)
    su.engine = su.params = None
    gc.collect()
    t = time.perf_counter()
    reqs = correct.sample(win.finished, su.spec["cell"]["check"]["requests"],
                          su.seed)
    Ref = correct.load_reference(su.spec["config"]["reference"],
                                 root / "bench")
    got = correct.served_gaps(
        Ref, su.dims, su.seed, reqs,
        correct.ref_length(su.traffic.max_sequence()), control=control)
    got["requests"] = len(reqs)
    log(f"[check] reference over {len(reqs)} requests, {got['tokens']} "
        f"served tokens, {time.perf_counter() - t:.3f} s")
    return got


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, need_chip: bool = True,
             peak: Optional[Dict] = None, t_start: float = T_START,
             control: bool = False) -> Dict:
    """One run of one cell; returns the result line as a dict. With
    ``control`` the tokens an fp8 forward puts first stand in for the
    served ones in the comparison (``correct.py``), which must then
    fail."""
    su = set_up(name, seed, seconds, root, need_chip, peak, t_start)
    win = serve(su, seconds, trace)
    chips = su.spec["workload"]["chips"]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.devices()[:chips])
    device = {"platform": su.info["platform"], "kind": su.info["kind"],
              "count": su.info["count"], "memory_peak_bytes": int(mem)}
    breakdown = None
    if trace:
        metrics, device["busy_s"], device["window_s"], breakdown = \
            per_layer(su, win, root)
    else:
        e2e = e2e_metrics(win, su.setup_s)
        log(f"[window] counts {e2e.pop('_counts')}")
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(su.spec["bench"], name, "end_to_end")
                   if e2e.get(m["name"]) is not None}
    failed = len(win.reqs) - len(win.finished)
    got = check(su, win, root, control)
    if control:
        log(f"[check] control: the served tokens' own gap "
            f"{got['max_gap']}")
        got["max_gap"] = got["control_max_gap"]
    limit = su.spec["cell"]["check"]["max_gap"]
    checks = {"max_gap": {"value": got["max_gap"], "limit": limit},
              "failed": {"value": failed, "limit": 0}}
    result = {"correct": failed == 0 and got["requests"] > 0
              and got["max_gap"] <= limit,
              "attempted": len(win.reqs), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks          # last, as the driver reads it
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: compare the fp8 control's tokens instead of "
                    "the served ones (a run that must not be correct)")
    a = ap.parse_args(argv)
    res = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                   control=bool(a.control))
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
