"""``repro.tune`` — probe-guided kernel autotuning from the command line.

    PYTHONPATH=src python -m repro.tune --kernel flash_attention
    PYTHONPATH=src python -m repro.tune --kernel all --seq 512 \
        --cache-dir .repro_cache/dse --json tune.json

Runs the DSE engine (enumerate -> cost-model prune -> successive-halving
ProbeSession measurement -> incremental eval cache) for each requested
kernel at the given shapes, prints the leaderboard, and leaves the
winners in the on-disk cache where ``serve.py --autotune`` /
``train.py --autotune`` (and ``repro.kernels.tuning.load_cache``) pick
them up.

``--sweep`` switches to the trace-once sweep farm (``core.dse.
run_sweep``): dense config x shape candidate pools captured once as
``KernelTrace`` artifacts, simulator-priced in microseconds, with
device measurement reserved for the per-shape finalists:

    PYTHONPATH=src python -m repro.tune --kernel flash_attention \
        --sweep --sweep-seqs 128,256,512 --workers 4 --top-k 16
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Optional

from repro.core import DeviceBudget, DSEEngine, EvalCache
from repro.core.dse import run_sweep
from repro.kernels import search_spaces

KERNELS = tuple(search_spaces.SPACES)


def _int_tuple(spec: str) -> tuple:
    return tuple(int(v) for v in spec.split(",") if v.strip())


def sweep_kernel(kernel: str, args: argparse.Namespace,
                 cache: EvalCache) -> Dict[str, Any]:
    shapes = None
    if args.sweep_seqs or args.sweep_heads:
        shapes = search_spaces.sweep_shapes(
            kernel, seqs=_int_tuple(args.sweep_seqs or ""),
            heads=_int_tuple(args.sweep_heads or ""))
    budget: Optional[DeviceBudget] = DeviceBudget(
        vmem_bytes=args.budget_vmem, hbm_bytes=args.budget_hbm,
        flops=args.budget_flops)
    result = run_sweep(
        kernel, shapes, workers=args.workers, top_k=args.top_k,
        steps=args.max_steps, budget=budget, cache=cache,
        calibrate=not args.no_calibrate, walk=args.walk,
        cycle_source=args.cycle_source, reuse_traces=not args.no_reuse)
    print(result.summary())
    return result.to_dict()


def build_space(kernel: str, args: argparse.Namespace):
    if kernel == "flash_attention":
        return search_spaces.flash_attention_space(
            B=args.batch, H=args.heads, S=args.seq, D=args.dim,
            seed=args.seed)
    if kernel == "ssd_scan":
        return search_spaces.ssd_scan_space(
            B=args.batch, H=args.heads, L=args.seq, seed=args.seed)
    if kernel == "paged_attention":
        return search_spaces.paged_attention_space(
            B=args.batch, n_pages=max(1, args.seq // 16), seed=args.seed)
    if kernel == "chunked_prefill":
        return search_spaces.chunked_prefill_space(
            prompt_pages=max(1, args.seq // 64), seed=args.seed)
    raise SystemExit(f"unknown kernel {kernel!r}; choose from "
                     f"{KERNELS + ('all',)}")


def tune_kernel(kernel: str, args: argparse.Namespace,
                cache: EvalCache) -> Dict[str, Any]:
    space = build_space(kernel, args)
    budget: Optional[DeviceBudget] = DeviceBudget(
        vmem_bytes=args.budget_vmem, hbm_bytes=args.budget_hbm,
        flops=args.budget_flops)
    engine = DSEEngine(space, budget=budget, cache=cache,
                       cycle_source=args.cycle_source, r0=args.r0,
                       eta=args.eta, max_steps=args.max_steps)
    result = engine.tune()
    print(result.leaderboard(top=args.top))
    best = result.best
    if best is not None and best.measured:
        print(f"-> best {kernel} config: {best.config} "
              f"({best.cycles_per_step:.0f} cyc/step, "
              f"{result.speedup:.2f}x vs default); cached for --autotune")
    return result.to_dict()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.tune",
        description="probe-guided Pallas kernel autotuning (DSE engine)")
    ap.add_argument("--kernel", default="flash_attention",
                    help=f"one of {KERNELS} or 'all'")
    ap.add_argument("--seq", type=int, default=256,
                    help="sequence length to tune at (S / L)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--dim", type=int, default=64,
                    help="head dim (flash_attention)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-dir", default=None,
                    help="eval cache dir (default .repro_cache/dse or "
                         "$REPRO_DSE_CACHE)")
    ap.add_argument("--clear-cache", action="store_true",
                    help="drop cached measurements for the kernel(s) first")
    ap.add_argument("--cycle-source", default="model",
                    choices=("model", "wallclock"))
    ap.add_argument("--r0", type=int, default=1,
                    help="successive-halving starting steps per candidate")
    ap.add_argument("--eta", type=int, default=2,
                    help="halving keep-fraction / step-growth factor")
    ap.add_argument("--max-steps", type=int, default=4,
                    help="steps the finalists run")
    ap.add_argument("--budget-vmem", type=int,
                    default=DeviceBudget().vmem_bytes,
                    help="VMEM budget per candidate, bytes")
    ap.add_argument("--budget-hbm", type=int, default=None,
                    help="HBM traffic budget per call, bytes")
    ap.add_argument("--budget-flops", type=int, default=None)
    ap.add_argument("--top", type=int, default=10,
                    help="leaderboard rows to print")
    ap.add_argument("--json", default=None,
                    help="write the full tune result(s) to this path")
    ap.add_argument("--sweep", action="store_true",
                    help="run the trace-once sweep farm instead of "
                         "successive halving")
    ap.add_argument("--workers", type=int, default=2,
                    help="sweep worker processes (<=1 runs inline)")
    ap.add_argument("--top-k", type=int, default=16,
                    help="sweep: total device-measured finalists across "
                         "shapes (>=2 per shape)")
    ap.add_argument("--sweep-seqs", default=None,
                    help="sweep: comma-separated sequence lengths "
                         "(S / L / n_pages)")
    ap.add_argument("--sweep-heads", default=None,
                    help="sweep: comma-separated head counts")
    ap.add_argument("--no-calibrate", action="store_true",
                    help="sweep: skip the grid-step calibration run")
    ap.add_argument("--walk", action="store_true",
                    help="sweep: also capture walked (sim-mode) grid "
                         "totals per candidate (slower capture)")
    ap.add_argument("--no-reuse", action="store_true",
                    help="sweep: ignore stored trace artifacts")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    kernels = list(KERNELS) if args.kernel == "all" else [args.kernel]
    cache = EvalCache(args.cache_dir)
    results = {}
    for kernel in kernels:
        if args.clear_cache:
            n = cache.clear(kernel)
            print(f"# cleared {n} cached entries for {kernel}")
        results[kernel] = (sweep_kernel(kernel, args, cache) if args.sweep
                          else tune_kernel(kernel, args, cache))
        print()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print(f"# wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
