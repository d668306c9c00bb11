#!/usr/bin/env python
"""Golden-record conformance suite: canonical decoded probe records.

The profiler now carries three exactness contracts (oracle equality,
streaming aggregation, mesh records) plus the intra-kernel grid-step
layer. This tool pins the *decoded record itself* — every counter,
ring slot and probe path of a fixed-seed probe run — as key-sorted
JSON under ``tests/golden/``; ``tests/test_golden.py`` asserts exact
equality on every run, so any change to probe selection, cost-model
pricing, event ordering or record layout shows up as a reviewable
JSON diff instead of a silent drift.

Records are produced by the deterministic model clock, so they are
machine-independent — but they DO depend on the traced jaxpr and
therefore on the jax version. Each file records the version it was
generated with (the CI baseline pin); the test skips on other
versions (the nightly pinned matrix keeps it exercised).

Usage:
    PYTHONPATH=src python tools/regen_golden.py            # rewrite all
    PYTHONPATH=src python tools/regen_golden.py --diff     # preview only
    PYTHONPATH=src python tools/regen_golden.py --case flash_grid
"""
from __future__ import annotations

import argparse
import difflib
import json
import os
import sys
from typing import Any, Callable, Dict, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_DIR = os.path.join(REPO, "tests", "golden")


# ------------------------------------------------------------- cases

def _case_flash_grid():
    """Causal flash attention, kernel grid-step probes, full offload."""
    import jax
    import jax.numpy as jnp
    from repro.core import ProbeConfig
    from repro.kernels import flash_attention as fa

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (1, 2, 128, 32), jnp.float32)
    k = jax.random.normal(k2, (1, 2, 128, 32), jnp.float32)
    v = jax.random.normal(k3, (1, 2, 128, 32), jnp.float32)

    def fn(q, k, v):
        with jax.named_scope("attn"):
            return fa.flash_attention(q, k, v, causal=True, block_q=64,
                                      block_k=64, pipeline=2,
                                      interpret=True)

    return fn, (q, k, v), ProbeConfig(inline="off_all",
                                      kernel_probes=("*",),
                                      offload=1.0, buffer_depth=4)


def _case_ssd_grid():
    """SSD chunk scan, kernel grid-step probes."""
    import jax
    import jax.numpy as jnp
    from repro.core import ProbeConfig
    from repro.kernels import ssd_scan as ssdk

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (1, 2, 128, 16), jnp.float32) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (1, 2, 128))) * 0.3
    b = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.float32) * 0.5
    c = jax.random.normal(ks[3], (1, 2, 128, 32), jnp.float32) * 0.5

    def fn(x, a, b, c):
        with jax.named_scope("ssd"):
            return ssdk.ssd_scan(x, a, b, c, chunk=32, pipeline=2,
                                 interpret=True)

    return fn, (x, a, b, c), ProbeConfig(inline="off_all",
                                         kernel_probes=("*",),
                                         offload=1.0, buffer_depth=4)


def _case_transformer_step():
    """Tiny transformer forward step (scope/loop probes, no kernels)."""
    import jax
    from repro.configs.registry import smoke_config
    from repro.core import ProbeConfig
    from repro.models import Model

    cfg = smoke_config("tinyllama-1.1b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    k = jax.random.PRNGKey(2)
    batch = {"tokens": jax.random.randint(k, (2, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(k, 1),
                                          (2, 32), 0, cfg.vocab_size)}

    def fn(params, batch):
        return model.loss_fn(params, batch)

    return fn, (params, batch), ProbeConfig(max_probes=24)


CASES: Dict[str, Callable[[], Tuple[Callable, tuple, Any]]] = {
    "flash_grid": _case_flash_grid,
    "ssd_grid": _case_ssd_grid,
    "transformer_step": _case_transformer_step,
}

# the serving-engine case has its own document shape (per-request phase
# bills instead of a single probe record), so it dispatches separately
ENGINE_CASE = "engine_serve"


def run_engine_case() -> Dict[str, Any]:
    """Mixed request trace through the continuous-batching engine with
    probing on: pins every decoded token, per-request per-phase cycle
    bill, page sharing, bucket histogram, and the zero-retrace count."""
    import jax
    import numpy as np
    from repro.configs.registry import smoke_config
    from repro.engine import EngineConfig, InferenceEngine
    from repro.models import Model

    cfg = smoke_config("tinyllama-1.1b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, cfg.vocab_size, 16).tolist()
    prompts = [prefix + rng.integers(0, cfg.vocab_size, 5).tolist(),
               rng.integers(0, cfg.vocab_size, 7).tolist(),
               prefix + rng.integers(0, cfg.vocab_size, 9).tolist(),
               rng.integers(0, cfg.vocab_size, 13).tolist()]
    max_new = [5, 3, 4, 6]
    eng = InferenceEngine(model, params, EngineConfig(
        page_size=16, pool_pages=32, max_pages=4, buckets=(1, 2, 4),
        probe=True))
    for p, m in zip(prompts, max_new):
        eng.submit(p, m)
    done = eng.run()
    st = eng.stats()
    eng.drain()
    balanced = eng.table.balanced()
    eng.close()
    return {
        "case": ENGINE_CASE, "jax": jax.__version__,
        "requests": [{
            "rid": r.rid, "prompt_len": len(r.prompt),
            "out_tokens": list(r.out_tokens),
            "phase_cycles": dict(r.phase_cycles),
            "decode_batches": list(r.decode_batches),
            "shared_pages": r.shared_pages,
        } for r in done],
        "phases": st["phases"],
        "stats": {
            "retraces": st["retraces"],
            "pages_peak": st["pages_peak"],
            "prefix_hits": st["prefix_hits"],
            "prefix_misses": st["prefix_misses"],
            "buckets": {str(k): v for k, v in st["buckets"].items()},
            "steps_traced": st["steps_traced"],
            "balanced_after_drain": balanced,
        },
    }


# ------------------------------------------- per-arch registry cases

def arch_slug(arch: str) -> str:
    """Golden filename stem for one registry architecture."""
    return "arch_" + arch.replace("-", "_").replace(".", "_")


def list_arch_cases() -> Dict[str, str]:
    """slug -> registry arch name, for every ``registry.list_archs()``
    entry (each gets one golden file holding a probed train step record
    AND a probed serve decode record)."""
    from repro.configs import registry
    return {arch_slug(a): a for a in registry.list_archs()}


def _arch_train(arch: str):
    """Probed ``build_train_step`` over the arch's smoke config —
    deterministic params/opt/batch, same idiom as the system tests."""
    import jax
    from repro.configs.base import TrainConfig
    from repro.configs.registry import smoke_config
    from repro.core import ProbeConfig
    from repro.distributed.steps import build_train_step
    from repro.models import Model
    from repro.optim import adamw

    import jax.numpy as jnp

    cfg = smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init(params, cfg.moment_dtype)
    B, S = 2, 32
    k = jax.random.PRNGKey(0)
    if cfg.frontend != "none":
        from repro.models.frontends import synth_frontend_batch
        batch = dict(synth_frontend_batch(cfg, B, S, jnp.bfloat16, k))
    else:
        batch = {"tokens": jax.random.randint(k, (B, S), 0,
                                              cfg.vocab_size)}
    batch["labels"] = jax.random.randint(jax.random.fold_in(k, 1),
                                         (B, S), 0, cfg.vocab_size)
    step = build_train_step(model, TrainConfig(total_steps=10,
                                               warmup_steps=1))
    return step, (params, opt, batch), ProbeConfig(max_probes=24)


def _arch_serve(arch: str):
    """Probed single-token ``decode_step`` against a fresh cache."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.configs.registry import smoke_config
    from repro.core import ProbeConfig
    from repro.models import Model

    cfg = smoke_config(arch)
    m = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init(key)
    B = 2
    shape = ShapeConfig("t", seq_len=64, global_batch=B, kind="decode")
    cache = m.init_cache(shape)
    if cfg.frontend != "none":
        from repro.models.frontends import synth_frontend_batch
        fb = synth_frontend_batch(cfg, B, 1, jnp.bfloat16, key)
        batch = {"embeds": fb["embeds"], "pos": jnp.int32(3)}
    else:
        batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
                 "pos": jnp.int32(3)}
    return m.decode_step, (params, cache, batch), \
        ProbeConfig(max_probes=24)


# ------------------------------------------------- canonical encoding

def _record_doc(pf, rec) -> Dict[str, Any]:
    """Canonical decoded-record sub-document for one probe run."""
    import jax
    from repro.core.instrument import decode_record

    dec = decode_record(jax.device_get(rec))
    return {
        "paths": list(pf.probe_paths()),
        "record": {
            "cycle": int(dec["cycle"]),
            "starts": [int(x) for x in dec["starts"]],
            "ends": [int(x) for x in dec["ends"]],
            "totals": [int(x) for x in dec["totals"]],
            "calls": [int(x) for x in dec["calls"]],
            "ring": dec["ring"].astype(int).tolist(),
        },
        "offloaded": {
            str(pid): [[int(s), int(e)] for s, e in pf.sink.records(pid)]
            for pid in range(pf.assignment.n) if pf.assignment.spill[pid]
        },
    }


def run_case(name: str) -> Dict[str, Any]:
    """Execute one case with a FRESH ProbedFunction and return its
    canonical golden document (plain JSON types, key-sorted on dump)."""
    import jax
    from repro.core import probe

    if name == ENGINE_CASE:
        return run_engine_case()
    arch_cases = list_arch_cases()
    if name in arch_cases:
        return run_arch_case(arch_cases[name])
    fn, args, cfg = CASES[name]()
    pf = probe(fn, cfg)
    _, rec = pf(*args)
    return {"case": name, "jax": jax.__version__, **_record_doc(pf, rec)}


def run_arch_case(arch: str) -> Dict[str, Any]:
    """One registry arch: probed train-step + serve-decode records."""
    import jax
    from repro.core import probe

    doc: Dict[str, Any] = {"case": arch_slug(arch), "arch": arch,
                           "jax": jax.__version__}
    for phase, builder in (("train", _arch_train), ("serve", _arch_serve)):
        fn, args, cfg = builder(arch)
        pf = probe(fn, cfg)
        _, rec = pf(*args)
        doc[phase] = _record_doc(pf, rec)
    return doc


def encode(doc: Dict[str, Any]) -> str:
    return json.dumps(doc, sort_keys=True, indent=1) + "\n"


def golden_path(name: str) -> str:
    return os.path.join(GOLDEN_DIR, f"{name}.json")


def main(argv=None) -> int:
    all_names = sorted(CASES) + [ENGINE_CASE] + sorted(list_arch_cases())
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--case", choices=all_names, default=None,
                    help="regenerate one case (default: all)")
    ap.add_argument("--diff", action="store_true",
                    help="preview the diff against the committed records "
                         "without writing anything")
    args = ap.parse_args(argv)
    names = [args.case] if args.case else all_names
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    changed = 0
    for name in names:
        new = encode(run_case(name))
        path = golden_path(name)
        old = ""
        if os.path.exists(path):
            with open(path) as f:
                old = f.read()
        if new == old:
            print(f"{name}: unchanged")
            continue
        changed += 1
        if args.diff:
            sys.stdout.writelines(difflib.unified_diff(
                old.splitlines(keepends=True), new.splitlines(keepends=True),
                fromfile=f"a/tests/golden/{name}.json",
                tofile=f"b/tests/golden/{name}.json"))
        else:
            with open(path, "w") as f:
                f.write(new)
            print(f"{name}: {'re' if old else ''}written -> {path}")
    if args.diff and changed:
        print(f"\n{changed} case(s) differ (run without --diff to rewrite)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
