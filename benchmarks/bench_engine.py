"""Serving-engine benchmark: per-phase cycle attribution at steady state.

A fixed-seed mixed request trace (shared prefixes, varied prompt and
decode lengths) is served through the continuous-batching engine with
probing on. All gated metrics come from the deterministic model clock
and the engine's exact bookkeeping, so they are machine-independent:

- ``cycles``            — model-clock cycles per phase (prefill /
                          cache / decode) and in total
- ``probed_steps``      — step-function invocations per phase
                          (scheduling drift changes these before it
                          changes wall time)
- ``retraces``          — compile-cache growth beyond one trace per
                          step (must stay 0: the zero-retrace contract)
- ``pages_peak``        — page-pool high-water occupancy
- ``hit_x1000``         — prefix-cache hit rate x1000
- ``evictions``         — prefix-cache pages reclaimed under pressure
- ``hol_blocked_steps`` — decode rounds displaced by whole-prompt
                          prefills beyond one chunk quantum
- ``tok_per_step_x1000``— emitted tokens per engine step x1000 (the
                          scheduler's throughput shape)

Two A/B workloads lock in the throughput-overhaul wins:

- ``engine/serve_hol_{whole,chunked}`` — the same long-prompt/decode
  mix served whole-prompt vs chunked; chunking must pin
  ``hol_blocked_steps`` at 0 while the whole-prompt run pays > 0.
- ``engine/evict_{lru,clear}`` — the same pressure trace (pool smaller
  than the prefix working set) under LRU vs all-or-nothing eviction;
  LRU must keep a strictly higher prefix hit rate.
"""
import time

import numpy as np

from benchmarks.common import emit


def _trace(vocab: int, seed: int = 23):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, 16).tolist()
    reqs = []
    for i in range(8):
        base = prefix if i % 2 == 0 else []
        tail = rng.integers(0, vocab, int(rng.integers(3, 14))).tolist()
        reqs.append((base + tail, int(rng.integers(2, 7))))
    return reqs


def _serve_stats(model, params, reqs, **cfg_overrides):
    """Serve one trace on a fresh engine; returns its stats()."""
    from repro.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(model, params, EngineConfig(
        page_size=16, probe=True, **cfg_overrides))
    for prompt, max_new in reqs:
        eng.submit(prompt, max_new)
    done = eng.run()
    assert len(done) == len(reqs)
    st = eng.stats()
    eng.drain()
    assert eng.table.balanced(), "page accounting out of balance"
    eng.close()
    return st


def _hol_trace(vocab: int, seed: int = 31):
    """One decode-heavy request followed by long prompts that, served
    whole, head-of-line-block its decode rounds."""
    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, vocab, 5).tolist(), 10)]
    for _ in range(2):
        reqs.append((rng.integers(0, vocab, 40).tolist(), 2))
    return reqs


def _pressure_trace(vocab: int, seed: int = 47):
    """Two hot shared prefixes interleaved with one-off cold prompts:
    the prefix working set exceeds the pool, so every admission beyond
    the first few must reclaim tree pages."""
    rng = np.random.default_rng(seed)
    hot = [rng.integers(0, vocab, 16).tolist() for _ in range(2)]
    reqs = []
    for i in range(15):
        if i % 3 == 2:
            base = rng.integers(0, vocab, 16).tolist()     # cold
        else:
            base = hot[i % 3]
        tail = rng.integers(0, vocab, 5).tolist()
        reqs.append((base + tail, 3))
    return reqs


def run():
    import jax

    from repro.configs.registry import smoke_config
    from repro.engine import EngineConfig, InferenceEngine
    from repro.models import Model

    cfg = smoke_config("tinyllama-1.1b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        page_size=16, pool_pages=32, max_pages=3, buckets=(1, 2, 4),
        probe=True))
    reqs = _trace(cfg.vocab_size)
    t0 = time.perf_counter()
    for prompt, max_new in reqs:
        eng.submit(prompt, max_new)
    done = eng.run()
    elapsed = time.perf_counter() - t0
    st = eng.stats()
    assert len(done) == len(reqs)
    assert all(len(r.out_tokens) == m for r, (_, m) in zip(done, reqs))

    total = sum(v["cycles"] for v in st["phases"].values())
    steps = sum(v["steps"] for v in st["phases"].values())
    emit("engine/serve", elapsed / len(reqs) * 1e6,
         f"cycles={total};retraces={st['retraces']};"
         f"pages_peak={st['pages_peak']};"
         f"hit_x1000={st['prefix_hit_rate'] * 1000:.0f};"
         f"evictions={st['evictions']};"
         f"hol_blocked_steps={st['hol_blocked_steps']};"
         f"tok_per_step_x1000={st['tokens_out'] * 1000 // steps}")
    for phase, v in st["phases"].items():
        emit(f"engine/{phase}", 0.0,
             f"cycles={v['cycles']};probed_steps={v['steps']}")
    eng.drain()
    assert eng.table.balanced(), "page accounting out of balance"
    eng.close()

    # -- chunked prefill vs whole-prompt: head-of-line displacement ----
    hol_reqs = _hol_trace(cfg.vocab_size)
    variants = {"whole": 0, "chunked": 1}
    hol_stats = {}
    for name, chunk in variants.items():
        s = _serve_stats(model, params, hol_reqs, pool_pages=32,
                         max_pages=3, buckets=(1, 2),
                         prefill_chunk_pages=chunk)
        hol_stats[name] = s
        steps = sum(v["steps"] for v in s["phases"].values())
        emit(f"engine/serve_hol_{name}", 0.0,
             f"hol_blocked_steps={s['hol_blocked_steps']};"
             f"retraces={s['retraces']};"
             f"tok_per_step_x1000={s['tokens_out'] * 1000 // steps}")
    assert hol_stats["whole"]["hol_blocked_steps"] > 0, \
        "HoL workload no longer blocks the whole-prompt scheduler"
    assert hol_stats["chunked"]["hol_blocked_steps"] == 0, \
        "chunked prefill must never head-of-line-block decode"

    # -- LRU vs clear() eviction under pool pressure -------------------
    press_reqs = _pressure_trace(cfg.vocab_size)
    evict_stats = {}
    for policy in ("lru", "clear"):
        s = _serve_stats(model, params, press_reqs, pool_pages=7,
                         max_pages=2, buckets=(1,),
                         evict_policy=policy)
        evict_stats[policy] = s
        emit(f"engine/evict_{policy}", 0.0,
             f"hit_x1000={s['prefix_hit_rate'] * 1000:.0f};"
             f"evictions={s['evictions']};retraces={s['retraces']}")
    assert evict_stats["lru"]["evictions"] > 0, \
        "pressure trace did not trigger LRU eviction"
    assert evict_stats["clear"]["evictions"] > 0, \
        "pressure trace did not trigger clear() eviction"
    assert (evict_stats["lru"]["prefix_hit_rate"]
            > evict_stats["clear"]["prefix_hit_rate"]), \
        "LRU eviction must strictly beat clear() on prefix hit rate"


if __name__ == "__main__":
    run()
