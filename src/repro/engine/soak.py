"""Engine soak: waves of random requests with flat-memory assertions.

The nightly CI runs this as ``python -m repro.engine.soak``: a long
random request trace (mixed prompt lengths, decode budgets, and shared
prefixes, in randomized arrival order) served wave after wave through
one :class:`~repro.engine.InferenceEngine`. After every wave the driver
asserts the steady-state invariants a long-lived server depends on:

- zero retraces — every step shape was traced during wave 1 and the
  compile caches never grow again;
- page accounting balances — after ``drain()`` the table returns to
  all-free (no leaked or double-freed pages);
- flat host memory — Python-side traced allocations after the last
  wave stay within a fixed slack of the first wave's high-water mark
  (finished requests are ``reap()``-ed per wave, aggregates are
  constant-size);
- flat device buffers — ``jax.live_arrays()`` after the last wave
  matches the first wave's count within a fixed slack (donated pools
  and per-step outputs are rebound, never accumulated).

``--pressure`` shrinks the page pool to ~60% of the trace's working
set so every wave must reclaim prefix-tree pages: the run additionally
asserts nonzero evictions, a prefix hit-rate floor (LRU keeps the hot
prefixes resident), and that ``PagePoolExhausted`` never fires — the
evictor alone absorbs the pressure. ``--chunk N`` serves the same
trace through the chunked-prefill scheduler (one more pinned trace per
chunk-shape bucket, still zero retraces after wave 1).
"""
from __future__ import annotations

import argparse
import tracemalloc
from typing import List

import numpy as np


def _wave(rng: np.random.Generator, eng, n_requests: int,
          vocab: int, prefixes: List[List[int]]) -> List[int]:
    ps = eng.config.page_size
    cap = eng.config.max_pages * ps
    rids = []
    for _ in range(n_requests):
        prompt: List[int] = []
        if rng.random() < 0.5:
            prompt += prefixes[int(rng.integers(len(prefixes)))]
        prompt += rng.integers(0, vocab,
                               int(rng.integers(1, 2 * ps))).tolist()
        max_new = int(rng.integers(1, ps))
        if len(prompt) + max_new - 1 > cap:
            prompt = prompt[:cap - max_new + 1 - ps]
        rids.append(eng.submit(prompt, max_new))
    return rids


def soak(*, arch: str = "tinyllama-1.1b", waves: int = 3,
         requests_per_wave: int = 8, seed: int = 0,
         use_kernel: bool = False, probe: bool = False,
         pressure: bool = False, chunk: int = 0,
         min_hit_rate: float = 0.15,
         mem_slack_bytes: int = 512 * 1024,
         buffer_slack: int = 16, verbose: bool = True) -> dict:
    import jax
    from repro.configs.registry import smoke_config
    from repro.engine import EngineConfig, InferenceEngine
    from repro.models.model import Model

    cfg = smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    # a wave's working set is ~4 pages per request (prefix + tail +
    # decode budget); under --pressure the pool holds ~60% of that, so
    # steady state is only reachable by evicting finished prefix pages
    pool = (max(12, int(0.6 * requests_per_wave * 4)) if pressure
            else 48)
    eng = InferenceEngine(model, params, EngineConfig(
        page_size=16, pool_pages=pool, max_pages=8, buckets=(1, 2, 4),
        use_kernel=use_kernel, pages_per_step=2, probe=probe,
        prefill_chunk_pages=chunk))
    rng = np.random.default_rng(seed)
    # one full page each, so later waves hit the prefix cache
    prefixes = [rng.integers(0, cfg.vocab_size, 16).tolist()
                for _ in range(3)]

    eng.warmup()                     # compile caches filled before wave 0
    tracemalloc.start()
    marks, bufs, served = [], [], 0
    for w in range(waves):
        rids = _wave(rng, eng, requests_per_wave, cfg.vocab_size, prefixes)
        eng.run()
        done = eng.reap()
        assert sorted(r.rid for r in done) == sorted(rids), \
            f"wave {w}: starved requests"
        assert all(len(r.out_tokens) == r.max_new for r in done)
        served += len(done)
        st = eng.stats()
        assert st["retraces"] == 0, f"wave {w}: retraced: {st}"
        mem = tracemalloc.get_traced_memory()[0]
        marks.append(mem)
        bufs.append(len(jax.live_arrays()))
        if verbose:
            print(f"wave {w}: {len(done)} served, "
                  f"pages_peak={st['pages_peak']}, "
                  f"hit_rate={st['prefix_hit_rate']:.2f}, "
                  f"evictions={st['evictions']}, "
                  f"host_mem={mem / 1024:.0f}KiB, "
                  f"buffers={bufs[-1]}", flush=True)
    tracemalloc.stop()
    eng.drain()
    assert eng.table.balanced(), "page accounting out of balance at drain"
    assert marks[-1] <= marks[0] + mem_slack_bytes, \
        f"host memory grew {marks[-1] - marks[0]}B over " \
        f"{waves} waves (> {mem_slack_bytes}B slack)"
    assert bufs[-1] <= bufs[0] + buffer_slack, \
        f"device buffers grew {bufs[0]} -> {bufs[-1]} over {waves} waves"
    st = eng.stats()
    if pressure:
        assert st["evictions"] > 0, \
            "pressure pool never forced an eviction (pool too large?)"
        assert st["prefix_hit_rate"] >= min_hit_rate, \
            f"prefix hit rate {st['prefix_hit_rate']:.2f} fell below " \
            f"{min_hit_rate} under pressure (evictor dropping hot pages?)"
    eng.close()
    out = {"served": served, "mem_first": marks[0], "mem_last": marks[-1],
           "buffers_first": bufs[0], "buffers_last": bufs[-1], **st}
    if verbose:
        print(f"soak OK: {served} requests over {waves} waves, "
              f"mem {marks[0]} -> {marks[-1]} bytes")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--waves", type=int, default=3)
    ap.add_argument("--requests-per-wave", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel", action="store_true",
                    help="decode through the paged_attention Pallas kernel")
    ap.add_argument("--probe", action="store_true",
                    help="run every phase under a ProbeSession")
    ap.add_argument("--pressure", action="store_true",
                    help="shrink the page pool to ~60%% of the working "
                         "set; asserts evictions happen and the prefix "
                         "hit rate holds its floor")
    ap.add_argument("--chunk", type=int, default=0,
                    help="prefill chunk quantum in pages (0 = whole)")
    ap.add_argument("--min-hit-rate", type=float, default=0.15,
                    help="prefix hit-rate floor under --pressure")
    args = ap.parse_args()
    soak(arch=args.arch, waves=args.waves,
         requests_per_wave=args.requests_per_wave, seed=args.seed,
         use_kernel=args.kernel, probe=args.probe,
         pressure=args.pressure, chunk=args.chunk,
         min_hit_rate=args.min_hit_rate)


if __name__ == "__main__":
    main()
