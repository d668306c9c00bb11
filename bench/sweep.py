#!/usr/bin/env python3
"""Find the rate an open-loop cell sustains: one set-up, one window per
offered rate, on the chip.

    python bench/sweep.py --workload granite-3-2b.chat --seed 7 \\
        --seconds 20 --rates 2 4 6 8

For each rate it prints the tokens per second offered and delivered
(over the window and over its second half), the time to
first token (median and 95th percentile, over the first and the last
third of the window) and how long the engine took to finish after the
last arrival. Where the queue grows through the window, the last third's
tail is far above the first's and the drain is long: that rate is past
the knee. The cell's ``rate_rps`` is set once from such a sweep; runs of
the benchmark never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import time

import run
from traffic import Traffic


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args(argv)
    spec = run.load_cell(a.workload)
    vocab = spec["config"]["config"]["vocab_size"]
    mixes = [dict(spec["traffic"], rate_rps=r) for r in a.rates]
    lens = sorted({p for m in mixes
                   for p in Traffic(m, a.seed, a.seconds, vocab)
                   .prompt_lengths()})
    su = run.set_up(a.workload, a.seed, a.seconds, prompt_lens=lens)
    for mix in mixes:
        traffic = Traffic(mix, a.seed, a.seconds, vocab)
        win = run.serve(su, a.seconds, traffic=traffic)
        e2e = run.e2e_metrics(win, su.setup_s)
        thirds = []
        for k in range(3):
            lo = win.t0 + k * a.seconds / 3
            hi = lo + a.seconds / 3
            tt = [r.out_tokens.times[0] - r.due for r in win.reqs
                  if lo <= r.due < hi]
            thirds.append([run.quantile(tt, 0.5), run.quantile(tt, 0.95)])
        half = win.t0 + a.seconds / 2
        late = [t for r in win.reqs for t in r.out_tokens.times
                if half <= t <= win.t0 + a.seconds]
        print(json.dumps({
            "rate_rps": mix["rate_rps"], "requests": len(win.reqs),
            "offered_tokens_per_s": sum(r.max_new for r in win.reqs)
            / a.seconds,
            "tokens_per_s": e2e["tokens_per_s"],
            "tokens_per_s_second_half": len(late) / (a.seconds / 2),
            "ttft_p95_ms": e2e["ttft_p95_ms"], "itl_p95_ms": e2e["itl_p95_ms"],
            "ttft_p50_p95_s_by_third": thirds,
            "drain_s": win.t1 - win.t0 - a.seconds,
            "decode_lanes_per_step": (
                (win.stats1["tokens_out"] - win.stats0["tokens_out"]
                 - len(win.reqs)) / max(1, run.adapter.decode_steps(
                     win.stats1) - run.adapter.decode_steps(win.stats0)))}),
            flush=True)
        su.engine.drain()
        time.sleep(1.0)


if __name__ == "__main__":
    main()
