"""The engine's spans, counters and request stamps.

Spans are read back from a CPU profile (``jax.profiler.trace``), where
they land on the host plane with their arguments as event stats, as on
the chip. The counters and stamps are plain host-clock numbers.
"""
import gc
import glob
import os
import time

import jax
import pytest

from repro.engine import EngineConfig, InferenceEngine
from repro.telemetry import StatusServer, TelemetryBus, render_metrics
from repro.telemetry import spans
from test_engine import _fake_engine, _mixed_trace

COUNTERS = ("rounds", "host_s", "sync_s", "publish_s", "first_calls",
            "first_call_s", "gc_s")


def _engine_spans(log_dir):
    """``engine.*`` events of the profile under ``log_dir``: (name,
    start_ns, end_ns, args, thread line)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                {k: v for k, v in ev.stats}, line.name))
    return out


def _inside(inner, outer):
    return (inner[4] == outer[4] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def _parent(ev, evs, name):
    return [o for o in evs if o[0] == name and _inside(ev, o)]


def _stamps_ordered(r):
    assert len(r.token_times) == len(r.out_tokens)
    assert r.t_submit <= r.t_admit <= r.t_first == r.token_times[0]
    assert r.token_times == sorted(r.token_times)


def test_spans_nest_carry_rids_and_compile_once(tiny_model, tmp_path):
    cfg, model, params = tiny_model
    prompts, max_new = _mixed_trace(cfg.vocab_size)
    eng = InferenceEngine(model, params, EngineConfig(
        page_size=16, pool_pages=16, max_pages=2, buckets=(1, 2, 4)))
    with jax.profiler.trace(str(tmp_path)):
        eng.warmup()
        warm = eng.counters()
        rids = [eng.submit(p, m) for p, m in zip(prompts, max_new)]
        done = eng.run()
    evs = _engine_spans(str(tmp_path))
    rounds = [e for e in evs if e[0] == "engine.round"]
    assert len(rounds) == eng.rounds > 0

    # one first call per (phase, size) program, all in warm-up: none of
    # them overlaps a round, and serving compiled nothing more
    compiles = [e for e in evs if e[0] == "engine.compile"]
    keys = [(e[3]["phase"], str(e[3]["size"])) for e in compiles]
    assert sorted(keys) == sorted((p, str(s)) for p, s in eng._steps)
    assert len(set(keys)) == len(keys) == warm["first_calls"] \
        == eng.first_calls == len(eng._steps)
    assert max(e[2] for e in compiles) <= min(e[1] for e in rounds)
    assert warm["first_call_s"] == eng.first_call_s > 0

    # sync inside decode inside round; every request's prefill names it
    decodes = [e for e in evs if e[0] == "engine.decode"]
    assert decodes
    for d in decodes:
        assert len(_parent(d, evs, "engine.round")) == 1
        syncs = [s for s in evs if s[0] == "engine.sync" and _inside(s, d)]
        assert [s[3]["phase"] for s in syncs] == ["decode"]
        assert 1 <= d[3]["lanes"] <= d[3]["bucket"]
        emits = [e for e in evs if e[0] == "engine.emit" and _inside(e, d)]
        assert len(emits) == 1 and emits[0][1] >= syncs[0][2]
    prefills = [e for e in evs if e[0] == "engine.prefill"]
    assert sorted(e[3]["rid"] for e in prefills) == sorted(rids)
    for p in prefills:
        assert _parent(p, evs, "engine.admit")
        assert [s[3]["phase"] for s in evs
                if s[0] == "engine.sync" and _inside(s, p)] == ["prefill"]
        r = done[rids.index(p[3]["rid"])]
        assert p[3]["prompt_len"] == r.prompt_len
        assert p[3]["queue_ms"] == pytest.approx(1e3 * (r.t_admit
                                                        - r.t_submit))
    assert sum(e[3]["admitted"] for e in evs
               if e[0] == "engine.admit") == len(rids)

    # each round's arguments are the counters at its start
    first = min(rounds, key=lambda e: e[1])
    assert first[3]["rounds"] == 0 and first[3]["host_s"] == 0.0
    assert first[3]["first_call_s"] == pytest.approx(warm["first_call_s"])
    last = max(rounds, key=lambda e: e[1])
    assert last[3]["rounds"] == eng.rounds - 1
    for r in done:
        _stamps_ordered(r)
    eng.drain()


def test_stamps_are_ordered_one_time_per_token():
    eng = _fake_engine()
    for n in (3, 9, 14):
        eng.submit(list(range(1, n + 1)), 1 + n % 5)
    done = eng.run()
    assert [len(r.out_tokens) for r in done] == [4, 5, 5]
    for r in done:
        _stamps_ordered(r)


def test_queue_wait_of_a_blocked_head_exceeds_the_blockers_service():
    """FCFS with a full pool: the second request waits for every page
    the first holds, so its queue wait covers the first's whole
    service; the bill on the bus carries both waits."""
    bus = TelemetryBus()
    eng = _fake_engine(pool_pages=8, max_pages=6, buckets=(1, 2))
    eng.bus = bus
    eng.submit(list(range(10)), 4)                 # 4 of 7 pages
    eng.submit(list(range(100, 116)), 5)           # 5 pages: must wait
    first, head = eng.run()
    service = first.token_times[-1] - first.t_admit
    wait = head.t_admit - head.t_submit
    assert wait > service > 0
    bills = {b["rid"]: b for b in bus.engine.recent}
    assert bills[head.rid]["queue_ms"] == pytest.approx(1e3 * wait)
    assert bills[head.rid]["first_token_ms"] == pytest.approx(
        1e3 * (head.t_first - head.t_submit))
    assert bills[first.rid]["queue_ms"] < bills[head.rid]["queue_ms"]


def test_host_sync_and_publish_seconds_fit_in_the_run():
    bus = TelemetryBus()
    bus.subscribe("phase", lambda *a: time.sleep(1e-3))
    eng = _fake_engine()
    eng.bus = bus
    for n in (3, 9, 14, 5):
        eng.submit(list(range(n)), 6)
    t = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t
    c = eng.stats()
    assert set(COUNTERS) <= set(c)
    assert c["rounds"] > 0 and c["host_s"] > 0 and c["sync_s"] > 0
    # the subscriber's sleeps are publish time, not the engine's
    steps = sum(v["steps"] for v in c["phases"].values())
    assert c["publish_s"] >= steps * 1e-3
    assert c["host_s"] + c["sync_s"] + c["publish_s"] <= wall
    assert bus.engine.counters["rounds"] == c["rounds"]


def test_gc_callback_registered_once_and_timed():
    for _ in range(3):
        _fake_engine()
    ours = [cb for cb in gc.callbacks
            if getattr(cb, "__module__", None) == spans.__name__]
    assert ours == [spans._on_gc]
    eng = _fake_engine()
    before = spans.gc_seconds()
    gc.collect()
    assert spans.gc_seconds() > before
    assert eng.counters()["gc_s"] >= spans.gc_seconds() - before


def test_gc_span_lands_in_the_profile(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
    gcs = [e for e in _engine_spans(str(tmp_path)) if e[0] == "engine.gc"]
    assert any(e[3]["generation"] == 2 for e in gcs)


def test_operator_views_show_waits_and_counters():
    bus = TelemetryBus()
    eng = _fake_engine()
    eng.bus = bus
    eng.submit([1, 2, 3], 3)
    eng.run()
    body = render_metrics(bus)
    for key in ("rounds", "host_s", "sync_s", "publish_s", "first_call_s",
                "gc_s"):
        assert f"# TYPE repro_engine_{key}_total counter" in body
    assert f"repro_engine_rounds_total {eng.rounds}" in body
    import json
    import urllib.request
    with StatusServer(bus) as srv:
        with urllib.request.urlopen(srv.url + "/engine/phases",
                                    timeout=10) as r:
            doc = json.loads(r.read())
        with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
            assert r.read().decode() == render_metrics(bus)
    bill, = doc["recent_requests"]
    assert bill["queue_ms"] >= 0 and bill["first_token_ms"] >= \
        bill["queue_ms"]
    assert doc["counters"]["rounds"] == eng.rounds
