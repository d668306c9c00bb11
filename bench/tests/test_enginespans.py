"""CPU self-tests of the readers of the engine's own spans
(``enginespans.py`` and the metrics that read it): the readers on a
tiny traced run, the older readers unmoved by the engine's spans, idle
gaps named by the span under them, and the engine's token stamps
against the harness's."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import devtrace
import enginespans
import family
import tiny
from readings import Readings, reader
from test_bench import DIMS, SEED, SMALL_TRACE

REPO = Path(__file__).resolve().parents[2]
NEW = ("queue_wait_ms", "host_ms_per_round", "first_call_s")

# the engine's spans on the small recorded trace's host line (offsets in
# ps from 0; the window runs from 1000 ns): two rounds, an admission
# with one prefill, a decode with its sync and emission, a second
# round's prefill
ENGINE_EVENTS = [
    (20, 1500000, 5000000, {"rounds": 4, "host_s": 0.5,
                            "first_call_s": 12.5}),
    (21, 1600000, 500000, {"admitted": 1}),
    (22, 1700000, 300000, {"rid": 3, "prompt_len": 40, "pages": 3,
                           "queue_ms": 7.25}),
    (23, 2200000, 1700000, {"bucket": 2, "lanes": 2}),
    (24, 2300000, 800000, {"phase": "decode"}),
    (25, 3150000, 650000, {"finished": 0}),
    (20, 6800000, 3000000, {"rounds": 5, "host_s": 0.503,
                            "first_call_s": 12.5}),
    (22, 8500000, 1000000, {"rid": 4, "prompt_len": 10, "pages": 1,
                            "queue_ms": 1.5}),
]
NAMES = {20: "engine.round", 21: "engine.admit", 22: "engine.prefill",
         23: "engine.decode", 24: "engine.sync", 25: "engine.emit"}
STAT_IDS = {"rounds": 30, "host_s": 31, "first_call_s": 32, "admitted": 33,
            "rid": 34, "prompt_len": 35, "pages": 36, "queue_ms": 37,
            "bucket": 38, "lanes": 39, "phase": 40, "finished": 41}


def _with_engine_spans(text: str) -> str:
    """The small trace with the engine's spans added to its host line."""
    def stat(k, v):
        kind = ("str_value: \"%s\"" % v if isinstance(v, str) else
                "double_value: %r" % v if isinstance(v, float) else
                "int64_value: %d" % v)
        return "stats { metadata_id: %d %s }" % (STAT_IDS[k], kind)

    events = "".join(
        "    events { metadata_id: %d offset_ps: %d duration_ps: %d %s }\n"
        % (m, off, dur, " ".join(stat(k, v) for k, v in args.items()))
        for m, off, dur, args in ENGINE_EVENTS)
    meta = "".join(
        '  event_metadata { key: %d value { id: %d name: "%s" } }\n'
        % (m, m, n) for m, n in NAMES.items())
    meta += "".join(
        '  stat_metadata { key: %d value { id: %d name: "%s" } }\n'
        % (i, i, k) for k, i in STAT_IDS.items())
    anchor = '    events { metadata_id: 3 offset_ps: 5000000 ' \
        'duration_ps: 2000000 }\n'
    assert anchor in text
    text = text.replace(anchor, anchor + events)
    host_meta = '  event_metadata { key: 3 value { id: 3 name: ' \
        '"host.after_prefill" } }\n'
    return text.replace(host_meta, host_meta + meta)


def _write(tmp: Path, text: str) -> Path:
    from jax.profiler import ProfileData
    path = tmp / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return tmp


def _ctx(trace):
    return Readings(family=family.load("dense"), dims=DIMS,
                    peak={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8},
                    programs={"decode": "^jit_decode",
                              "prefill": "^jit_prefill",
                              "cache": "^jit_scatter"},
                    counters={"decode_steps": 2, "decode_tokens": 5},
                    trace=trace, traced={"decode": [[3, 5]],
                                         "prefill": [3]})


def _read_all(ctx, names):
    return {n: reader(n)(ctx) for n in names}


def test_engine_spans_move_no_older_reading_and_feed_the_new(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    older = [m["name"] for m in bench["per_layer"] if m["name"] not in NEW]
    plain = devtrace.load(devtrace.latest_xplane(
        str(_write(tmp_path / "plain", SMALL_TRACE))))
    spans_dir = _write(tmp_path / "spans", _with_engine_spans(SMALL_TRACE))
    traced = devtrace.load(devtrace.latest_xplane(str(spans_dir)))
    a, b = _read_all(_ctx(plain), older), _ctx(traced)
    assert a == _read_all(b, older)
    assert sum(v is not None for v in a.values()) >= 4
    assert plain.idle_gaps() == traced.idle_gaps()
    assert plain.top_ops() == traced.top_ops()
    # no engine spans where the window is another trace's
    assert all(v is None for v in _read_all(_ctx(plain), NEW).values())
    assert enginespans.of(b, tmp_path / "plain") == []
    # the new readers, pointed at the trace with the spans
    spans = enginespans.of(b, spans_dir)
    assert [s.name for s in spans][:3] == ["engine.round", "engine.admit",
                                           "engine.prefill"]
    rounds = [s for s in spans if s.name == "engine.round"]
    assert [s.args["rounds"] for s in rounds] == [4, 5]
    assert rounds[0].start == pytest.approx(500e-9)
    prefill = [s for s in spans if s.name == "engine.prefill"]
    assert [(s.args["rid"], s.args["queue_ms"]) for s in prefill] == \
        [(3, 7.25), (4, 1.5)]


def test_idle_gaps_are_named_by_the_engine_span_under_them(tmp_path):
    """Gaps [2000, 3000) ns from the window start, under the decode's
    emission; [4000, 6000) under the first round alone (its decode
    ended at 2900); [7000, 9000) under the second round's prefill,
    where no host span of the harness is open."""
    path = devtrace.latest_xplane(str(_write(
        tmp_path, _with_engine_spans(SMALL_TRACE))))
    trace = devtrace.load(path)
    _, spans = enginespans.load(path)
    got = sorted((round(s * 1e9), n, round(d * 1e9))
                 for n, d, s in enginespans.idle_gaps(trace, spans))
    assert got == [(2000, "engine.emit", 1000),
                   (4000, "engine.round", 2000),
                   (7000, "engine.prefill", 2000)]
    # the harness's names where the program has no spans
    plain = enginespans.idle_gaps(trace, [])
    assert sorted(n for n, _, _ in plain) == sorted(
        n for n, _ in trace.idle_gaps())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark with its cells added to the new metrics'."""
    root = tiny.make_root(tmp_path_factory.mktemp("spans_root"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] += list(tiny.CELLS)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_the_new_readers_on_a_tiny_traced_run(root):
    import run
    res = run.run_cell("tiny-dense.open", SEED, 2.0, True, root=root,
                       need_chip=False, peak=tiny.PEAK)
    assert res["correct"] is True, res["checks"]
    got = {n: res["metrics"][n]["value"] for n in NEW}
    assert 0 < got["host_ms_per_round"] < 1e3
    assert got["queue_wait_ms"] >= 0
    # every step program's first call came in set-up, before the window
    assert got["first_call_s"] > 0
    assert [res["metrics"][n]["unit"] for n in NEW] == ["ms", "ms", "s"]


def test_engine_token_times_match_the_harness_stamps(root):
    import engine_adapter as adapter
    import run
    su = run.set_up("tiny-dense.open", SEED, 2.0, root, need_chip=False,
                    peak=tiny.PEAK)
    win = run.serve(su, 2.0)
    adapter.release(su.engine, su.before)
    assert win.reqs and all(len(r.token_times) == len(r.out_tokens.times)
                            for r in win.reqs)
    worst = max(abs(a - b) for r in win.reqs
                for a, b in zip(r.token_times, r.out_tokens.times))
    assert worst < 1e-3
    for r in win.reqs:
        assert r.t_submit <= r.t_admit <= r.t_first == r.token_times[0]


def test_benchmark_json_holds_the_three_engine_metrics():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    m = {x["name"]: x for x in bench["per_layer"]}
    both = ["granite-3-2b.chat", "minicpm-2b.longdoc"]
    assert list(m)[-3:] == list(NEW)
    assert (m["queue_wait_ms"]["moves"], m["queue_wait_ms"]["workloads"]) \
        == ("ttft_p95_ms", ["granite-3-2b.chat"])
    assert (m["host_ms_per_round"]["moves"],
            m["host_ms_per_round"]["workloads"]) == ("tokens_per_s", both)
    assert (m["first_call_s"]["moves"], m["first_call_s"]["workloads"]) \
        == ("setup_s", both)
    for name in NEW:
        assert m[name]["source"] == "program_counter"
        assert (REPO / "bench" / "metrics" / f"{name}.py").is_file()
