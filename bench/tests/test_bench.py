"""CPU self-tests of the benchmark: run with ``python -m pytest bench/tests``.

They check the yardstick (counts, trace reduction, peaks, traffic,
weights), that the reference agrees with the engine at smoke size and
that its fp8 control does not, that a cell can be added from new files
alone, and that the faults a serving cell can have turn ``correct``
false. Nothing here measures speed.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import costs
import devtrace
import tiny
import weights as W
from traffic import Traffic

REPO = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SEED = 2**33 + 17                # larger than 32 bits, as the driver's are

# --------------------------------------------------------------- counts

DIMS = {"L": 1, "d": 4, "H": 2, "kv": 1, "hd": 2, "ff": 8, "V": 10,
        "tied": True, "eps": 1e-5, "theta": 1e4}


def test_costs_against_hand_counts():
    # q, o: 4*4 each; k, v: 4*2 each; gate, up, down: 4*8 each; head 4*10
    assert costs.matmul_params(DIMS) == 16 + 16 + 8 + 8 + 96 + 40
    # norms: 2 per layer + final, 4 wide; bf16
    assert costs.weight_bytes(DIMS) == (184 + 12) * 2
    # k and v rows, 1 kv head of 2, 1 layer, bf16
    assert costs.kv_bytes_per_token(DIMS) == 8
    # 2 * 184 per token; attention 4 * L * H * hd = 16 per visible position
    assert costs.token_flops(DIMS, 3) == 368 + 48
    assert costs.prefill_flops(DIMS, 3) == 3 * 368 + 16 * (1 + 2 + 3)
    flops, nbytes = costs.decode_step(DIMS, [3, 5])
    assert flops == (368 + 48) + (368 + 80)
    assert nbytes == 392 + 8 * (3 + 5 + 2)
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_time_s(864, 472, peak) == pytest.approx(47.2)
    assert costs.least_time_s(8640, 472, peak) == pytest.approx(86.4)


def test_peaks_of_v5e_and_unknown_device():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        costs.peaks("cpu")


# ---------------------------------------------------------------- trace

SMALL_TRACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 13 offset_ps: 6000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 11 offset_ps: 0 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "jit(decode)/layers/attn" }
             stats { metadata_id: 2 str_value: "jit_decode" } }
    events { metadata_id: 12 offset_ps: 500000 duration_ps: 1500000 }
    events { metadata_id: 12 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 11 offset_ps: 6000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "jit(decode)/layers/attn" }
             stats { metadata_id: 2 str_value: "jit_decode" } }
  }
  event_metadata { key: 10 value { id: 10 name: "jit_decode(42)" } }
  event_metadata { key: 13 value { id: 13 name: "jit_prefill(7)" } }
  event_metadata { key: 11 value { id: 11 name: "fusion.1" } }
  event_metadata { key: 12 value { id: 12 name: "fusion.2" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "hlo_module" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "host.trace_window" } }
  event_metadata { key: 2 value { id: 2 name: "host.after_decode" } }
  event_metadata { key: 3 value { id: 3 name: "host.after_prefill" } }
}
'''


def test_trace_reduction_on_a_small_recorded_trace(tmp_path):
    """Device ops in ns from the window start (1000 ns, its span):
    [0, 1000), [500, 2000), [3000, 4000), [6000, 7000) of a 9000 ns
    window; programs decode [0, 4000) and prefill [6000, 7000)."""
    from jax.profiler import ProfileData
    path = tmp_path / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SMALL_TRACE))
    tr = devtrace.load(devtrace.latest_xplane(str(tmp_path)))
    assert tr.n_devices == 1
    assert tr.window_s == pytest.approx(9000e-9)
    assert tr.busy_s() == pytest.approx(4000e-9)        # 2000 + 1000 + 1000
    assert tr.program_times("^jit_decode") == [pytest.approx(4000e-9)]
    assert tr.program_times("^jit_prefill") == [pytest.approx(1000e-9)]
    assert tr.program_times("^jit_") == [pytest.approx(4000e-9),
                                         pytest.approx(1000e-9)]
    assert tr.top_ops() == [["fusion.2", pytest.approx(2500e-9)],
                            ["jit_decode:jit(decode)/layers/attn",
                             pytest.approx(2000e-9)]]
    # idle [2000, 3000) mid-way through host.after_decode ([1000, 4000)),
    # [4000, 6000) through host.after_prefill ([4000, 6000)), and
    # [7000, 9000) under no host span
    gaps = sorted((round(s * 1e9), n) for n, s in tr.idle_gaps())
    assert gaps == [(1000, "host.after_decode"), (2000, "host.after_prefill"),
                    (2000, "host.none")]


def test_readers_find_nothing_without_a_device_trace():
    from readings import Readings, reader
    ctx = Readings(dims=DIMS, peak={"bf16_flops_per_s": 1.0,
                                     "hbm_bytes_per_s": 1.0},
                   programs={"decode": "x"},
                   counters={"decode_steps": 0, "decode_tokens": 0},
                   trace=devtrace.Trace(1.0, [], []))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert reader(m["name"])(ctx) is None, m["name"]


# -------------------------------------------------------------- traffic

def test_every_seed_gets_the_same_work_in_another_order():
    for path in sorted((REPO / "bench" / "traffic").glob("*.json")):
        spec = json.loads(path.read_text())
        a, b = (Traffic(spec, s, 30, 1000) for s in (SEED, 5))
        if spec["loop"] == "open":
            ra, rb = a.schedule(), b.schedule()
            assert len(ra) == len(rb) == round(spec["rate_rps"] * 30)
            assert ra[-1].arrival_s == pytest.approx(rb[-1].arrival_s)
            assert ra[-1].arrival_s == pytest.approx(30, rel=0.05)
        else:
            ra = next(a.blocks())
            rb = next(b.blocks())
        assert sorted(len(r.prompt) for r in ra) == \
            sorted(len(r.prompt) for r in rb)
        assert sorted(r.max_new for r in ra) == sorted(r.max_new for r in rb)
        assert [len(r.prompt) for r in ra] != [len(r.prompt) for r in rb]
        lens = a.prompt_lengths()
        lo, hi = spec["prompt_tokens"]["min"], spec["prompt_tokens"]["max"]
        assert lo <= min(lens) and max(lens) <= hi
    again = Traffic(spec, SEED, 30, 1000)
    assert [r.prompt for r in next(again.blocks())] == \
        [r.prompt for r in ra]


# -------------------------------------------------------------- weights

def test_a_layer_rebuilt_alone_equals_its_slice_of_the_stack():
    dims = dict(DIMS, L=3, d=16, H=4, kv=2, hd=4, ff=32, V=50)
    key = W.seed_key(SEED)
    stack = jax.jit(lambda k: W.stacked_layers(k, dims))(key)
    one = W.layer_weights(key, 2, dims)
    for name, v in one.items():
        assert v.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(stack[name][2]),
                                      np.asarray(v))
    assert not np.array_equal(np.asarray(stack["q"][0]),
                              np.asarray(stack["q"][1]))
    assert W.seed_key(2**40) is not None
    k40, k0 = (jax.random.key_data(W.seed_key(s)) for s in (2**40, 0))
    assert not np.array_equal(np.asarray(k40), np.asarray(k0))


# --------------------------------------------------------- whole runs

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_root"))


def _run(root, cell, trace=False, seed=SEED):
    import run
    return run.run_cell(cell, seed, 2.0, trace, root=root, need_chip=False,
                        peak=tiny.PEAK, t_start=__import__("time")
                        .perf_counter())


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_a_cell_added_from_new_files_runs_and_is_correct(root, cell):
    res = _run(root, cell)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]
                                   if "workloads" not in m}
    assert res["device"]["platform"] == "cpu"
    # the reference agrees with the engine's prefill-then-decode within
    # the limit that bf16 rounding over 2 layers keeps to
    assert res["checks"]["max_gap"]["value"] <= tiny.CELL["check"]["max_gap"]


def test_a_traced_run_reports_the_new_metric(root):
    res = _run(root, "tiny-dense.closed", trace=True)
    assert res["correct"] is True
    assert res["metrics"]["tiny_prompt_tokens"]["value"] > 0
    assert res["metrics"]["decode_lanes_per_step"]["value"] > 1
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_fp8_control_fails_the_limit_that_sound_runs_pass(root):
    import run
    for seed in (1, SEED):
        sound = run.run_cell("tiny-dense.closed", seed, 2.0, False, root=root,
                             need_chip=False, peak=tiny.PEAK)
        ctl = run.run_cell("tiny-dense.closed", seed, 2.0, False, root=root,
                           need_chip=False, peak=tiny.PEAK, control=True)
        assert sound["correct"] is True, sound["checks"]
        assert ctl["correct"] is False, ctl["checks"]
        assert sound["checks"]["max_gap"]["value"] <= \
            tiny.CELL["check"]["max_gap"] < ctl["checks"]["max_gap"]["value"]


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_a_faulty_timed_path_is_not_correct(root, fault, monkeypatch):
    """Faults planted in the program under the harness: the decode step
    emits another token than it computed; the page scatter returns the
    KV pool unchanged (decode then reads KV never written); or the
    decode step serves only the first half of its lanes and hands their
    tokens to the rest."""
    import repro.engine.engine as eng

    if fault in ("token_altered", "half_batch"):
        build = eng.build_paged_decode

        def alter(tok, vocab):
            if fault == "token_altered":
                return (tok + 1) % vocab
            n = tok.shape[0]
            return jnp.concatenate([tok[:n - n // 2], tok[:n // 2]])

        def faulty(model, *a, **kw):
            fn = build(model, *a, **kw)

            def decode(*args):
                logits, pk, pv, tok = fn(*args)
                return logits, pk, pv, alter(tok, model.cfg.vocab_size)
            return decode
        monkeypatch.setattr(eng, "build_paged_decode", faulty)
    else:
        monkeypatch.setattr(eng, "build_page_scatter",
                            lambda n: lambda pk, pv, k, v, ids: (pk, pv))
    res = _run(root, "tiny-dense.closed")
    assert res["correct"] is False
    assert res["checks"]["max_gap"]["value"] > \
        res["checks"]["max_gap"]["limit"]


def test_no_accelerator_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-3-2b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


# ------------------------------------------------------------ the file

def test_benchmark_json_keeps_to_its_contract():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    cfgs = {c["name"] for c in b["configs"]}
    used = {w["config"] for w in b["workloads"]}
    assert cfgs == used
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file()
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (REPO / "bench" / "cells" / f"{w['name']}.json").is_file()
        reports = [m for m in b["end_to_end"]
                   if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in reports} and len(reports) > 1
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in b["per_layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert cell in e2e[m["moves"]].get("workloads", [cell])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
