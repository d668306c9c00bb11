"""Probe-guided autotuning DSE engine (ISSUE 2 tentpole): cache
hit/miss semantics under IR-hash invalidation, static pruning safety,
successive-halving budget accounting, and the repro.tune CLI."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import (DeviceBudget, DSEEngine, EvalCache, SearchSpace,
                        device_kind)
from repro.kernels import tuning
from repro.kernels.search_spaces import flash_attention_space


def toy_space(scale: float = 1.0, values=(1, 2, 4)) -> SearchSpace:
    """Cheap non-Pallas space: model cycles grow with cfg['n'], so the
    measured-best is always n=min(values) and the default (n=max) loses."""
    x = jnp.ones((8, 16)) * 0.1
    w = jnp.eye(16) * 0.5

    def bind(cfg):
        def fn(x, w):
            y = x
            for _ in range(cfg["n"]):
                y = jnp.tanh(y @ w) * scale
            return y
        return fn

    return SearchSpace(kernel_id="toy", axes={"n": tuple(values)},
                       bind=bind, args=(x, w),
                       default={"n": max(values)})


@pytest.fixture()
def cache(tmp_path):
    return EvalCache(str(tmp_path / "dse"))


# ------------------------------------------------------------- cache

def test_cache_hit_miss_semantics(cache):
    dev = device_kind()
    cfg = {"block_q": 64, "block_k": 64, "pipeline": 1}
    assert cache.get("flash_attention", cfg, "aaaa", dev) is None
    cache.put("flash_attention", cfg, "aaaa", dev,
              cycles_per_step=123.0, steps=4)
    hit = cache.get("flash_attention", cfg, "aaaa", dev)
    assert hit is not None and hit["cycles_per_step"] == 123.0
    # a longer-run requirement misses a short-run entry
    assert cache.get("flash_attention", cfg, "aaaa", dev,
                     min_steps=8) is None
    # IR-hash invalidation: same config, edited kernel -> different hash
    assert cache.get("flash_attention", cfg, "bbbb", dev) is None
    # config identity is exact
    assert cache.get("flash_attention", {**cfg, "block_q": 128}, "aaaa",
                     dev) is None
    # persists across instances (on-disk)
    again = EvalCache(cache.root)
    assert again.get("flash_attention", cfg, "aaaa", dev) is not None
    assert again.best_config("flash_attention", dev) == cfg


def test_warm_cache_skips_all_measurements(cache):
    space = toy_space()
    cold = DSEEngine(space, cache=cache, max_steps=4).tune()
    assert cold.n_measurements > 0
    warm = DSEEngine(space, cache=cache, max_steps=4).tune()
    assert warm.n_measurements == 0, "warm run must be 100% cache hits"
    assert warm.measured_steps == 0
    assert warm.n_cache_hits > 0
    assert warm.best.config == cold.best.config


def test_latest_tuning_run_decides_best_config(cache):
    # raw eval entries are not mutually comparable (cycles scale with
    # shape); best_config must serve the LATEST run's winner, not the
    # globally lowest-cycles entry
    first = DSEEngine(toy_space(values=(1, 2, 4)), cache=cache,
                      max_steps=2).tune()
    assert first.best.config == {"n": 1}
    assert cache.best_config("toy") == {"n": 1}
    # a later run over a space excluding n=1: its winner (n=2, higher
    # absolute cycles than the stale n=1 entry) must now be served
    second = DSEEngine(toy_space(values=(2, 4)), cache=cache,
                       max_steps=2).tune()
    assert second.best.config == {"n": 2}
    assert cache.best_config("toy") == {"n": 2}
    # clearing the kernel also clears its winner record
    cache.clear("toy")
    assert cache.best_config("toy") is None


def test_kernel_edit_invalidates_cache(cache):
    # "editing the kernel" = a bind that lowers to different IR; the
    # fingerprint changes, so identical configs re-measure
    cold = DSEEngine(toy_space(scale=1.0), cache=cache, max_steps=2).tune()
    edited = DSEEngine(toy_space(scale=2.0), cache=cache,
                       max_steps=2).tune()
    assert edited.n_measurements == cold.n_measurements
    # and the unedited space still hits
    warm = DSEEngine(toy_space(scale=1.0), cache=cache, max_steps=2).tune()
    assert warm.n_measurements == 0


# ------------------------------------------- successive halving budget

def test_successive_halving_budget_accounting(cache):
    # 3 candidates, r0=1, eta=2, max_steps=4:
    #   rung 1: 3 x 1 step; keep ceil(3/2)=2
    #   rung 2: 2 x 2 steps; keep 1
    #   rung 3: 1 x 4 steps -> done
    # + the default baseline (n=4, eliminated at rung 1) topped up to
    #   the finalist's 4 steps for a like-for-like comparison
    res = DSEEngine(toy_space(values=(1, 2, 4)), cache=cache,
                    r0=1, eta=2, max_steps=4).tune()
    assert res.n_candidates == 3
    assert res.n_measurements == (3 + 2 + 1) + 1
    assert res.measured_steps == (3 * 1 + 2 * 2 + 1 * 4) + 4
    # the cheapest config wins and ran the full finalist budget
    assert res.best.config == {"n": 1}
    assert res.best.steps == 4
    # the baseline was re-measured at the finalist's rung
    assert res.default.steps == res.best.steps
    # an eliminated non-default candidate kept its short-run measurement
    mid = next(t for t in res.trials if t.config == {"n": 2})
    assert mid.steps < 4


def test_default_always_measured(cache):
    res = DSEEngine(toy_space(), cache=cache, max_steps=2).tune()
    assert res.default is not None and res.default.measured
    assert res.default.config == {"n": 4}
    assert res.best.cycles_per_step <= res.default.cycles_per_step
    assert res.speedup >= 1.0


# ------------------------------------------------------ static pruning

@pytest.fixture(scope="module")
def flash_space():
    return flash_attention_space(B=1, H=1, S=128, D=16,
                                 blocks_q=(64, 128), blocks_k=(64, 128),
                                 pipelines=(1, 2))


def test_pruning_never_discards_measured_best(flash_space, tmp_path):
    # measure EVERY candidate (r0 == max_steps: single exhaustive rung)
    unpruned = DSEEngine(flash_space, budget=None,
                         cache=EvalCache(str(tmp_path / "a")),
                         r0=1, max_steps=1).tune()
    assert unpruned.n_pruned == 0
    measured_best = unpruned.best.config
    # default pruning = real device ceilings + a generous static-cycles
    # ratio; neither may reject the config that actually measures best
    engine = DSEEngine(flash_space, budget=DeviceBudget(),
                       cache=EvalCache(str(tmp_path / "b")),
                       static_prune_ratio=4.0, r0=1, max_steps=1)
    trials = [engine.analyze(c) for c in flash_space.candidates()]
    survivors = engine.prune(trials)
    assert measured_best in [t.config for t in survivors]


def test_tight_budget_prunes_but_respects_it(flash_space, cache):
    # a VMEM ceiling between the smallest and largest candidate
    engine = DSEEngine(flash_space, budget=None, cache=cache)
    trials = [engine.analyze(c) for c in flash_space.candidates()]
    sizes = sorted(t.resources.vmem_bytes for t in trials)
    ceiling = (sizes[0] + sizes[-1]) // 2
    engine = DSEEngine(flash_space,
                       budget=DeviceBudget(vmem_bytes=ceiling), cache=cache)
    survivors = engine.prune(trials)
    assert 0 < len(survivors) < len(trials)
    assert all(t.resources.vmem_bytes <= ceiling for t in survivors)
    pruned = [t for t in trials if t.pruned is not None]
    assert all("vmem" in t.pruned for t in pruned)


# ---------------------------------------------------- tuned registry

def test_tuned_registry_resolution(cache):
    tuning.clear_tuned()
    try:
        assert tuning.tuned_value("flash_attention", "block_q", 128) == 128
        tuning.set_tuned("flash_attention", {"block_q": 64, "block_k": 64,
                                             "pipeline": 2})
        assert tuning.tuned_value("flash_attention", "block_q", 128) == 64
        # tuned configs change tiling, never outputs
        from repro.kernels import ops, ref
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 16))
        k = jax.random.normal(ks[1], (1, 2, 128, 16))
        v = jax.random.normal(ks[2], (1, 2, 128, 16))
        o_tuned = ops.flash_attention(q, k, v, causal=True)
        o_ref = ref.flash_attention_ref(q, k, v, causal=True)
        assert float(jnp.abs(o_tuned - o_ref).max()) < 2e-5
    finally:
        tuning.clear_tuned()


def test_tuned_config_survives_foreign_shapes(cache):
    # a config tuned at S=256 must not crash the wrappers at shapes it
    # doesn't divide — tiles fall back to the gcd, pipeline to 1
    from repro.kernels import ops, ref
    tuning.clear_tuned()
    try:
        tuning.set_tuned("flash_attention", {"block_q": 64, "block_k": 64,
                                             "pipeline": 2})
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 2, 96, 16))      # 96 % 64 != 0
        k = jax.random.normal(ks[1], (1, 2, 96, 16))
        v = jax.random.normal(ks[2], (1, 2, 96, 16))
        o = ops.flash_attention(q, k, v, causal=True)
        o_ref = ref.flash_attention_ref(q, k, v, causal=True)
        assert float(jnp.abs(o - o_ref).max()) < 2e-5
        tuning.set_tuned("ssd_scan", {"chunk": 64, "pipeline": 4})
        B, L, H, P, G, N = 1, 96, 4, 8, 2, 16             # 96 % 64 != 0
        x = jax.random.normal(ks[0], (B, L, H, P)) * 0.5
        a = -jnp.abs(jax.random.normal(ks[1], (B, L, H))) * 0.3
        b = jax.random.normal(ks[2], (B, L, G, N)) * 0.5
        c = jax.random.normal(jax.random.fold_in(ks[2], 1),
                              (B, L, G, N)) * 0.5
        y = ops.ssd_scan(x, a, b, c)
        yk = ref.ssd_ref(x.transpose(0, 2, 1, 3), a.transpose(0, 2, 1),
                         b.transpose(0, 2, 1, 3),
                         c.transpose(0, 2, 1, 3))[0].transpose(0, 2, 1, 3)
        assert float(jnp.abs(y - yk).max() /
                     (jnp.abs(yk).max() + 1e-9)) < 2e-5
    finally:
        tuning.clear_tuned()


def test_load_cache_into_registry(cache):
    cfg = {"block_q": 64, "block_k": 64, "pipeline": 1}
    cache.put("flash_attention", cfg, "ffff", device_kind(),
              cycles_per_step=10.0, steps=4)
    tuning.clear_tuned()
    try:
        loaded = tuning.load_cache("flash_attention", cache_dir=cache.root)
        assert loaded == {"flash_attention": cfg}
        assert tuning.tuned_value("flash_attention", "block_q", 128) == 64
    finally:
        tuning.clear_tuned()


# ------------------------------------------------------------ CLI

def test_tune_cli_smoke(tmp_path, capsys, monkeypatch):
    from repro.launch.tune import main
    # main() turns on the persistent compile cache; a placed directory
    # keeps this worker's JAX config (and the checkout) as they were
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    cache_dir = str(tmp_path / "cli")
    rc = main(["--kernel", "flash_attention", "--seq", "64", "--dim", "16",
               "--heads", "1", "--cache-dir", cache_dir, "--max-steps", "2",
               "--json", str(tmp_path / "tune.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "DSE leaderboard: flash_attention" in out
    assert (tmp_path / "tune.json").exists()
    # the winner is now loadable for --autotune
    assert EvalCache(cache_dir).best_config("flash_attention") is not None
    tuning.clear_tuned()


# --------------------------------------- cache correctness regressions

def test_put_keeps_higher_step_entry(cache):
    """Regression: put() used to overwrite unconditionally, so a cheap
    1-step probe could clobber a converged 8-step measurement."""
    cfg = {"n": 1}
    cache.put("toy", cfg, "aaaa", device_kind(),
              cycles_per_step=100.0, steps=8)
    kept = cache.put("toy", cfg, "aaaa", device_kind(),
                     cycles_per_step=999.0, steps=1)
    assert kept["steps"] == 8 and kept["cycles_per_step"] == 100.0
    got = cache.get("toy", cfg, "aaaa", device_kind())
    assert got["steps"] == 8 and got["cycles_per_step"] == 100.0
    # equal step count is a refresh, not a downgrade
    cache.put("toy", cfg, "aaaa", device_kind(),
              cycles_per_step=90.0, steps=8)
    assert cache.get("toy", cfg, "aaaa", device_kind())[
        "cycles_per_step"] == 90.0


_WRITER = """
import sys
from repro.core import EvalCache
root, tag = sys.argv[1], sys.argv[2]
cache = EvalCache(root)
for i in range(40):
    cache.put("toy", {"n": i}, "f" + tag, "cpu",
              cycles_per_step=float(i), steps=4)
cache.set_winner("toy_" + tag, "cpu", {"n": int(tag)},
                 cycles_per_step=1.0)
print("done")
"""


def test_concurrent_writers_lose_no_entries(tmp_path):
    """Regression: _save() rewrote the whole file from a possibly-stale
    in-memory snapshot with no locking, so two processes sharing a cache
    dir silently dropped each other's measurements."""
    import os
    import subprocess
    import sys

    import repro
    root = str(tmp_path / "shared")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(repro.__file__))
    procs = [subprocess.Popen([sys.executable, "-c", _WRITER, root, tag],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE)
             for tag in ("0", "1")]
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err.decode()
        assert b"done" in out
    merged = EvalCache(root)
    for tag in ("0", "1"):
        hits = [e for e in merged.entries("toy")
                if e["fingerprint"] == f"f{tag}"]
        assert len(hits) == 40, f"writer {tag} lost {40 - len(hits)} entries"
        assert merged.best_config(f"toy_{tag}", "cpu") == {"n": int(tag)}


# -------------------------------------------------------- sweep farm

def test_sweep_farm_two_workers_smoke(tmp_path):
    """Tier-1 end-to-end: 2-process capture, parent-side measurement
    over a shared cache, simulator-first filtering, warm rerun fully
    served from artifacts."""
    from repro.core.dse import run_sweep
    shapes = [{"S": 64, "D": 16}, {"S": 128, "D": 16}]
    cache = EvalCache(str(tmp_path / "sweep"))
    res = run_sweep("flash_attention", shapes, workers=2, top_k=6,
                    steps=2, cache=cache, calibrate=False)
    assert res.n_candidates > 2 * res.n_finalists
    assert res.n_measured <= res.n_finalists <= 6
    assert res.n_captured == res.n_candidates
    assert len(res.shapes) == 2
    for sh in res.shapes:
        assert sh.best_cycles <= sh.default_cycles
        assert sh.best_config is not None
    assert len(cache.entries("flash_attention")) == res.n_measured
    assert cache.best_config("flash_attention") is not None
    # warm rerun: traces + evals all on disk, nothing touches the device
    res2 = run_sweep("flash_attention", shapes, workers=2, top_k=6,
                     steps=2, cache=EvalCache(str(tmp_path / "sweep")),
                     calibrate=False)
    assert res2.n_measured == 0 and res2.n_captured == 0
    assert res2.n_cache_hits == res.n_measured
    assert [s.best_config for s in res2.shapes] == \
        [s.best_config for s in res.shapes]


def test_sweep_calibration_transfers(tmp_path):
    from repro.core import costmodel as cm
    from repro.core.dse import run_sweep
    cm.clear_kernel_calibration()
    try:
        res = run_sweep("flash_attention", [{"S": 64, "D": 16}], workers=0,
                        top_k=2, steps=2,
                        cache=EvalCache(str(tmp_path / "cal")),
                        calibrate=True)
    finally:
        cm.clear_kernel_calibration()
    assert res.n_calibration_runs == 1
    assert res.calibration_scale is not None and res.calibration_scale > 0
