"""End-to-end behaviour: real training runs converge, the probed train
step is non-intrusive and exact, serving decodes, dry-run machinery
lowers a small cell."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import probe, ProbeConfig
from repro.core.instrument import decode_record


@pytest.mark.slow
def test_training_loss_decreases(tmp_path):
    from repro.launch.train import train
    _, _, hist = train("tinyllama-1.1b", steps=40, batch=4, seq=64,
                       checkpoint_dir=str(tmp_path / "ck"), log_every=100)
    first = np.mean(hist[:5])
    last = np.mean(hist[-5:])
    assert last < first - 0.15, (first, last)


@pytest.mark.slow
def test_training_resume_continues(tmp_path):
    from repro.checkpoint import Checkpointer
    from repro.launch.train import train
    d = str(tmp_path / "ck")
    train("tinyllama-1.1b", steps=10, batch=2, seq=32, checkpoint_dir=d,
          log_every=100)
    assert Checkpointer(d).latest() == 10
    # resume to 14 from the stored state (exactly-once data accounting)
    _, _, hist = train("tinyllama-1.1b", steps=14, batch=2, seq=32,
                       checkpoint_dir=d, resume=True, log_every=100)
    assert len(hist) == 4


def test_serve_decodes_tokens():
    from repro.launch.serve import serve
    toks = serve("tinyllama-1.1b", batch=2, prompt_len=16, max_new=4,
                 cache_len=32)
    assert toks.shape == (2, 4)
    from repro.configs.registry import smoke_config
    assert toks.max() < smoke_config("tinyllama-1.1b").vocab_size


@pytest.mark.slow
def test_probed_production_train_step(key):
    """RealProbe on the REAL train step (optimizer included): exact vs
    oracle + identical numerics to the unprobed step."""
    from repro.configs.base import TrainConfig
    from repro.configs.registry import smoke_config
    from repro.distributed.steps import build_train_step
    from repro.models import Model
    from repro.optim import adamw

    cfg = smoke_config("mamba2-370m")
    model = Model(cfg)
    params = model.init(key)
    opt = adamw.init(params, cfg.moment_dtype)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.ones((2, 32), jnp.int32)}
    step = build_train_step(model, TrainConfig(total_steps=10,
                                               warmup_steps=1))
    pf = probe(step, ProbeConfig(max_probes=25))
    (p1, o1, m1), rec = pf(params, opt, batch)
    p0, o0, m0 = jax.jit(step)(params, opt, batch)
    assert np.allclose(float(m0["loss"]), float(m1["loss"]), rtol=1e-6)
    oc = pf.oracle(params, opt, batch)
    dec = decode_record(rec)
    for i, path in enumerate(pf.probe_paths()):
        assert int(dec["totals"][i]) == oc.totals[i], path
    rep = pf.report(rec)
    assert rep.bottleneck() is not None
    assert rep.timeline()


@pytest.mark.slow
def test_dryrun_cell_machinery_smoke():
    """lower_cell-equivalent flow on 1 device with a smoke config: the
    same builders + sharding plumbing the 512-way dry-run uses."""
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.configs.registry import smoke_config
    from repro.distributed.steps import build_train_step
    from repro.models import Model
    from repro.optim import adamw

    cfg = smoke_config("granite-3-2b")
    model = Model(cfg)
    shape = ShapeConfig("t", seq_len=64, global_batch=4, kind="train")
    ins = model.input_specs(shape)
    params_abs = model.abstract_params()
    opt_abs = jax.eval_shape(lambda p: adamw.init(p, cfg.moment_dtype),
                             params_abs)
    step = build_train_step(model, TrainConfig())
    lowered = jax.jit(step).lower(params_abs, opt_abs, ins)
    compiled = lowered.compile()
    assert compiled.cost_analysis() is not None
    from repro.launch.hlo_cost import analyze
    cost = analyze(compiled.as_text())
    assert cost["flops"] > 0
    assert cost["bytes"] > 0


def test_hlo_cost_trip_count_awareness():
    """The roofline source must multiply scan bodies by trip count."""
    from repro.launch.hlo_cost import analyze

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y.sum()

    x = jnp.ones((64, 64))
    w = jnp.ones((64, 64))
    c8 = analyze(jax.jit(f).lower(x, w).compile().as_text())

    def f1(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=1)
        return y.sum()

    c1 = analyze(jax.jit(f1).lower(x, w).compile().as_text())
    ratio = c8["flops"] / max(c1["flops"], 1)
    assert 6.0 < ratio < 10.0, ratio


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache is
    the fixed <repo>/.jax_cache."""
    import os
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cc.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert cc.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
