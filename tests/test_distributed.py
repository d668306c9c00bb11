"""Multi-device behavior on a small CPU mesh (subprocesses set
XLA_FLAGS=8 devices before jax init — the main test process stays at the
real device count, per the dry-run isolation rule)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow   # subprocess-spawned 8-device meshes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"            # forced host devices, never the chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import smoke_config
from repro.configs.base import TrainConfig, ShapeConfig
from repro.models.model import Model
from repro.distributed import sharding as shd
from repro.distributed.steps import build_train_step, build_decode_step
from repro.launch.mesh import make_mesh
from repro.optim import adamw
"""


def test_sharded_train_step_matches_single_device():
    """The distributed train step must compute the same loss as the
    single-device one (GSPMD is an implementation detail)."""
    code = PREAMBLE + """
cfg = smoke_config("tinyllama-1.1b").replace(compute_dtype="float32")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
B, S = 8, 64
k = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
tcfg = TrainConfig(total_steps=10, warmup_steps=1)
step = build_train_step(model, tcfg)
opt = adamw.init(params, cfg.moment_dtype)

# single device
p1, o1, m1 = jax.jit(step)(params, opt, batch)

# 2x4 mesh
mesh = make_mesh((2, 4), ("data", "model"))
rules = shd.filter_rules(shd.TRAIN_RULES, mesh)
pspecs = shd.schema_pspecs(model.schema(), rules, mesh)
psh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                             is_leaf=lambda x: isinstance(x, P))
bsh = {kk: NamedSharding(mesh, P("data")) for kk in batch}
osh = adamw.AdamWState(step=NamedSharding(mesh, P()), mu=psh, nu=psh)
with jax.set_mesh(mesh), shd.axis_rules(rules, mesh):
    p2, o2, m2 = jax.jit(step, in_shardings=(psh, osh, bsh))(params, opt, batch)
print(json.dumps({"l1": float(m1["loss"]), "l2": float(m2["loss"])}))
"""
    out = json.loads(run_sub(code).strip().splitlines()[-1])
    assert abs(out["l1"] - out["l2"]) / abs(out["l1"]) < 2e-3, out


def test_sharded_decode_step_matches_single_device():
    code = PREAMBLE + """
cfg = smoke_config("granite-3-2b").replace(compute_dtype="float32",
                                           kv_cache_dtype="float32")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
B = 8
shape = ShapeConfig("t", seq_len=64, global_batch=B, kind="decode")
cache = model.init_cache(shape)
batch = {"tokens": jnp.zeros((B, 1), jnp.int32), "pos": jnp.int32(5)}
step = build_decode_step(model)
l1, _, _ = jax.jit(step)(params, cache, batch)
mesh = make_mesh((2, 4), ("data", "model"))
rules = shd.filter_rules(shd.SERVE_RULES, mesh)
with jax.set_mesh(mesh), shd.axis_rules(rules, mesh):
    l2, _, _ = jax.jit(step)(params, model.init_cache(shape), batch)
V = cfg.vocab_size   # pad columns are -inf by design
l1, l2 = l1[:, :V], l2[:, :V]
err = float(jnp.abs(l1 - l2).max() / (jnp.abs(l1).max() + 1e-9))
print(json.dumps({"err": err}))
"""
    out = json.loads(run_sub(code).strip().splitlines()[-1])
    assert out["err"] < 2e-3, out


def test_moe_shard_map_matches_local():
    code = PREAMBLE + """
cfg = smoke_config("granite-moe-1b-a400m").replace(compute_dtype="float32")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
B, S = 8, 32
k = jax.random.PRNGKey(2)
batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
l1, _ = jax.jit(model.loss_fn)(params, batch)
mesh = make_mesh((2, 4), ("data", "model"))
rules = shd.filter_rules(shd.TRAIN_RULES, mesh)
with jax.set_mesh(mesh), shd.axis_rules(rules, mesh):
    l2, _ = jax.jit(model.loss_fn)(params, batch)
print(json.dumps({"l1": float(l1), "l2": float(l2)}))
"""
    out = json.loads(run_sub(code).strip().splitlines()[-1])
    # routing is data-parallel-local: sort order within shard differs, but
    # at high capacity nothing drops -> losses must match closely
    assert abs(out["l1"] - out["l2"]) / abs(out["l1"]) < 5e-3, out


def test_int8_ef_grad_compression_pod_axis():
    """Compressed cross-pod exchange: loss finite, params update, and
    the result stays close to the uncompressed step (a partial-manual
    shard_map over the pod axis)."""
    code = PREAMBLE + """
from repro.optim import compression
cfg = smoke_config("tinyllama-1.1b").replace(compute_dtype="float32")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
B, S = 8, 32
k = jax.random.PRNGKey(3)
batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
tcfg0 = TrainConfig(total_steps=10, warmup_steps=1)
tcfg1 = TrainConfig(total_steps=10, warmup_steps=1,
                    grad_compression="int8_ef")
opt = adamw.init(params, cfg.moment_dtype)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = shd.filter_rules(shd.TRAIN_RULES, mesh)
res = compression.init_residual(params)
with jax.set_mesh(mesh), shd.axis_rules(rules, mesh):
    step0 = build_train_step(model, tcfg0)
    p0, _, m0 = jax.jit(step0)(params, opt, batch)
    step1 = build_train_step(model, tcfg1)
    p1, _, r1, m1 = jax.jit(step1)(params, opt, batch, res)
d0 = jax.tree_util.tree_leaves(p0)
d1 = jax.tree_util.tree_leaves(p1)
# one AdamW step moves params by <= ~lr; int8-EF quantization error is
# bounded by the same scale (deadzoned small grads recover via the
# residual over subsequent steps)
abs_diff = max(float(jnp.abs(a - b).max()) for a, b in zip(d0, d1))
print(json.dumps({"l0": float(m0["loss"]), "l1": float(m1["loss"]),
                  "abs_diff": abs_diff}))
"""
    out = json.loads(run_sub(code).strip().splitlines()[-1])
    assert abs(out["l0"] - out["l1"]) / abs(out["l0"]) < 2e-3
    # bounded by ~2 optimizer steps' worth of movement (lr=3e-4)
    assert out["abs_diff"] < 2 * 3e-4 + 1e-6, out


def test_microbatched_grads_match_full_batch():
    code = PREAMBLE + """
cfg = smoke_config("tinyllama-1.1b").replace(compute_dtype="float32")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
B, S = 8, 32
k = jax.random.PRNGKey(4)
batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
opt = adamw.init(params, cfg.moment_dtype)
p1, _, m1 = jax.jit(build_train_step(model, TrainConfig()))(params, opt, batch)
p4, _, m4 = jax.jit(build_train_step(model, TrainConfig(microbatches=4)))(
    params, opt, batch)
rel = max(float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-9))
          for a, b in zip(jax.tree_util.tree_leaves(p1),
                          jax.tree_util.tree_leaves(p4)))
print(json.dumps({"rel": rel, "l1": float(m1["loss"]), "l4": float(m4["loss"])}))
"""
    out = json.loads(run_sub(code).strip().splitlines()[-1])
    assert abs(out["l1"] - out["l4"]) / abs(out["l1"]) < 1e-3
    assert out["rel"] < 5e-3, out
