"""Mesh-aware probing invariants.

Fast tests run in-process (1-device meshes and pure decoding/reduction
logic need no multi-device backend). The end-to-end 8-device
guarantees — per-device records integer-equal to per-shard oracle
replays, bit-identical outputs under shard_map, session aggregation
exact vs one-shot, deterministic skew — run in a subprocess that forces
an 8-device host platform before jax initializes (the dry-run isolation
rule, like tests/test_distributed.py)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CycleRecord, ProbeConfig, StreamAggregator, mesh_probe
from repro.launch.mesh import make_mesh, parse_mesh_arg, probe_axis_names

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ fast part

def test_make_mesh_raises_with_factorizations():
    dc = jax.device_count()
    bad = dc * 2 + 1                       # never divides the device count
    with pytest.raises(ValueError) as e:
        make_mesh((bad,), ("dev",))
    msg = str(e.value)
    assert str(bad) in msg and "factorization" in msg and f"({dc},)" in msg
    with pytest.raises(ValueError):
        make_mesh((1, bad), ("a", "b"))
    with pytest.raises(ValueError):        # shape/axes arity mismatch
        make_mesh((1, 1), ("a",))
    assert make_mesh((1,), ("dev",)).devices.size == 1


def test_parse_mesh_arg():
    assert parse_mesh_arg(None) == ()
    assert parse_mesh_arg("") == ()
    assert parse_mesh_arg("8") == (8,)
    assert parse_mesh_arg("2x4") == (2, 4)
    assert parse_mesh_arg("2,4") == (2, 4)
    with pytest.raises(ValueError):
        parse_mesh_arg("2xbanana")
    assert probe_axis_names((8,)) == ("dev",)
    assert probe_axis_names((2, 4)) == ("dev0", "dev1")


def _record(totals, mesh_shape=(4,), paths=("a", "b")):
    totals = np.asarray(totals, np.int64)
    D, n = totals.shape
    return CycleRecord(
        mesh_axes=tuple(f"d{i}" for i in range(len(mesh_shape))),
        mesh_shape=tuple(mesh_shape), paths=tuple(paths),
        cycle=totals.sum(axis=1), starts=np.zeros_like(totals),
        ends=totals, totals=totals,
        calls=np.ones_like(totals),
        ring=np.zeros((D, n, 2, 2), np.int64))


def test_cycle_record_reductions_and_skew():
    rec = _record([[10, 1], [20, 1], [30, 1], [40, 5]])
    assert np.array_equal(rec.reduce("max"), [40, 5])
    assert np.array_equal(rec.reduce("mean"), [25.0, 2.0])
    assert rec.reduce("per-device").shape == (4, 2)
    assert np.array_equal(rec.skew(), [30, 4])
    assert rec.straggler() == (3, "a")
    assert rec.coords(3) == (3,)
    assert rec.row("a", device=2) == 30
    dev = rec.device(1)
    assert dev["cycle"] == 21 and list(dev["totals"]) == [20, 1]
    with pytest.raises(ValueError):
        rec.reduce("median")


def test_zero_probe_record_renders_without_crash():
    """Unknown targets select zero probes — every view must degrade
    gracefully (the single-device invariant, kept under a mesh)."""
    from repro.core.report import (mesh_device_table, mesh_heat,
                                   mesh_session_table)
    rec = _record(np.zeros((4, 0), np.int64), paths=())
    assert rec.straggler() == (0, "")
    assert rec.skew().shape == (0,)
    assert mesh_heat(rec) == "(no probes selected)"
    assert "mesh" in mesh_device_table(rec)

    class Snap:
        record, steps, state_nbytes = rec, 3, 0
    assert "mesh session" in mesh_session_table(Snap())


def test_stream_aggregator_cross_device_modes():
    # device-major rows: (device, probe) for D=3, n=2
    agg = StreamAggregator(6)
    for row, total in enumerate([5, 1, 7, 2, 9, 6]):
        agg.add(row, np.array([total]))
    assert np.array_equal(agg.reduce("max", n_devices=3), [9, 6])
    assert np.array_equal(agg.reduce("mean", n_devices=3), [7.0, 3.0])
    assert agg.reduce("per-device", n_devices=3).shape == (3, 2)
    assert np.array_equal(agg.skew(3), [4, 5])
    with pytest.raises(ValueError):
        agg.reduce("min", n_devices=3)


def _workload():
    def step(x, w):
        def body(c, _):
            with jax.named_scope("layer"):
                c = jnp.tanh(c @ w) + c
            return c, None
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(body, x, None, length=3)
        with jax.named_scope("sync"):
            g = jax.lax.pmean(jnp.sum(x * x), "dev")
        with jax.named_scope("head"):
            return jnp.sum(x * x) + g
    return step


def test_mesh_probe_single_device_mesh(tiny_mesh):
    """The full pipeline on a 1-device mesh: exact oracle equality,
    bit-identical outputs, collective attribution, report rendering."""
    mesh = tiny_mesh
    step = _workload()
    x = jnp.arange(16.0).reshape(4, 4) * 0.1
    w = jnp.full((4, 4), 0.25)
    from jax.sharding import PartitionSpec as P
    mpf = mesh_probe(step, mesh, in_specs=(P("dev"), P()), out_specs=P(),
                     config=ProbeConfig(inline="off_all"))
    out, state = mpf(x, w)
    rec = mpf.decode(state)
    assert rec.n_devices == 1 and rec.totals.shape[0] == 1
    # bit-identity vs the uninstrumented shard_map
    ref = mpf.unprobed()(x, w)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    # oracle equality (the ILA check), device 0
    oc = mpf.oracle(x, w, device=0)
    assert list(rec.device(0)["totals"]) == oc.totals
    assert list(rec.device(0)["calls"]) == list(oc.calls)
    assert rec.device(0)["cycle"] == oc.cycle
    # collective attribution: the pmean lives under "sync"
    sites = mpf.collectives()
    assert any(s.path == "sync" and s.kind == "all-reduce" for s in sites)
    rep = mpf.report(state)
    assert "sync" in rep.comm_table()
    assert "dev0" in rep.device_table()
    assert "skew" in rep.device_table()
    assert "heat" in rep.heat("layers")
    # stateful threading accumulates (session substrate)
    st = mpf.init_state()
    for _ in range(3):
        _, st = mpf.stateful_call(st, x, w)
    rec3 = mpf.decode(st)
    assert np.array_equal(rec3.totals, 3 * rec.totals)


def test_mesh_probe_rejects_wallclock(tiny_mesh):
    with pytest.raises(ValueError):
        mesh_probe(lambda x: x, tiny_mesh, None, None,
                   ProbeConfig(cycle_source="wallclock"))


def test_shard_oracle_resolves_axis_index():
    """ShardOracle replays a device-dependent loop exactly for each
    mesh coordinate — without any multi-device backend."""
    from repro.core.hierarchy import extract
    from repro.core.instrument import ProbeAssignment
    from repro.core.meshprobe import ShardOracle
    from repro.core.meshprobe import extend_axis_env

    def fn(x):
        i = jax.lax.axis_index("dev")
        def cond(s):
            return s[1] < i + 1
        def body(s):
            with jax.named_scope("grow"):
                return (s[0] * 1.5, s[1] + 1)
        with jax.named_scope("dynamic"):
            x, n = jax.lax.while_loop(cond, body, (x, jnp.int32(0)))
        return jnp.sum(x), n

    with extend_axis_env({"dev": 4}):
        closed = jax.make_jaxpr(fn)(jnp.ones((4,)))
    h = extract(closed)
    asg = ProbeAssignment(paths=("dynamic",), depth=4, spill=(False,))
    totals = []
    for d in range(4):
        oc = ShardOracle(h, asg, {"dev": d}).run(closed,
                                                 [np.ones(4, np.float32)])
        assert oc.calls[0] == 1
        totals.append(oc.totals[0])
    # trip count == device index + 1 -> strictly increasing cycle totals
    assert totals == sorted(totals) and len(set(totals)) == 4


# ------------------------------------------------- 8-device subprocess

def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"            # forced host devices, never the chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_mesh_probe_8_devices_end_to_end():
    """Acceptance criteria on a forced 8-device mesh, one subprocess:
    (1) per-device cycle records integer-equal to per-shard oracle
    replays on every device, (2) bit-identical model outputs with
    probes on/off under shard_map, (3) session reduction modes exact vs
    one-shot, (4) deterministic nonzero skew from a device-dependent
    loop, (5) per-device + heat report views render."""
    code = """
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P
from repro.core import MeshProbeSession, ProbeConfig, mesh_probe
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("dev",))

def step(x, w):
    def body(c, _):
        with jax.named_scope("layer"):
            c = jnp.tanh(c @ w) + c
        return c, None
    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(body, x, None, length=4)
    with jax.named_scope("sync"):
        g = jax.lax.pmean(jnp.sum(x * x), "dev")
    i = jax.lax.axis_index("dev")
    def cond(s): return s[1] < i + 1
    def grow(s):
        with jax.named_scope("grow"):
            return (s[0] * 1.1, s[1] + 1)
    with jax.named_scope("dynamic"):
        x, n = jax.lax.while_loop(cond, grow, (x, jnp.int32(0)))
    with jax.named_scope("head"):
        return jnp.sum(x * x) + g, n

x = jnp.arange(64.0).reshape(16, 4) * 0.01
w = jnp.full((4, 4), 0.25)
cfg = ProbeConfig(inline="off_all")
fn_traces = [0]
def counted_step(x, w):
    fn_traces[0] += 1
    return step(x, w)
mpf = mesh_probe(step, mesh, in_specs=(P("dev"), P()), out_specs=P(),
                 config=cfg)
(out, n), state = mpf(x, w)
rec = mpf.decode(state)

# (1) oracle equality for EVERY device
oracle_ok = True
for d in range(8):
    oc = mpf.oracle(x, w, device=d)
    dev = rec.device(d)
    oracle_ok &= (list(dev["totals"]) == oc.totals and
                  list(dev["calls"]) == list(oc.calls) and
                  list(dev["starts"]) == oc.starts and
                  list(dev["ends"]) == oc.ends and
                  dev["cycle"] == oc.cycle)

# (2) bit identity probes on/off
ref_out, ref_n = mpf.unprobed()(x, w)
bit_ok = (np.array_equal(np.asarray(out), np.asarray(ref_out)) and
          np.array_equal(np.asarray(n), np.asarray(ref_n)))

# (3) session: K steps, totals and reductions exact vs one-shot
K = 5
with MeshProbeSession(mesh_probe(counted_step, mesh, (P("dev"), P()), P(),
                                 cfg), window_steps=2) as s:
    sizes = []
    for _ in range(K):
        s.step(x, w)
        sizes.append(getattr(s.mpf._jitted_stateful, "_cache_size",
                             lambda: None)())
    snap = s.snapshot()
    # zero retraces: the user function is traced ONCE for the whole
    # session, and the executable cache is steady from step 2 on (the
    # C++ fastpath may add one signature entry without re-lowering)
    steady = (sizes[0] is None or len(set(sizes[1:])) == 1)
    traces = fn_traces[0] if steady else -1
sess_ok = (np.array_equal(snap.record.totals, K * rec.totals) and
           np.array_equal(snap.record.reduce("max"), K * rec.reduce("max")) and
           np.array_equal(snap.record.skew(), K * rec.skew()) and
           np.array_equal(snap.stats.reduce("per-device", 8),
                          snap.record.totals) and
           np.array_equal(snap.stats.skew(8), snap.record.skew()))

# (4) deterministic skew from the device-dependent while loop
pid = rec.paths.index("dynamic")
skew = int(rec.skew()[pid])
per_dev = rec.totals[:, pid]
mono = bool(np.all(np.diff(per_dev) > 0))

# (5) report views render
rep = mpf.report(state)
views_ok = ("dev7" in rep.device_table() and "heat" in rep.heat() and
            "sync" in rep.comm_table() and "mesh session" in snap.table())

print(json.dumps({"oracle_ok": bool(oracle_ok), "bit_ok": bool(bit_ok),
                  "sess_ok": bool(sess_ok), "skew": skew, "mono": mono,
                  "views_ok": bool(views_ok), "traces": traces}))
"""
    out = json.loads(run_sub(code).strip().splitlines()[-1])
    assert out["oracle_ok"], out
    assert out["bit_ok"], out
    assert out["sess_ok"], out
    assert out["skew"] > 0 and out["mono"], out
    assert out["views_ok"], out
    assert out["traces"] in (None, 1), out


@pytest.mark.slow
def test_dp_train_step_probed_on_mesh_matches_unprobed():
    """The data-parallel train step builder is probeable per device and
    non-intrusive: params after a probed step are bit-identical to the
    unprobed shard_map step, and per-device grad_exchange cycles carry
    the collective term."""
    code = """
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import PartitionSpec as P
from repro.configs.registry import smoke_config
from repro.configs.base import TrainConfig
from repro.core import ProbeConfig, mesh_probe
from repro.distributed.steps import build_dp_train_step
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.optim import adamw

cfg = smoke_config("tinyllama-1.1b").replace(compute_dtype="float32")
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = adamw.init(params, cfg.moment_dtype)
B, S = 8, 32
k = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(k, (B, S), 0, cfg.vocab_size)}
mesh = make_mesh((8,), ("dev",))
step = build_dp_train_step(model, TrainConfig(total_steps=10,
                                              warmup_steps=1), axis="dev")
mpf = mesh_probe(step, mesh,
                 in_specs=(P(), P(), P("dev")), out_specs=(P(), P(), P()),
                 config=ProbeConfig(targets=("grad_exchange", "optimizer")))
(p1, o1, m1), state = mpf(params, opt, batch)
p2, o2, m2 = mpf.unprobed()(params, opt, batch)
bit_ok = all(np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(jax.tree_util.tree_leaves((p1, o1, m1)),
                             jax.tree_util.tree_leaves((p2, o2, m2))))
rec = mpf.decode(state)
ge = rec.totals[:, rec.paths.index("grad_exchange")]
comm = [s for s in mpf.collectives() if s.path.startswith("grad_exchange")]
print(json.dumps({"bit_ok": bool(bit_ok),
                  "ge_min": int(ge.min()), "n_comm": len(comm),
                  "wire": sum(s.wire_bytes for s in comm),
                  "loss": float(m1["loss"])}))
"""
    out = json.loads(run_sub(code).strip().splitlines()[-1])
    assert out["bit_ok"], out
    assert out["ge_min"] > 0, out          # exchange cycles recorded/device
    assert out["n_comm"] > 0 and out["wire"] > 0, out
    assert np.isfinite(out["loss"]), out
