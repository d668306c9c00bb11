"""Mesh-aware probing: per-device overhead and skew metrics vs mesh size.

For each mesh size the bench forces an N-device host platform in a
subprocess (the dry-run isolation rule — the parent process keeps the
real backend) and runs the canonical skewed workload (a DP layer stack,
an all-reduce, and a device-index-dependent while loop) under
``mesh_probe``:

- ``span`` / ``mean_cycles`` / ``skew``: deterministic model-clock
  metrics per device — skew is the straggler signal (max−min total
  cycles of the dynamic scope across devices) and GROWS with the mesh
  because the last device loops longest;
- ``wire_B``: ring-model collective wire bytes of the program
  (mesh-size-sensitive through the cost model's collective term);
- ``state_B``: total on-device counter footprint (rows × devices);
- ``us_per_call``: wall-clock per probed step (not gated on CI).

All the model-clock metrics are gated by ``check_regression.py``
against the committed baselines.
"""
import json
import os
import subprocess
import sys

from benchmarks.common import emit

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import MeshProbeSession, ProbeConfig, mesh_probe
from repro.launch.mesh import make_mesh

D = jax.device_count()
mesh = make_mesh((D,), ("dev",))

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
    def cond(s):
        return s[1] < i + 1
    def grow(s):
        with jax.named_scope("grow"):
            return (s[0] * 1.1, s[1] + 1)
    with jax.named_scope("dynamic"):
        x, n = jax.lax.while_loop(cond, grow, (x, jnp.int32(0)))
    with jax.named_scope("head"):
        return jnp.sum(x * x) + g, n

x = jnp.arange(float(D * 8 * 4)).reshape(D * 8, 4) * 0.01
w = jnp.full((4, 4), 0.25)
cfg = ProbeConfig(inline="off_all")
mpf = mesh_probe(step, mesh, in_specs=(P("dev"), P()), out_specs=P(),
                 config=cfg)
out, state = mpf(x, w)
jax.block_until_ready(out)
rec = mpf.decode(state)
wire = sum(s.wire_bytes for s in mpf.collectives())

ref = mpf.unprobed()
jax.block_until_ready(ref(x, w))

def best_us(fn, reps=5):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x, w))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6

probed_us = best_us(lambda *a: mpf(*a)[0])
base_us = best_us(ref)

with MeshProbeSession(mpf, window_steps=4) as s:
    for _ in range(8):
        s.step(x, w)
    snap = s.snapshot()

pid = rec.paths.index("dynamic")
print(json.dumps({
    "devices": D,
    "span": int(rec.cycle.max()),
    "mean_cycles": float(rec.reduce("mean").sum()),
    "skew": int(rec.skew()[pid]),
    "session_skew": int(snap.record.skew()[pid]),
    "wire_B": int(wire),
    "state_B": int(snap.state_nbytes),
    "probed_us": probed_us,
    "base_us": base_us,
}))
"""


def _run_child(n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"            # forced host devices, never the chip
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=540)
    if out.returncode != 0:
        raise RuntimeError(f"mesh-{n_devices} child failed:\n"
                           f"{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run():
    print("# per-device probing vs mesh size (forced host devices)")
    for n in (2, 8):
        r = _run_child(n)
        overhead = (r["probed_us"] / r["base_us"] - 1) * 100 \
            if r["base_us"] else 0.0
        # session skew after 8 steps must telescope to 8x the one-shot
        # skew (deterministic model clock) — emit the check, gate the raw
        assert r["session_skew"] == 8 * r["skew"], r
        emit(f"distributed/mesh{n}", r["probed_us"],
             f"span={r['span']};mean_cycles={r['mean_cycles']:.0f};"
             f"skew={r['skew']};wire_B={r['wire_B']};"
             f"state_B={r['state_B']};overhead={overhead:.0f}%")
