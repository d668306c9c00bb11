"""End-to-end trainer (example application + the serving ground for the
RealProbe integration: ``--probe`` runs the whole loop under a streaming
``ProbeSession`` and prints periodic telemetry snapshots).

Runs on anything from 1 CPU device (smoke configs) to the production
mesh; fault-tolerance wiring (atomic async checkpoints, SIGTERM hook,
exactly-once data accounting, elastic restore) is exercised by the test
suite on small meshes.
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import TrainConfig
from repro.configs.registry import get_config, smoke_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.distributed import sharding as shd
from repro.distributed.steps import build_train_step
from repro.models.model import Model
from repro.optim import adamw


def train(arch: str = "tinyllama-1.1b", *, smoke: bool = True,
          steps: int = 20, batch: int = 8, seq: int = 128,
          mesh_shape=None, probe_targets: Optional[tuple] = None,
          probe_mesh: Optional[tuple] = None,
          checkpoint_dir: Optional[str] = None, resume: bool = False,
          tcfg: Optional[TrainConfig] = None, log_every: int = 10,
          probe_every: int = 0, autotune: bool = False,
          tune_cache: Optional[str] = None,
          status_port: Optional[int] = None):
    if autotune:
        from repro.kernels import tuning
        tuning.load_cache(cache_dir=tune_cache, verbose=True)
    cfg = smoke_config(arch) if smoke else get_config(arch)
    model = Model(cfg)
    tcfg = tcfg or TrainConfig(total_steps=steps, warmup_steps=max(steps // 10, 1),
                               checkpoint_dir=checkpoint_dir or "/tmp/repro_ckpt")

    if mesh_shape:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(*mesh_shape)
    else:
        mesh = None
    rules = shd.filter_rules(shd.TRAIN_RULES, mesh) if mesh else None

    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                    global_batch=batch, seed=tcfg.seed))
    params = model.init(jax.random.PRNGKey(tcfg.seed))
    opt_state = adamw.init(params, cfg.moment_dtype)

    ckpt = None
    start_step = 0
    if checkpoint_dir:
        ckpt = Checkpointer(checkpoint_dir, keep=tcfg.keep_checkpoints,
                            async_save=tcfg.async_checkpoint)
        last = ckpt.latest()
        if resume and last is not None:
            (params, opt_state), extra = ckpt.restore(
                last, (params, opt_state))
            start_step = int(extra["step"])
            pipe.state.step = int(extra["data_step"])

    step_fn = build_train_step(model, tcfg)
    plane = None
    if status_port is not None:
        from repro.telemetry import ControlPlane
        plane = ControlPlane(status_port).start()
    bus = plane.bus if plane is not None else None
    session = None
    mesh_session = False
    if probe_targets is not None and probe_mesh:
        # mesh-aware probing: data-parallel per-shard step under a probed
        # shard_map — one cycle-counter row per device (docs/distributed.md)
        from jax.sharding import PartitionSpec as P
        from repro.core import MeshProbeSession, ProbeConfig, mesh_probe
        from repro.distributed.steps import build_dp_train_step
        from repro.launch.mesh import make_mesh, probe_axis_names
        axes = probe_axis_names(probe_mesh)
        pmesh = make_mesh(probe_mesh, axes)
        dp_step = build_dp_train_step(
            model, tcfg, axis=axes[0] if len(axes) == 1 else axes)
        session = MeshProbeSession(
            mesh_probe(dp_step, pmesh,
                       in_specs=(P(), P(), P(axes)),
                       out_specs=(P(), P(), P()),
                       config=ProbeConfig(targets=tuple(probe_targets),
                                          max_probes=16)),
            window_steps=max(probe_every or log_every, 1),
            bus=bus, source="train/mesh")
        run_jitted = session.step
        mesh_session = True
    elif probe_targets is not None:
        from repro.core import ProbeConfig, ProbeSession
        session = ProbeSession(
            step_fn, ProbeConfig(targets=tuple(probe_targets),
                                 offload=1.0, max_probes=16),
            window_steps=max(probe_every or log_every, 1),
            bus=bus, source="train/step")
        run_jitted = session.step
    else:
        run_jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    def run_step(params, opt_state, batch_np):
        b = {k: jnp.asarray(v) for k, v in batch_np.items()}
        return run_jitted(params, opt_state, b)

    ctx = shd.axis_rules(rules, mesh)
    history = []
    mesh_ctx = jax.set_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()
    with mesh_ctx, ctx:
        t0 = time.time()
        for step in range(start_step, steps):
            batch_np = pipe.batch_at(step)
            pipe.state.step = step + 1
            params, opt_state, metrics = run_step(params, opt_state,
                                                  batch_np)
            loss = float(metrics["loss"])
            history.append(loss)
            if step % log_every == 0 or step == steps - 1:
                dt = time.time() - t0
                print(f"step {step:5d} loss {loss:8.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"({dt:.1f}s)", flush=True)
            if session is not None and \
                    session.steps % (probe_every or log_every) == 0:
                snap = session.snapshot()
                print(f"[probe] {snap.steps} steps, span={snap.span} "
                      f"cycles, state={snap.state_nbytes}B", flush=True)
                print(snap.table(), flush=True)
            if ckpt and (step + 1) % tcfg.checkpoint_every == 0:
                ckpt.save(step + 1, (params, opt_state),
                          extra={"step": step + 1,
                                 "data_step": pipe.state.step})
        if ckpt:
            ckpt.save(steps, (params, opt_state),
                      extra={"step": steps, "data_step": pipe.state.step})
            ckpt.wait()
    if session is not None:
        final = session.close()
        if final is not None:
            print("\n# final streaming probe telemetry")
            print(final.table())
            if mesh_session:
                print("\n# per-device cycle records")
                print(final.device_table())
                print("\n# straggler heat view")
                print(final.heat())
            else:
                print(final.bump_chart())
    if plane is not None:
        plane.finish()
    return params, opt_state, history


def main():
    from repro.launch.mesh import parse_mesh_arg
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full config (needs real hardware)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="profile the train step with a live ProbeSession")
    ap.add_argument("--mesh", default=None,
                    help="probe per device on an N-way mesh, e.g. '8' or "
                         "'2x4' (with --probe; batch must divide the mesh "
                         "size). Force devices on CPU via XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8")
    ap.add_argument("--probe-targets", default="",
                    help="comma-separated probe subtree roots")
    ap.add_argument("--probe-every", type=int, default=0,
                    help="snapshot period in steps (default: log-every)")
    ap.add_argument("--autotune", action="store_true",
                    help="load DSE-tuned kernel configs from the eval cache")
    ap.add_argument("--tune-cache", default=None,
                    help="eval cache dir (default .repro_cache/dse)")
    ap.add_argument("--status-port", type=int, default=None,
                    help="expose live telemetry over HTTP on this port "
                         "(0 = OS-assigned; prints the bound URL)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    train(args.arch, smoke=not args.full, steps=args.steps,
          batch=args.batch, seq=args.seq,
          probe_targets=(tuple(args.probe_targets.split(","))
                         if args.probe else None),
          probe_mesh=parse_mesh_arg(args.mesh),
          probe_every=args.probe_every,
          checkpoint_dir=args.checkpoint_dir, resume=args.resume,
          autotune=args.autotune, tune_cache=args.tune_cache,
          status_port=args.status_port)


if __name__ == "__main__":
    main()
