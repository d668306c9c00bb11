"""Serving entry point, routed through the continuous-batching engine.

Engine-compatible models (attention family, token inputs) serve through
``repro.engine.InferenceEngine``: each batch row becomes one request,
decode runs at a pre-traced batch bucket over the paged KV pool, and
``--profile`` attributes model-clock cycles to prefill / cache / decode
per request (docs/serving.md). Outputs are bit-identical to the legacy
lock-step loop (asserted in tests/test_engine.py).

The legacy loop remains for frontend/SSM/hybrid models and for
``--mesh`` per-device probing, where ``--profile`` runs the decode step
under a live ``ProbeSession`` with streaming telemetry.
"""
from __future__ import annotations

import argparse
import math
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig
from repro.configs.registry import get_config, smoke_config
from repro.distributed.steps import build_decode_step, build_prefill_step
from repro.models.frontends import synth_frontend_batch
from repro.models.model import Model


def _mesh_decode_session(model, shape, mesh_shape, frontend: bool,
                         targets, max_probes, window_steps, bus=None):
    """Mesh-probed decode: batch (and every cache leaf's batch dim)
    sharded over the probing mesh, so the live session records one
    cycle-counter row per device (docs/distributed.md)."""
    from jax.sharding import PartitionSpec as P
    from repro.core import MeshProbeSession, ProbeConfig, mesh_probe
    from repro.launch.mesh import make_mesh, probe_axis_names
    axes = probe_axis_names(mesh_shape)
    pmesh = make_mesh(mesh_shape, axes)
    cspecs, caxes = model.cache_specs(shape)
    cache_spec = {k: P(*[axes if a == "batch" else None
                         for a in caxes[k]]) for k in cspecs}
    batch_spec = ({"embeds": P(axes)} if frontend else
                  {"tokens": P(axes)})
    batch_spec["pos"] = P()
    return MeshProbeSession(
        mesh_probe(build_decode_step(model), pmesh,
                   in_specs=(P(), cache_spec, batch_spec),
                   out_specs=(P(axes), cache_spec, P(axes)),
                   config=ProbeConfig(targets=targets,
                                      max_probes=max_probes)),
        window_steps=window_steps, bus=bus, source="serve/mesh")


def engine_config(batch: int, prompt_len: int, max_new: int, **knobs):
    """The ``EngineConfig`` that ``serve`` runs ``batch`` requests of
    ``prompt_len`` + ``max_new`` tokens with: 16-token pages, a page
    table one request wide, a pool for the whole batch, and decode
    buckets (1, batch). ``knobs`` are the remaining config fields."""
    from repro.engine import EngineConfig
    page = 16
    max_pages = max(1, math.ceil((prompt_len + max_new - 1) / page))
    return EngineConfig(page_size=page, pool_pages=batch * max_pages + 2,
                        max_pages=max_pages,
                        buckets=(1, batch) if batch > 1 else (1,), **knobs)


def _engine_serve(model, params, key, *, batch: int, prompt_len: int,
                  max_new: int, profile: bool,
                  profile_targets: Tuple[str, ...],
                  profile_max_probes: int, engine_kernel: bool,
                  prefill_chunk: int = 0,
                  donate: Optional[bool] = None, bus=None):
    """Serve ``batch`` random prompts through the continuous-batching
    engine (one request per row, decode bucketed at the batch size)."""
    from repro.engine import InferenceEngine
    cfg = model.cfg
    eng = InferenceEngine(model, params, engine_config(
        batch, prompt_len, max_new, use_kernel=engine_kernel,
        probe=profile, probe_targets=profile_targets,
        probe_max_probes=profile_max_probes,
        prefill_chunk_pages=prefill_chunk, donate=donate), bus=bus)
    tokens = jax.random.randint(key, (batch, prompt_len), 0,
                                cfg.vocab_size)
    prompts = np.asarray(tokens)
    t0 = time.time()
    for b in range(batch):
        eng.submit(prompts[b].tolist(), max_new)
    done = eng.run()
    t_serve = time.time() - t0
    toks = np.array([r.out_tokens for r in done], np.int32)
    st = eng.stats()
    print(f"engine: {batch} requests x {max_new} tokens in "
          f"{t_serve * 1e3:.1f} ms (pages peak {st['pages_peak']}, "
          f"retraces {st['retraces']})")
    if profile:
        print("\n# per-phase cycle attribution")
        print(eng.phase_table())
        if prefill_chunk:
            print("\n# per-chunk-shape prefill bill")
            print(eng.chunk_table())
        print("\n# per-request phase bill")
        print(eng.request_table(done))
    eng.drain()
    eng.close()
    return toks


def serve(arch: str = "tinyllama-1.1b", *, smoke: bool = True,
          batch: int = 4, prompt_len: int = 32, max_new: int = 16,
          cache_len: int = 128, profile: bool = False,
          profile_targets: Tuple[str, ...] = ("",),
          profile_every: int = 8, profile_max_probes: int = 16,
          profile_mesh: Tuple[int, ...] = (),
          autotune: bool = False, tune_cache: Optional[str] = None,
          engine: Optional[bool] = None, engine_kernel: bool = False,
          prefill_chunk: int = 0, donate: Optional[bool] = None,
          status_port: Optional[int] = None):
    if autotune:
        from repro.kernels import tuning
        tuning.load_cache(cache_dir=tune_cache, verbose=True)
    cfg = smoke_config(arch) if smoke else get_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)

    plane = None
    if status_port is not None:
        from repro.telemetry import ControlPlane
        plane = ControlPlane(status_port).start()
    bus = plane.bus if plane is not None else None

    if engine is None:
        from repro.engine import engine_compatible
        engine = engine_compatible(cfg) and not profile_mesh
    if engine:
        try:
            return _engine_serve(
                model, params, key, batch=batch, prompt_len=prompt_len,
                max_new=max_new, profile=profile,
                profile_targets=profile_targets,
                profile_max_probes=profile_max_probes,
                engine_kernel=engine_kernel,
                prefill_chunk=prefill_chunk, donate=donate, bus=bus)
        finally:
            if plane is not None:
                plane.finish()

    prefill = jax.jit(build_prefill_step(
        model, ShapeConfig("pf", cache_len, batch, "prefill")))
    profile_every = max(profile_every, 1)
    session = None
    mesh_session = False
    if profile and profile_mesh:
        session = _mesh_decode_session(
            model, ShapeConfig("pf", cache_len, batch, "decode"),
            profile_mesh, cfg.frontend != "none", profile_targets,
            profile_max_probes, max(profile_every, 1), bus=bus)
        decode = session.step
        mesh_session = True
    elif profile:
        from repro.core import ProbeConfig, ProbeSession
        session = ProbeSession(
            build_decode_step(model),
            ProbeConfig(targets=profile_targets, offload=1.0,
                        max_probes=profile_max_probes),
            window_steps=max(profile_every, 1),
            bus=bus, source="serve/decode")
        decode = session.step
    else:
        decode = jax.jit(build_decode_step(model), donate_argnums=(1,))

    if cfg.frontend != "none":
        fb = synth_frontend_batch(cfg, batch, prompt_len, jnp.bfloat16, key)
        pbatch = dict(fb)
    else:
        pbatch = {"tokens": jax.random.randint(key, (batch, prompt_len), 0,
                                               cfg.vocab_size)}
    t0 = time.time()
    logits, cache = prefill(params, pbatch)
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t_prefill = time.time() - t0

    out_tokens = [np.asarray(next_tok)]
    t0 = time.time()
    for i in range(max_new - 1):
        pos = jnp.int32(prompt_len + i)
        if cfg.frontend != "none":
            fb1 = synth_frontend_batch(cfg, batch, 1, jnp.bfloat16,
                                       jax.random.fold_in(key, i))
            dbatch = {"embeds": fb1["embeds"], "pos": pos}
        else:
            dbatch = {"tokens": next_tok[:, None], "pos": pos}
        logits, cache, next_tok = decode(params, cache, dbatch)
        out_tokens.append(np.asarray(next_tok))
        if session is not None and session.steps % profile_every == 0:
            snap = session.snapshot()
            if mesh_session:
                d, p = snap.record.straggler()
                print(f"[probe] decode step {session.steps:4d}: "
                      f"span(max)={snap.span} cycles over "
                      f"{snap.record.n_devices} devices, "
                      f"straggler=dev{d}:{p} "
                      f"(skew {int(snap.record.skew().max(initial=0))})",
                      flush=True)
            else:
                hot = snap.bottleneck()
                hot_s = (f"{hot.path} (ema {hot.ema:.1f} cyc/call)"
                         if hot else "-")
                print(f"[probe] decode step {session.steps:4d}: "
                      f"span={snap.span} cycles, "
                      f"state={snap.state_nbytes}B, "
                      f"hot={hot_s}", flush=True)
    t_decode = time.time() - t0
    toks = np.stack(out_tokens, axis=1)
    print(f"prefill {prompt_len} tokens x{batch}: {t_prefill * 1e3:.1f} ms; "
          f"decode {max_new} steps: {t_decode * 1e3:.1f} ms "
          f"({t_decode / max(max_new - 1, 1) * 1e3:.2f} ms/tok)")
    if session is not None:
        final = session.close()
        if final is not None:
            print("\n# streaming probe telemetry (decode loop)")
            print(final.table())
            if mesh_session:
                print("\n# per-device cycle records")
                print(final.device_table())
                print("\n# straggler heat view")
                print(final.heat())
            else:
                print("\n# bottleneck drift across windows")
                print(final.bump_chart())
    if plane is not None:
        plane.finish()
    return toks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--full", action="store_true",
                    help="full config (needs real hardware)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--profile", action="store_true",
                    help="run the decode loop under a live ProbeSession")
    ap.add_argument("--mesh", default=None,
                    help="profile per device on an N-way mesh, e.g. '8' "
                         "(with --profile; batch must divide the mesh size)")
    ap.add_argument("--profile-targets", default="",
                    help="comma-separated probe subtree roots")
    ap.add_argument("--profile-every", type=int, default=8)
    ap.add_argument("--autotune", action="store_true",
                    help="load DSE-tuned kernel configs from the eval cache")
    ap.add_argument("--tune-cache", default=None,
                    help="eval cache dir (default .repro_cache/dse)")
    ap.add_argument("--no-engine", action="store_true",
                    help="force the legacy lock-step loop instead of the "
                         "continuous-batching engine")
    ap.add_argument("--engine-kernel", action="store_true",
                    help="decode through the paged_attention Pallas kernel")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill chunk quantum in pages (0 = whole-prompt "
                         "prefill; >0 interleaves prefill chunks with "
                         "decode rounds, killing head-of-line blocking)")
    ap.add_argument("--donate", action="store_true", default=None,
                    help="donate the paged KV pool to the cache/decode "
                         "steps (in-place pool updates; default: auto "
                         "on accelerators, off under --profile)")
    ap.add_argument("--status-port", type=int, default=None,
                    help="expose live telemetry over HTTP on this port "
                         "(0 = OS-assigned; prints the bound URL)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import parse_mesh_arg
    enable_compile_cache()
    toks = serve(args.arch, smoke=not args.full, batch=args.batch,
                 prompt_len=args.prompt_len,
                 max_new=args.max_new, profile=args.profile,
                 profile_targets=tuple(args.profile_targets.split(",")),
                 profile_every=args.profile_every,
                 profile_mesh=parse_mesh_arg(args.mesh),
                 autotune=args.autotune, tune_cache=args.tune_cache,
                 engine=False if args.no_engine else None,
                 engine_kernel=args.engine_kernel,
                 prefill_chunk=args.prefill_chunk, donate=args.donate,
                 status_port=args.status_port)
    print("sampled token ids (first sequence):", toks[0].tolist())


if __name__ == "__main__":
    main()
