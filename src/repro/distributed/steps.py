"""Train / prefill / decode step builders.

These close over a ``Model`` + ``TrainConfig`` and produce pure functions
ready for ``jax.jit`` with explicit in/out shardings — used identically
by the real trainer (``launch/train.py``), the multi-pod dry-run
(``launch/dryrun.py``), and the RealProbe integration tests (the probed
function IS the train step).

Features:
- microbatched gradient accumulation (``TrainConfig.microbatches``):
  lax.scan over microbatches so XLA's latency-hiding scheduler can
  overlap microbatch k's gradient reduce-scatter with k+1's compute;
- optional int8 error-feedback compression of the cross-pod gradient
  exchange (``grad_compression="int8_ef"``): gradients stay pod-local
  (partial-manual shard_map over the ``pod`` axis; data/model stay
  auto-sharded inside), get quantized to int8 with per-tensor scales, and
  ring-exchange across pods at 1 byte/element over DCI instead of 4,
  with the quantization error carried as error-feedback state;
- dtype policies handled by the model/optimizer (bf16 compute, fp32 or
  bf16 master+moments).
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShapeConfig, TrainConfig
from repro.models.model import Model
from repro.optim import adamw, compression
from repro.optim.schedule import make_schedule


def _split_microbatches(batch: Dict[str, Any], k: int) -> Dict[str, Any]:
    def split(x):
        if x.ndim == 0:
            return x
        b = x.shape[0]
        if b % k:
            raise ValueError(f"batch {b} % microbatches {k}")
        return x.reshape((k, b // k) + x.shape[1:])
    return {key: split(v) for key, v in batch.items()}


def build_train_step(model: Model, tcfg: TrainConfig) -> Callable:
    """Returns train_step(params, opt_state, batch[, ef_residual])."""
    cfg = model.cfg
    schedule = make_schedule(cfg.schedule, tcfg)
    k = tcfg.microbatches

    def loss_fn(params, batch):
        if "positions" in batch and cfg.pos_emb == "mrope" and \
                batch["positions"].shape[0] != 3:
            batch = dict(batch)
            batch["positions"] = jnp.moveaxis(batch["positions"], 1, 0)
        with jax.named_scope("loss"):
            return model.loss_fn(params, batch)

    def grads_of(params, batch):
        if k == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads

        b = dict(batch)
        if cfg.pos_emb == "mrope" and "positions" in b:
            b["positions"] = jnp.moveaxis(b["positions"], 0, 1)  # (B,3,S)
        mb = _split_microbatches(b, k)

        acc_dt = jnp.dtype(cfg.grad_accum_dtype)

        def body(acc, micro):
            (loss, _metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, micro)
            gsum = jax.tree_util.tree_map(
                lambda a, gg: a + gg.astype(acc_dt), acc[0], g)
            return (gsum, acc[1] + loss), None

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dt), params)
        with jax.named_scope("microbatches"):
            (gsum, loss_sum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), mb)
        grads = jax.tree_util.tree_map(lambda g: g / k, gsum)
        loss = loss_sum / k
        return loss, {"nll": loss}, grads

    def compressed_grads_of(params, batch, residual):
        """Pod-local grads + int8 error-feedback ring exchange over the
        pod axis. data/model axes stay auto-sharded inside."""
        mesh = jax.sharding.get_abstract_mesh()
        n_pods = mesh.shape["pod"]
        perm = [(i, (i + 1) % n_pods) for i in range(n_pods)]

        def pod_local(params_, batch_, res_):
            loss, metrics, grads = grads_of(params_, batch_)
            with jax.named_scope("grad_compress"):
                payload, scales, new_res = compression.compress(grads, res_)

                def xchg(q8, s):
                    total = q8.astype(jnp.float32) * s
                    q_rot, s_rot = q8, s
                    for _ in range(n_pods - 1):     # int8 on the wire
                        q_rot = jax.lax.ppermute(q_rot, "pod", perm)
                        s_rot = jax.lax.ppermute(s_rot, "pod", perm)
                        total = total + q_rot.astype(jnp.float32) * s_rot
                    return total / n_pods

                grads = jax.tree_util.tree_map(xchg, payload, scales)
            loss = jax.lax.pmean(loss, "pod")
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, "pod"), metrics)
            return loss, metrics, grads, new_res

        def batch_spec(x):
            if x.ndim == 0:
                return P()
            if cfg.pos_emb == "mrope" and x.ndim == 3 and x.shape[0] == 3:
                return P(None, "pod")
            return P("pod")

        in_batch_specs = {kk: batch_spec(v) for kk, v in batch.items()}
        rep_p = jax.tree_util.tree_map(lambda _: P(), params)
        rep_r = jax.tree_util.tree_map(lambda _: P(), residual)
        metrics_spec = {"nll": P()} if k > 1 else \
            {"nll": P(), "z_loss": P(), "aux_loss": P()}
        return jax.shard_map(
            pod_local, mesh=mesh,
            in_specs=(rep_p, in_batch_specs, rep_r),
            out_specs=(P(), metrics_spec, rep_p, rep_r),
            axis_names={"pod"}, check_vma=False,
        )(params, batch, residual)

    def train_step(params, opt_state, batch, ef_residual=None):
        if ef_residual is not None and tcfg.grad_compression == "int8_ef":
            loss, metrics, grads, ef_residual = compressed_grads_of(
                params, batch, ef_residual)
        else:
            loss, metrics, grads = grads_of(params, batch)
        with jax.named_scope("optimizer"):
            params, opt_state, om = adamw.update(params, grads, opt_state,
                                                 tcfg, schedule)
        metrics = dict(metrics)
        metrics.update(loss=loss, **om)
        if ef_residual is not None:
            return params, opt_state, ef_residual, metrics
        return params, opt_state, metrics

    return train_step


def build_prefill_step(model: Model, shape: ShapeConfig) -> Callable:
    k = model.cfg.prefill_microbatches

    def prefill_step(params, batch):
        if k == 1:
            with jax.named_scope("prefill"):
                logits, cache = model.prefill(params, batch, shape.seq_len)
            return logits, cache

        # batch-chunked prefill: fwd activations scale with B/k while the
        # cache output stays identical (32k-prompt HBM lever; the serving
        # engine's request batching maps directly onto this).
        def split(key, v):
            if key == "positions" and v.ndim == 3 and v.shape[0] == 3:
                b = v.shape[1]
                return jnp.moveaxis(
                    v.reshape(3, k, b // k, v.shape[2]), 1, 0)
            return v.reshape((k, v.shape[0] // k) + v.shape[1:])

        mb = {key: split(key, v) for key, v in batch.items()}
        # keep the chunked batch data-sharded through the map reshape
        from repro.distributed import sharding as shd
        def respec(key, v):
            if key == "positions" and v.ndim == 4:
                return shd.shard(v, None, None, "batch", "seq")
            if v.ndim == 3:
                return shd.shard(v, None, "batch", "seq")
            return v
        mb = {key: respec(key, v) for key, v in mb.items()}

        def body(b):
            if "positions" in b and b["positions"].ndim == 3:
                pass
            with jax.named_scope("prefill_chunk"):
                return model.prefill(params, b, shape.seq_len)

        logits, cache = jax.lax.map(body, mb)
        logits = logits.reshape((-1,) + logits.shape[2:])
        # cache leaves: (k, L, B/k, ...) -> (L, B, ...)
        cache = jax.tree_util.tree_map(
            lambda a: jnp.moveaxis(a, 0, 1).reshape(
                (a.shape[1], a.shape[0] * a.shape[2]) + a.shape[3:]),
            cache)
        return logits, cache

    return prefill_step


def build_decode_step(model: Model) -> Callable:
    def decode_step(params, cache, batch):
        with jax.named_scope("decode"):
            logits, cache, next_token = model.decode_step(params, cache,
                                                          batch)
        return logits, cache, next_token
    return decode_step


def build_eval_step(model: Model) -> Callable:
    """Forward-only eval step (loss + metrics, no optimizer). Probeable
    as-is on one device, or per shard via ``build_dp_eval_step``."""
    def eval_step(params, batch):
        with jax.named_scope("eval"):
            loss, metrics = model.loss_fn(params, batch)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return loss, metrics
    return eval_step


# ---------------------------------------------------- per-shard bodies
#
# Explicit-collective SPMD bodies for `shard_map` — and therefore for
# `repro.core.mesh_probe`, which records a per-device cycle row for
# every probe inside them. Parameters/optimizer state are replicated,
# the batch is sharded over `axis` (pure data parallelism), and the
# gradient exchange is an explicit `psum`-mean that the probe attributes
# to the "grad_exchange" scope (ring wire-byte model; see
# launch/collectives.py). The auto-sharded `build_train_step` stays the
# production path — these exist so the *same* training math is
# observable per device.

def _pmean_tree(tree, axis):
    return jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, axis), tree)


def build_dp_train_step(model: Model, tcfg: TrainConfig,
                        axis="dev") -> Callable:
    """Data-parallel per-shard train step: grads_local -> psum-mean over
    ``axis`` -> replicated AdamW update. Returns
    ``train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)`` with every output replicated."""
    schedule = make_schedule(model.cfg.schedule, tcfg)

    def loss_fn(params, batch):
        with jax.named_scope("loss"):
            return model.loss_fn(params, batch)

    def train_step(params, opt_state, batch):
        with jax.named_scope("grads"):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        with jax.named_scope("grad_exchange"):
            grads = _pmean_tree(grads, axis)
            loss = jax.lax.pmean(loss, axis)
            metrics = _pmean_tree(metrics, axis)
        with jax.named_scope("optimizer"):
            params, opt_state, om = adamw.update(params, grads, opt_state,
                                                 tcfg, schedule)
        metrics = dict(metrics)
        metrics.update(loss=loss, **om)
        return params, opt_state, metrics

    return train_step


def build_dp_eval_step(model: Model, axis="dev") -> Callable:
    """Data-parallel per-shard eval step (loss psum-meaned over ``axis``)."""
    base = build_eval_step(model)

    def eval_step(params, batch):
        loss, metrics = base(params, batch)
        with jax.named_scope("loss_exchange"):
            loss = jax.lax.pmean(loss, axis)
            metrics = _pmean_tree(metrics, axis)
        return loss, metrics

    return eval_step
