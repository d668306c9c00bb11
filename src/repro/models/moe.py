"""Mixture-of-Experts FFN: top-k routing + sort-based grouped GEMM.

Dispatch is MegaBlocks-style [arXiv:2211.15841]: flatten tokens, sort the
(token, expert) assignments by expert, run ``jax.lax.ragged_dot`` grouped
GEMMs, unsort, and combine with the routing weights. No token dropping,
and FLOPs are exactly the active-expert FLOPs (6·N_active·D accounting).

Sharding: expert weights keep all experts on every model shard but are
TP-sharded on the expert d_ff dimension ("ff" -> model axis) and
FSDP-sharded on d_model ("embed" -> data axis). The shard_map interior
all-gathers the FSDP shards (reduce-scatter in reverse on the backward
pass) and psums the down-projection partials over the model axis — the
same collective pattern as the dense TP MLP, so MoE adds **zero** extra
collective classes to the step. The token sort/argsort stays local to
each data shard (no global sort collective). An all-to-all EP variant is
the §Perf hillclimb alternative.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models.layers import Param


def moe_schema(cfg: ModelConfig) -> Dict[str, Param]:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    s = {
        "router": Param((d, E), (None, None)),  # small; replicated
        "wi": Param((E, d, ff), ("expert", "embed", "ff")),
        "wg": Param((E, d, ff), ("expert", "embed", "ff")),
        "wo": Param((E, ff, d), ("expert", "ff", "embed")),
    }
    if cfg.moe.dense_residual:
        rff = cfg.moe.residual_d_ff or ff
        s["res_wi"] = Param((d, rff), ("embed", "ff"))
        s["res_wg"] = Param((d, rff), ("embed", "ff"))
        s["res_wo"] = Param((rff, d), ("ff", "embed"))
    return s


def _route(x_flat, router_w, cfg: ModelConfig):
    """x_flat: (T, d) -> (weights (T,k), expert_idx (T,k), aux_loss)."""
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    top_p, top_i = jax.lax.top_k(probs, k)                      # (T, k)
    weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss (Switch [arXiv:2101.03961])
    T = x_flat.shape[0]
    assign = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    frac_assign = assign / (T * k)
    frac_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac_assign * frac_prob)
    return weights, top_i, aux


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """ragged_dot with a sparse custom VJP.

    jax.lax.ragged_dot's builtin autodiff materializes DENSE per-expert
    gradients — (rows, E, d) and (rows, E*d) intermediates, measured at
    256 GiB/device on granite-moe train_4k (§Perf iteration log). The
    flash-style fix: both backward products are themselves grouped GEMMs:

        dx    = ragged_dot(dy, swapaxes(w, 1, 2), gs)
        dw[e] = x_e^T @ dy_e   (ragged_dot_general, ragged contracting)
    """
    return jax.lax.ragged_dot(x, w, group_sizes)


def _grouped_matmul_fwd(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w, group_sizes), (x, w, group_sizes)


def _grouped_matmul_bwd(res, dy):
    x, w, gs = res
    dx = jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), gs)
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=(0,),
        rhs_group_dimensions=())
    dw = jax.lax.ragged_dot_general(x, dy, gs, dims,
                                    preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _expert_gemms_ragged(xs, wi, wg, wo, group_sizes):
    """Grouped SwiGLU over expert-sorted rows. xs: (T*k, d)."""
    h = grouped_matmul(xs, wi, group_sizes)
    g = grouped_matmul(xs, wg, group_sizes)
    h = jax.nn.silu(g) * h
    return grouped_matmul(h, wo, group_sizes)


def _capacity(cfg: ModelConfig, T: int) -> int:
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    c = int(math.ceil(T * k / E * cfg.moe.capacity_factor))
    return max(8, ((c + 7) // 8) * 8)


def _moe_local(x, router_w, wi, wg, wo, cfg: ModelConfig,
               fsdp_axis=None, model_axis=None, batch_axes=None):
    """Per-shard MoE body. x: (B_local, S, d). Collectives only when the
    corresponding mesh axis name is given (shard_map interior).

    Dispatch: sort assignments by expert, scatter rows into
    capacity-padded (E, C, d) blocks, run dense *batched* GEMMs, gather
    back. Batched-einsum fwd/bwd never materializes anything bigger than
    (E, C, ff_local) — ragged_dot's autodiff (and even
    ragged_dot_general's CPU lowering of the dW product) materializes
    dense (rows, E*d) intermediates, measured at 260 GiB/device on
    granite-moe train_4k (§Perf iteration log). Overflowing tokens are
    dropped (GShard-style, capacity_factor=1.25); the aux loss keeps
    routing balanced. ``impl="ragged"`` keeps the dropless grouped-GEMM
    path (custom sparse VJP) for TPU megablox-class backends.
    """
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    B, S, D = x.shape
    if fsdp_axis is not None:   # FSDP all-gather of the embed shards
        wi = jax.lax.all_gather(wi, fsdp_axis, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
    x_flat = x.reshape(B * S, D)
    T = B * S
    with jax.named_scope("router"):
        weights, top_i, aux = _route(x_flat, router_w, cfg)
    with jax.named_scope("dispatch"):
        flat_expert = top_i.reshape(-1)                          # (T*k,)
        sort_idx = jnp.argsort(flat_expert)                      # local sort
        expert_sorted = jnp.take(flat_expert, sort_idx)
        token_of = sort_idx // k
        group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)
    if cfg.moe.impl == "ragged":
        with jax.named_scope("expert_gemm"):
            xs = jnp.take(x_flat, token_of, axis=0)              # (T*k, d)
            out_sorted = _expert_gemms_ragged(xs, wi, wg, wo, group_sizes)
        with jax.named_scope("combine"):
            inv = jnp.argsort(sort_idx)
            out = jnp.take(out_sorted, inv, axis=0).reshape(T, k, D)
            out = jnp.einsum("tkd,tk->td", out, weights.astype(out.dtype))
    else:
        C = _capacity(cfg, T)
        with jax.named_scope("dispatch_pad"):
            # gather-only dispatch: rows are expert-sorted, so block (e,c)
            # reads sorted row starts[e]+c. No scatter in the forward —
            # XLA:CPU scatter lowering materializes (rows, d)-wide u32
            # index planes (§Perf iteration log).
            starts = jnp.cumsum(group_sizes) - group_sizes       # (E,)
            c_iota = jnp.arange(C)
            blk_valid = c_iota[None, :] < group_sizes[:, None]   # (E, C)
            blk_sorted_idx = jnp.minimum(starts[:, None] + c_iota[None, :],
                                         T * k - 1)
            blk_token = jnp.take(token_of, blk_sorted_idx)       # (E, C)
            xs = jnp.take(x_flat, blk_token.reshape(-1), axis=0)
            xs = (xs.reshape(E, C, D) *
                  blk_valid[..., None].astype(x_flat.dtype))
        with jax.named_scope("expert_gemm"):
            h = jnp.einsum("ecd,edf->ecf", xs, wi)
            g = jnp.einsum("ecd,edf->ecf", xs, wg)
            h = jax.nn.silu(g) * h
            out_blocks = jnp.einsum("ecf,efd->ecd", h, wo)
        with jax.named_scope("combine"):
            pos = jnp.arange(T * k) - jnp.take(starts, expert_sorted)
            keep = pos < C
            flat_blk = expert_sorted * C + jnp.minimum(pos, C - 1)
            gathered = jnp.take(out_blocks.reshape(E * C, D), flat_blk,
                                axis=0)
            gathered = jnp.where(keep[:, None], gathered, 0.0)
            inv = jnp.argsort(sort_idx)
            out = jnp.take(gathered, inv, axis=0).reshape(T, k, D)
            out = jnp.einsum("tkd,tk->td", out, weights.astype(out.dtype))
    with jax.named_scope("reduce"):
        if model_axis is not None:   # partial d_ff contributions
            out = jax.lax.psum(out, model_axis)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        if model_axis is not None:
            aux = jax.lax.pmean(aux, model_axis)
    return out.reshape(B, S, D), aux


def moe_apply(params, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN. x: (B, S, d) -> (out, aux_loss).

    With active sharding rules, runs the dispatch/grouped-GEMM interior
    under shard_map (local sort, TP-sharded d_ff, FSDP-gathered weights);
    otherwise runs the plain local path (single device / smoke tests).
    """
    rules = shd.current_rules()
    with jax.named_scope("moe"):
        if rules is None:
            out, aux = _moe_local(x, params["router"], params["wi"],
                                  params["wg"], params["wo"], cfg)
        else:
            mesh = jax.sharding.get_abstract_mesh()
            rules = shd.filter_rules(rules, mesh)
            batch = rules.get("batch")
            batch_axes = ((batch,) if isinstance(batch, str) else
                          tuple(batch) if batch else ())
            fsdp = rules.get("embed")
            model = rules.get("ff")
            x_spec = P(batch, None, None)
            w_spec = P(None, fsdp, model)       # (E, d, ff)
            wo_spec = P(None, model, fsdp)      # (E, ff, d) — embed stays FSDP
            body = functools.partial(
                _moe_local, cfg=cfg, fsdp_axis=fsdp, model_axis=model,
                batch_axes=batch_axes)
            # wo's embed-dim FSDP shards: gather inside to keep memory flat
            def wrapped(x_, rw, wi_, wg_, wo_):
                if fsdp is not None:
                    wo_f = jax.lax.all_gather(wo_, fsdp, axis=2, tiled=True)
                else:
                    wo_f = wo_
                return body(x_, rw, wi_, wg_, wo_f)
            out, aux = jax.shard_map(
                wrapped, mesh=mesh,
                in_specs=(x_spec, P(None, None), w_spec, w_spec, wo_spec),
                out_specs=(x_spec, P()),
                check_vma=False,
            )(x, params["router"], params["wi"], params["wg"], params["wo"])
        if cfg.moe.dense_residual:
            with jax.named_scope("dense_residual"):
                from repro.models.layers import mlp_apply
                res = mlp_apply({"wi": params["res_wi"], "wg": params["res_wg"],
                                 "wo": params["res_wo"]}, x)
            out = out + res
    return out, aux * cfg.moe.aux_loss_weight
