"""Pre-traced engine step builders: bucketed prefill / scatter / decode.

Every step here is traced exactly once per (bucket, shape) at engine
warmup — continuous batching then serves any request mix with zero
retraces (asserted in tests/test_engine.py). Three step families:

- **prefill** (one per prompt page-count bucket): batch-1 forward over
  the page-aligned padded prompt. The KV rows written for real
  positions are bit-identical to an unpadded prefill (causal attention
  makes each row depend only on its prefix), and the returned logits
  are gathered at the *real* last token, not the padded one.
- **scatter** (one per page-count bucket): copies the prefill cache
  into the shared page pool at the request's page-table entries — the
  engine's cache-management phase, probed separately from model math.
- **decode** (one per batch-size bucket): batched single-token step
  over the paged pool. The attend math mirrors
  ``models.attention.attn_decode`` operation-for-operation (same einsum
  shapes, same global softmax, vector positions instead of a shared
  scalar), optionally routed through the ``paged_attention`` Pallas
  kernel — both paths bit-identical to the dense reference.
- **chunkpf** (one per (ctx pages, chunk pages) pair): continuation
  prefill of one page-aligned prompt chunk against KV context gathered
  from the pool. The flash blocks replay the *whole-prompt* row plan
  (``attention._row_plan`` over ctx+chunk) restricted to the chunk's
  rows, and every _flash_row op is row-independent, so chunked prefill
  is bit-identical to the equivalent whole-prompt prefill step.

The pool is lane-dense: ``(num_layers, pool_pages, page_size,
kv_heads*head_dim)``, whose minor dimensions tile without padding on
the TPU, so no step relays it. Prefill and chunkpf return their page
blocks in the same layout, and each family reshapes to ``(kv, hd)``
only what it gathers. The XLA decode reads the pool in place and writes
only the rows it changes, once per step, after the layer scan.

Padded lanes of a decode bucket run token 0 at position 0 against the
null page; every dummy lane writes identical values to the same slot,
so the pool stays deterministic and no real page is touched.
"""
from __future__ import annotations

import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.models.attention import (_flash_row, _head_mask, _project_qkv,
                                    _repeat_kv, _row_plan)
from repro.models.layers import mlp_apply, rmsnorm


def engine_compatible(cfg) -> bool:
    """Token-in/token-out attention stacks only: the paged KV layout
    has no analogue for SSM/hybrid recurrent state or frontend embeds."""
    return cfg.family not in ("ssm", "hybrid") and cfg.frontend == "none"


def donation_argnums(phase: str) -> Tuple[int, ...]:
    """Positional args each step family may donate under
    ``jax.jit(..., donate_argnums=...)``.

    Only buffers the step returns an updated version of are donatable:
    the scatter step consumes+returns (pool_k, pool_v) at args (0, 1),
    decode at args (1, 2). Prefill returns no pool, and chunkpf *reads*
    the pool without returning it — donating either would invalidate
    live engine state."""
    if phase == "cache":
        return (0, 1)
    if phase == "decode":
        return (1, 2)
    return ()


def build_engine_prefill(model, n_pages: int, page_size: int) -> Callable:
    """Batch-1 prefill over ``n_pages * page_size`` padded tokens.

    fn(params, batch) with batch = {"tokens": (1, n_pages*page_size),
    "last_idx": (1,)} -> (logits (1, V) at last_idx, k, v) where k/v are
    (L, n_pages, page_size, kv_heads*head_dim) lane-dense page blocks.
    """
    cfg = model.cfg
    seq = n_pages * page_size

    def prefill(params, batch):
        p = model._compute_cast(params)
        x = model._embed_in(p, batch)
        B, S, _ = x.shape
        assert S == seq, (S, seq)
        positions = model._positions(batch, S, B)
        x, cache = tfm.stack_prefill(p["stack"], x, positions, cfg, seq)
        with jax.named_scope("last_logits"):
            idx = batch["last_idx"][:, None, None].astype(jnp.int32)
            last = jnp.take_along_axis(
                x, idx.repeat(x.shape[-1], -1), axis=1)[:, 0]
            logits = jnp.einsum(
                "bd,dv->bv", last,
                model._unembed_weight(p).astype(last.dtype),
                preferred_element_type=jnp.float32)
            logits = model._mask_pad(logits)
        L = cache["k"].shape[0]
        width = cfg.num_kv_heads * cfg.resolved_head_dim
        k = cache["k"].reshape(L, n_pages, page_size, width)
        v = cache["v"].reshape(L, n_pages, page_size, width)
        return logits, k, v

    return prefill


def build_page_scatter(n_pages: int) -> Callable:
    """Cache-management step: write ``n_pages`` prefilled page blocks
    into the pool at the request's page-table entries.

    fn(pool_k, pool_v, k, v, page_ids (n_pages,)) -> (pool_k, pool_v),
    pools ``(L, P, page_size, kv*hd)`` and blocks
    ``(L, n_pages, page_size, kv*hd)``: whole pages are set in place.
    Re-writing a prefix-shared page stores bit-identical values (same
    token prefix -> same KV rows), so sharing never perturbs readers.
    """

    def scatter(pool_k, pool_v, k, v, page_ids):
        with jax.named_scope("page_scatter"):
            pool_k = pool_k.at[:, page_ids].set(k.astype(pool_k.dtype))
            pool_v = pool_v.at[:, page_ids].set(v.astype(pool_v.dtype))
        return pool_k, pool_v

    return scatter


def build_chunk_prefill(model, ctx_pages: int, chunk_pages: int,
                        page_size: int) -> Callable:
    """Continuation prefill: one page-aligned prompt chunk against the
    request's already-written context pages in the pool.

    fn(params, pool_k, pool_v, batch) with batch = {"tokens":
    (1, chunk_pages*page_size), "ctx_pages": (ctx_pages,) int32,
    "last_idx": (1,)} -> (logits (1, V) at last_idx *within the chunk*,
    k, v) where k/v are (L, chunk_pages, page_size, kv*hd) lane-dense
    page blocks for the chunk's own rows. Each layer gathers its context
    pages straight from the pool's ``(L*P, page_size, kv*hd)`` view.

    Bit-identity with whole-prompt prefill is structural: the flash
    blocks replay ``_row_plan(ctx+chunk, attn_chunk, attn_chunk)`` — the
    exact plan the whole-prompt step uses at this padded length —
    restricted to the chunk's q rows, and every ``_flash_row`` reduction
    is row-independent, so each row's (m, l, acc) accumulation sequence
    is identical. Context K/V gathered from the pool equals the freshly
    computed K/V bit-for-bit because the flash einsums cast inputs to
    bfloat16 and the pool's ``kv_cache_dtype`` round-trip commutes with
    that cast (exact for the repo's bf16/f32 cache dtypes).
    """
    cfg = model.cfg
    ctx_len = ctx_pages * page_size
    Sq = chunk_pages * page_size
    S = ctx_len + Sq                     # whole-prompt padded length
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)
    qb, rows = _row_plan(S, cfg.attn_chunk, cfg.attn_chunk)
    # whole-prompt flash blocks clipped to the chunk's rows: _flash_row
    # is row-independent, so computing the sub-range of a block with the
    # block's own (ctx, kv_chunk) reproduces the whole-prompt bits.
    subs = []
    for (off, ctx, chunk) in rows:
        i0, i1 = max(off, ctx_len), min(off + qb, S)
        if i0 < i1:
            subs.append((i0, i1, ctx, chunk))

    def chunkpf(params, pool_k, pool_v, batch):
        p = model._compute_cast(params)
        x = model._embed_in(p, batch)
        assert x.shape[1] == Sq, (x.shape, Sq)
        cd = x.dtype
        positions = jnp.broadcast_to(
            jnp.arange(ctx_len, S, dtype=jnp.int32)[None], (1, Sq))
        ctx_ids = batch["ctx_pages"]
        L, P = pool_k.shape[:2]
        page_k = pool_k.reshape(L * P, page_size, kv * hd)
        page_v = pool_v.reshape(L * P, page_size, kv * hd)

        def body(carry, inp):
            h, = carry
            lp, li = inp
            with jax.named_scope("layer"):
                with jax.named_scope("attn"):
                    qn = rmsnorm(h, lp["ln1"], cfg.norm_eps)
                    q, k_new, v_new = _project_qkv(lp["attn"], qn, cfg,
                                                   positions)
                    with jax.named_scope("ctx_gather"):
                        kc = page_k[li * P + ctx_ids].reshape(
                            1, ctx_len, kv, hd)
                        vc = page_v[li * P + ctx_ids].reshape(
                            1, ctx_len, kv, hd)
                        k_full = jnp.concatenate(
                            [kc.astype(cd), k_new], axis=1)
                        v_full = jnp.concatenate(
                            [vc.astype(cd), v_new], axis=1)
                    kr, vr = _repeat_kv(k_full, v_full, cfg)
                    with jax.named_scope("flash"):
                        outs = []
                        for (i0, i1, ctx, chunk) in subs:
                            q_blk = jax.lax.slice_in_dim(
                                q, i0 - ctx_len, i1 - ctx_len, axis=1)
                            k_ctx = jax.lax.slice_in_dim(kr, 0, ctx, axis=1)
                            v_ctx = jax.lax.slice_in_dim(vr, 0, ctx, axis=1)
                            o, _, _ = _flash_row(q_blk, k_ctx, v_ctx, i0,
                                                 chunk, scale)
                            outs.append(o.astype(cd))
                        o = (jnp.concatenate(outs, axis=1)
                             if len(outs) > 1 else outs[0])
                    with jax.named_scope("out_proj"):
                        hm = _head_mask(cfg, o.dtype)
                        if hm is not None:
                            o = o * hm[None, None, :, None]
                        a = jnp.einsum("bsnh,nhd->bsd", o, lp["attn"]["wo"])
                h = h + a
                if cfg.moe is not None:
                    with jax.named_scope("moe"):
                        m, _ = moe_mod.moe_apply(
                            lp["moe"], rmsnorm(h, lp["ln2"], cfg.norm_eps),
                            cfg)
                else:
                    with jax.named_scope("mlp"):
                        m = mlp_apply(lp["mlp"],
                                      rmsnorm(h, lp["ln2"], cfg.norm_eps))
                h = h + m
            return (h,), (k_new, v_new)

        stack = p["stack"]
        with jax.named_scope("layers"):
            (x,), (ks, vs) = jax.lax.scan(
                body, (x,),
                (stack["layers"],
                 jnp.arange(cfg.num_layers, dtype=jnp.int32)))
        with jax.named_scope("final_norm"):
            x = rmsnorm(x, stack["ln_f"], cfg.norm_eps)
        with jax.named_scope("last_logits"):
            idx = batch["last_idx"][:, None, None].astype(jnp.int32)
            last = jnp.take_along_axis(
                x, idx.repeat(x.shape[-1], -1), axis=1)[:, 0]
            logits = jnp.einsum(
                "bd,dv->bv", last,
                model._unembed_weight(p).astype(last.dtype),
                preferred_element_type=jnp.float32)
            logits = model._mask_pad(logits)
        k = ks[:, 0].reshape(L, chunk_pages, page_size, kv * hd)
        v = vs[:, 0].reshape(L, chunk_pages, page_size, kv * hd)
        return logits, k, v

    return chunkpf


def _decode_qkv(lp, x, pos, cfg):
    """The decode token's grouped queries ``(B, 1, kv, g, hd)`` and its
    new K/V rows ``(B, kv, hd)``, at vector positions ``pos``."""
    q, k_new, v_new = _project_qkv(lp, x, cfg, pos[:, None])
    B = x.shape[0]
    if q.shape[2] != cfg.num_heads:
        q = q[:, :, :cfg.num_heads]
    qg = q.reshape(B, 1, cfg.num_kv_heads, cfg.q_per_kv,
                   cfg.resolved_head_dim)
    return qg, k_new[:, 0], v_new[:, 0]


def _attend_dense(qg, kd, vd, pos):
    """``attn_decode``'s math over a dense ``(B, s_max, kv, hd)`` gather
    of each lane's pages: the same einsum shapes, the same global
    softmax, vector positions instead of a shared scalar."""
    s_max, hd = kd.shape[1], kd.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.bfloat16),
                   kd.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s_max)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    pr = jnp.exp(s - m)
    l = pr.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bkgqs,bskh->bkgqh", (pr / l).astype(jnp.bfloat16),
                   vd.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return o[:, :, :, 0]                                # (B, kv, g, hd)


def build_paged_decode(model, batch_size: int, n_pages: int,
                       page_size: int, *, use_kernel: bool = True,
                       pages_per_step: int = 1) -> Callable:
    """Batched single-token decode over the paged pool.

    fn(params, pool_k, pool_v, batch) with batch = {"tokens": (B, 1),
    "pos": (B,), "pages": (B, n_pages)} ->
    (logits (B, V), pool_k, pool_v, next_tokens (B,)); the pools are
    lane-dense ``(L, P, page_size, kv*hd)``.

    The XLA path (``use_kernel=False``) reads the pool in place: each
    layer gathers its lanes' pages straight from the pool and puts the
    new token's K/V into that gathered copy at ``pos``. The L x B new
    rows are written once, after the layer scan, by one row scatter into
    the pool's ``(L*P*page_size, kv*hd)`` view, so only the written rows
    move. The kernel path writes each layer's rows inside the scan, on a
    ``(P, page_size, kv, hd)`` view of the layer, because the kernel
    reads them from the pool.
    """
    cfg = model.cfg
    s_max = n_pages * page_size
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def out_proj(lp, h, o):
        with jax.named_scope("out_proj"):
            B = h.shape[0]
            H = cfg.num_heads
            Hp = lp["wo"].shape[0]
            ow = o[:, None].reshape(B, 1, H, hd).astype(h.dtype)
            if Hp != H:
                ow = jnp.pad(ow, [(0, 0), (0, 0), (0, Hp - H), (0, 0)])
            return jnp.einsum("bsnh,nhd->bsd", ow, lp["wo"])

    def ffn(lp, h):
        """The residual MLP or MoE block."""
        if cfg.moe is not None:
            with jax.named_scope("moe"):
                mo, _ = moe_mod.moe_apply(
                    lp["moe"], rmsnorm(h, lp["ln2"], cfg.norm_eps), cfg)
        else:
            with jax.named_scope("mlp"):
                mo = mlp_apply(lp["mlp"],
                               rmsnorm(h, lp["ln2"], cfg.norm_eps))
        return h + mo

    def layers_xla(layers, x, pool_k, pool_v, pages, pos, pidx, slot):
        L, P = pool_k.shape[:2]
        B = x.shape[0]
        page_k = pool_k.reshape(L * P, page_size, kv * hd)
        page_v = pool_v.reshape(L * P, page_size, kv * hd)
        at_pos = (jnp.arange(s_max)[None, :] == pos[:, None])[..., None,
                                                              None]

        def body(h, inp):
            lp, li = inp
            with jax.named_scope("layer"):
                with jax.named_scope("attn"):
                    qg, k_new, v_new = _decode_qkv(
                        lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps),
                        pos, cfg)
                    k_new = k_new.astype(pool_k.dtype)
                    v_new = v_new.astype(pool_v.dtype)
                    with jax.named_scope("attend"):
                        ids = li * P + pages
                        kd = page_k[ids].reshape(B, s_max, kv, hd)
                        vd = page_v[ids].reshape(B, s_max, kv, hd)
                        kd = jnp.where(at_pos, k_new[:, None], kd)
                        vd = jnp.where(at_pos, v_new[:, None], vd)
                        o = _attend_dense(qg, kd, vd, pos)
                    a = out_proj(lp["attn"], h, o)
                h = ffn(lp, h + a)
            return h, (k_new.reshape(B, kv * hd), v_new.reshape(B, kv * hd))

        x, (ks, vs) = jax.lax.scan(
            body, x, (layers, jnp.arange(L, dtype=jnp.int32)))
        with jax.named_scope("cache_update"):
            rows = (jnp.arange(L, dtype=jnp.int32)[:, None] * P + pidx
                    ) * page_size + slot

            def write(pool, new):
                flat = pool.reshape(L * P * page_size, kv * hd)
                return flat.at[rows.reshape(L * B)].set(
                    new.reshape(L * B, kv * hd)).reshape(pool.shape)
            return x, write(pool_k, ks), write(pool_v, vs)

    def layers_kernel(layers, x, pool_k, pool_v, pages, pos, pidx, slot):
        from repro.kernels.ops import _interpret_default
        from repro.kernels.paged_attention import paged_attention
        L, P = pool_k.shape[:2]

        def body(carry, inp):
            h, pk, pv = carry
            lp, li = inp
            with jax.named_scope("layer"):
                kp = jax.lax.dynamic_index_in_dim(pk, li, 0, keepdims=False)
                vp = jax.lax.dynamic_index_in_dim(pv, li, 0, keepdims=False)
                kp = kp.reshape(P, page_size, kv, hd)
                vp = vp.reshape(P, page_size, kv, hd)
                with jax.named_scope("attn"):
                    qg, k_new, v_new = _decode_qkv(
                        lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps),
                        pos, cfg)
                    with jax.named_scope("cache_update"):
                        kp = kp.at[pidx, slot].set(k_new.astype(kp.dtype))
                        vp = vp.at[pidx, slot].set(v_new.astype(vp.dtype))
                    o = paged_attention(qg[:, 0], kp, vp, pages, pos,
                                        pages_per_step=pages_per_step,
                                        interpret=_interpret_default())
                    a = out_proj(lp["attn"], h, o)
                h = ffn(lp, h + a)
                pk = jax.lax.dynamic_update_index_in_dim(
                    pk, kp.reshape(pk.shape[1:]), li, 0)
                pv = jax.lax.dynamic_update_index_in_dim(
                    pv, vp.reshape(pv.shape[1:]), li, 0)
            return (h, pk, pv), None

        (x, pool_k, pool_v), _ = jax.lax.scan(
            body, (x, pool_k, pool_v),
            (layers, jnp.arange(L, dtype=jnp.int32)))
        return x, pool_k, pool_v

    layers_fn = layers_kernel if use_kernel else layers_xla

    def decode(params, pool_k, pool_v, batch):
        cd = jnp.dtype(cfg.compute_dtype)
        p = model._compute_cast(params)
        with jax.named_scope("embed"):
            x = jnp.take(p["embed"], batch["tokens"], axis=0).astype(cd)
        pos = batch["pos"]
        pages = batch["pages"]
        pidx = jnp.take_along_axis(pages, (pos // page_size)[:, None],
                                   axis=1)[:, 0]
        stack = p["stack"]
        with jax.named_scope("layers"):
            x, pool_k, pool_v = layers_fn(stack["layers"], x, pool_k, pool_v,
                                          pages, pos, pidx, pos % page_size)
        with jax.named_scope("final_norm"):
            x = rmsnorm(x, stack["ln_f"], cfg.norm_eps)
        with jax.named_scope("last_logits"):
            logits = jnp.einsum("bd,dv->bv", x[:, -1],
                                model._unembed_weight(p).astype(cd),
                                preferred_element_type=jnp.float32)
            logits = model._mask_pad(logits)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, pool_k, pool_v, next_tok

    return decode
