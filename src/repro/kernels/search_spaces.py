"""Declarative DSE search spaces for the Pallas kernels.

Each factory builds a ``repro.core.dse.SearchSpace`` over the kernel's
tunable axes — MXU tile sizes and the software-pipelining depth — at a
concrete problem shape (tuning is shape-specific, like the paper's
per-design DSE). The ``bind`` closures call the raw kernels (not the
jitted ``ops`` wrappers) so the traced jaxpr exposes the ``pallas_call``
directly to the cost model and the probe instrumenter.

``chunked_prefill`` is the odd one out: it tunes a *schedule* (the
serving engine's prefill chunk quantum) rather than kernel tiles, so
its bind traces plain XLA steps — the cost model sees zero Pallas
resources and never prunes, and all pricing comes from probed cycles.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd_scan as _ssd
from repro.kernels.ops import _interpret_default as _interpret


def flash_attention_space(*, B: int = 1, H: int = 2, S: int = 256,
                          D: int = 64, Hkv: int | None = None,
                          causal: bool = True,
                          dtype=jnp.float32,
                          blocks_q: Tuple[int, ...] = (64, 128, 256),
                          blocks_k: Tuple[int, ...] = (64, 128, 256),
                          pipelines: Tuple[int, ...] = (1, 2),
                          seed: int = 0):
    """Block/tile x pipeline space for the causal GQA flash kernel."""
    from repro.core.dse import SearchSpace
    Hkv = Hkv or H
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k0, (B, H, S, D)).astype(dtype)
    k = jax.random.normal(k1, (B, Hkv, S, D)).astype(dtype)
    v = jax.random.normal(k2, (B, Hkv, S, D)).astype(dtype)

    def is_valid(cfg):
        bq, bk, pp = cfg["block_q"], cfg["block_k"], cfg["pipeline"]
        return (bq <= S and bk <= S and S % bq == 0 and S % bk == 0
                and (S // bk) % pp == 0)

    def bind(cfg):
        bq, bk, pp = cfg["block_q"], cfg["block_k"], cfg["pipeline"]
        interp = _interpret()

        def fn(q, k, v):
            with jax.named_scope("flash_attention"):
                return _fa.flash_attention(
                    q, k, v, causal=causal, block_q=bq, block_k=bk,
                    pipeline=pp, interpret=interp)
        return fn

    return SearchSpace(
        kernel_id="flash_attention",
        axes={"block_q": blocks_q, "block_k": blocks_k,
              "pipeline": pipelines},
        bind=bind, args=(q, k, v),
        default={"block_q": min(_fa.DEFAULT_BLOCK_Q, S),
                 "block_k": min(_fa.DEFAULT_BLOCK_K, S), "pipeline": 1},
        is_valid=is_valid)


def ssd_scan_space(*, B: int = 1, H: int = 4, G: int = 2, L: int = 256,
                   P: int = 16, N: int = 32,
                   chunks: Tuple[int, ...] = (32, 64, 128, 256),
                   pipelines: Tuple[int, ...] = (1, 2, 4),
                   seed: int = 0):
    """Chunk x sub-chunk-pipeline space for the Mamba-2 SSD scan."""
    from repro.core.dse import SearchSpace
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (B, H, L, P)) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (B, H, L))) * 0.3
    b = jax.random.normal(ks[2], (B, G, L, N)) * 0.5
    c = jax.random.normal(ks[3], (B, G, L, N)) * 0.5

    def is_valid(cfg):
        ch, pp = cfg["chunk"], cfg["pipeline"]
        return (ch <= L and L % ch == 0 and ch % pp == 0
                and ch // pp >= 8)

    def bind(cfg):
        ch, pp = cfg["chunk"], cfg["pipeline"]
        interp = _interpret()

        def fn(x, a, b, c):
            with jax.named_scope("ssd_scan"):
                return _ssd.ssd_scan(x, a, b, c, chunk=ch, pipeline=pp,
                                     interpret=interp)
        return fn

    return SearchSpace(
        kernel_id="ssd_scan",
        axes={"chunk": chunks, "pipeline": pipelines},
        bind=bind, args=(x, a, b, c),
        default={"chunk": min(256, L), "pipeline": 1},
        is_valid=is_valid)


def paged_attention_space(*, B: int = 4, KV: int = 4, G: int = 2,
                          HD: int = 64, page_size: int = 16,
                          n_pages: int = 8, pool_pages: int = 64,
                          kv_dtype=jnp.bfloat16,
                          pages_per_step: Tuple[int, ...] = (1, 2, 4, 8),
                          seed: int = 0):
    """Pipelining-depth space for the paged-attention decode kernel.

    The workload is a randomly permuted page table (the serving
    engine's steady state: pages are scattered by alloc/free churn),
    with per-request positions spread across the cache range.
    """
    from repro.core.dse import SearchSpace
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(k0, (B, KV, G, HD), jnp.float32)
    pool_k = jax.random.normal(
        k1, (pool_pages, page_size, KV, HD)).astype(kv_dtype)
    pool_v = jax.random.normal(
        k2, (pool_pages, page_size, KV, HD)).astype(kv_dtype)
    pages = jax.random.permutation(
        k3, pool_pages)[:B * n_pages].reshape(B, n_pages).astype(jnp.int32)
    s_max = page_size * n_pages
    pos = (jnp.arange(B, dtype=jnp.int32) * (s_max // max(B, 1))
           + page_size - 1) % s_max

    def is_valid(cfg):
        return n_pages % cfg["pages_per_step"] == 0

    def bind(cfg):
        pps = cfg["pages_per_step"]
        interp = _interpret()

        def fn(q, pool_k, pool_v, pages, pos):
            with jax.named_scope("paged_attention"):
                return _pa.paged_attention(q, pool_k, pool_v, pages, pos,
                                           pages_per_step=pps,
                                           interpret=interp)
        return fn

    return SearchSpace(
        kernel_id="paged_attention",
        axes={"pages_per_step": pages_per_step},
        bind=bind, args=(q, pool_k, pool_v, pages, pos),
        default={"pages_per_step": _pa.DEFAULT_PAGES_PER_STEP},
        is_valid=is_valid)


def chunked_prefill_space(*, arch: str = "tinyllama-1.1b",
                          prompt_pages: int = 4, page_size: int = 16,
                          chunks: Tuple[int, ...] | None = None,
                          seed: int = 0):
    """Chunk-size space for the engine's chunked-prefill schedule.

    The tunable axis is ``chunk_pages`` — how many pages of prompt one
    scheduler quantum prefills (the engine's
    ``EngineConfig.prefill_chunk_pages``), sitting next to the decode
    kernel's ``pages_per_step`` axis. Each candidate binds the full
    static chain the engine would run for a ``prompt_pages`` prompt:
    an opening prefill step, then continuation chunks against the pool
    (``build_chunk_prefill``), each followed by its page scatter. Every
    candidate computes bit-identical logits (chunking is a pure
    schedule change), so the DSE engine is pricing pure overhead:
    context re-gather and per-chunk dispatch vs head-of-line latency.
    The pool is the engine's lane-dense ``(L, prompt_pages + 2,
    page_size, kv_heads*head_dim)``.
    """
    from repro.configs.registry import smoke_config
    from repro.core.dse import SearchSpace
    from repro.engine.step import (build_chunk_prefill,
                                   build_engine_prefill,
                                   build_page_scatter)
    from repro.models import Model

    cfg = smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    pp, ps = prompt_pages, page_size
    if chunks is None:   # pow2 quanta plus the whole-prompt baseline
        chunks = tuple(sorted(set(_pow2_range(1, pp)) | {pp}))
    kvd = jnp.dtype(cfg.kv_cache_dtype)
    # identity page table: prompt page i lives at pool slot i+1 (slot 0
    # is the engine's pinned null page)
    pool_shape = (cfg.num_layers, pp + 2, ps,
                  cfg.num_kv_heads * cfg.resolved_head_dim)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (1, pp * ps), 0, cfg.vocab_size, jnp.int32)

    def is_valid(c):
        return 1 <= c["chunk_pages"] <= pp

    def bind(c):
        K = c["chunk_pages"]
        plan = []                        # (cs, n, step_fn, scatter_fn)
        cs = 0
        while cs < pp:
            n = min(K, pp - cs)
            step = (build_engine_prefill(model, n, ps) if cs == 0
                    else build_chunk_prefill(model, cs, n, ps))
            plan.append((cs, n, step, build_page_scatter(n)))
            cs += n

        def fn(params, pool_k, pool_v, tokens):
            with jax.named_scope("chunked_prefill"):
                logits = None
                for cs, n, step, scatter in plan:
                    batch = {
                        "tokens": tokens[:, cs * ps:(cs + n) * ps],
                        "last_idx": jnp.array([n * ps - 1], jnp.int32),
                    }
                    if cs == 0:
                        logits, k, v = step(params, batch)
                    else:
                        batch["ctx_pages"] = jnp.arange(
                            1, cs + 1, dtype=jnp.int32)
                        logits, k, v = step(params, pool_k, pool_v,
                                            batch)
                    ids = jnp.arange(cs + 1, cs + n + 1,
                                     dtype=jnp.int32)
                    pool_k, pool_v = scatter(pool_k, pool_v, k, v, ids)
                return logits, pool_k, pool_v
        return fn

    return SearchSpace(
        kernel_id="chunked_prefill",
        axes={"chunk_pages": chunks},
        bind=bind,
        args=(params, jnp.zeros(pool_shape, kvd),
              jnp.zeros(pool_shape, kvd), tokens),
        default={"chunk_pages": pp},
        is_valid=is_valid)


SPACES = {
    "flash_attention": flash_attention_space,
    "ssd_scan": ssd_scan_space,
    "paged_attention": paged_attention_space,
    "chunked_prefill": chunked_prefill_space,
}


# ------------------------------------------------- sweep-farm variants

def _pow2_range(lo: int, hi: int) -> Tuple[int, ...]:
    out = []
    v = 1
    while v <= hi:
        if v >= lo:
            out.append(v)
        v *= 2
    return tuple(out)


def sweep_space(kernel_id: str, **shape):
    """Dense sweep-farm variant of a registered space: same ``bind`` /
    validity / default, tile axes widened to every power of two up to
    the problem size. The floor ``max(8, S // 32)`` bounds the grid-step
    product per candidate, which keeps trace capture (the scalar-env
    grid walk enumerates the used ``program_id`` axes) cheap even for
    thousand-candidate sweeps. Rebuilt by name inside sweep workers —
    ``bind`` closures don't pickle across the spawn boundary."""
    if kernel_id == "flash_attention":
        S = int(shape.get("S", 256))
        blocks = _pow2_range(max(8, S // 32), S)
        return flash_attention_space(blocks_q=blocks, blocks_k=blocks,
                                     pipelines=(1, 2, 4, 8), **shape)
    if kernel_id == "ssd_scan":
        L = int(shape.get("L", 256))
        chunks = _pow2_range(max(8, L // 32), L)
        return ssd_scan_space(chunks=chunks, pipelines=(1, 2, 4, 8), **shape)
    if kernel_id == "paged_attention":
        n_pages = int(shape.get("n_pages", 8))
        return paged_attention_space(
            pages_per_step=_pow2_range(1, n_pages), **shape)
    if kernel_id == "chunked_prefill":
        pp = int(shape.get("prompt_pages", 4))
        chunks = tuple(sorted(set(_pow2_range(1, pp)) | {pp}))
        return chunked_prefill_space(chunks=chunks, **shape)
    raise KeyError(f"no sweep space for kernel {kernel_id!r}; "
                   f"known: {tuple(SPACES)}")


def sweep_shapes(kernel_id: str, *, seqs: Tuple[int, ...] = (),
                 heads: Tuple[int, ...] = ()) -> list:
    """Default (sequence x heads) shape grid a sweep iterates — the
    candidate pool is configs x shapes, with calibration transferred
    from the first shape to the rest."""
    if kernel_id == "flash_attention":
        return [{"S": s, "H": h, "D": 32}
                for s in (seqs or (128, 256, 512))
                for h in (heads or (2,))]
    if kernel_id == "ssd_scan":
        return [{"L": s, "H": h}
                for s in (seqs or (128, 256, 512))
                for h in (heads or (2,))]
    if kernel_id == "paged_attention":
        return [{"n_pages": n} for n in (seqs or (8, 16))]
    if kernel_id == "chunked_prefill":
        return [{"prompt_pages": n} for n in (seqs or (2, 4))]
    raise KeyError(f"no sweep shapes for kernel {kernel_id!r}; "
                   f"known: {tuple(SPACES)}")
