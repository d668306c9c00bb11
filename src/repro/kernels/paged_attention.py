"""Paged-attention decode kernel — Pallas TPU, bit-exact by construction.

Single-token GQA decode over a paged KV cache: each sequence's cache
lives in non-contiguous fixed-size pages of a shared pool, addressed
through a per-request page table row. The page indices are **scalar
prefetch** operands (``pltpu.PrefetchScalarGridSpec``), so the
BlockSpec index maps chase the page table and the pipeline DMAs each
page of the pool directly into VMEM — the dense (B, S_max) gather that
the XLA fallback materializes in HBM never exists.

Exactness contract (the serving engine's bit-identity guarantee rests
on this): the kernel does NOT use streaming flash softmax. It stages
the pages into a VMEM scratch shaped exactly like the dense gather and
then runs the *same einsum shapes and the same global softmax* as the
reference ``models.attention.attn_decode`` — equal-length reductions
over equal values produce equal floats, so the output is bit-identical
to the unpaged reference (asserted in tests/test_engine.py). Slots
beyond ``pos`` contribute exact ``exp(-inf) = 0.0``, which also makes
stale contents of reused pool pages harmless.

RealProbe tie-in: the copy/attend phases sit under named scopes so
``ProbeConfig(kernel_probes=...)`` attributes per-grid-step cycles to
page staging vs attend math, and ``pages_per_step`` (pages DMA'd per
grid step — the pipelining depth) is a DSE axis tuned by
``kernels.search_spaces.paged_attention_space``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_PAGES_PER_STEP = 1

_SEMANTICS = ("parallel", "arbitrary")


def _paged_kernel(pages_ref, pos_ref, q_ref, *rest, pages_per_step: int,
                  page_size: int, n_pages: int, sm_scale: float):
    k_refs = rest[:pages_per_step]
    v_refs = rest[pages_per_step:2 * pages_per_step]
    o_ref = rest[2 * pages_per_step]
    k_scr, v_scr = rest[2 * pages_per_step + 1:]
    # every program_id/num_programs read happens at the kernel's top
    # level, outside the pl.when body
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_steps = pl.num_programs(1)
    s_max = n_pages * page_size
    kv, g, _ = q_ref.shape[1:]

    with jax.named_scope("copy_pages"):
        # one grid step stages `pages_per_step` pool pages into the
        # per-kv-head (kv, s_max, hd) VMEM scratch (statically unrolled)
        for i in range(pages_per_step):
            off = pl.multiple_of((j * pages_per_step + i) * page_size,
                                 page_size)
            for h in range(kv):
                k_scr[h, pl.ds(off, page_size), :] = k_refs[i][0, :, h, :]
                v_scr[h, pl.ds(off, page_size), :] = v_refs[i][0, :, h, :]

    with jax.named_scope("attend"):
        @pl.when(j == n_steps - 1)
        def _attend():
            # dense-length global softmax, one 2-D dot pair per kv head:
            # the same products and reduction lengths as the XLA
            # reference's batched einsums — not flash
            mask = jax.lax.broadcasted_iota(
                jnp.int32, (g, s_max), 1) <= pos_ref[b]
            for h in range(kv):
                s = jax.lax.dot_general(
                    q_ref[0, h].astype(jnp.bfloat16),
                    k_scr[h].astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                s = jnp.where(mask, s, -jnp.inf)
                m = s.max(axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = p.sum(axis=-1, keepdims=True)
                o_ref[0, h] = jax.lax.dot_general(
                    (p / l).astype(jnp.bfloat16),
                    v_scr[h].astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)


def paged_attention(q, pool_k, pool_v, pages, pos, *,
                    pages_per_step: int = DEFAULT_PAGES_PER_STEP,
                    interpret: bool = False):
    """Paged single-token GQA decode attention.

    q:       (B, kv_heads, q_per_kv, head_dim) — current-token queries
    pool_k:  (num_pool_pages, page_size, kv_heads, head_dim)
    pool_v:  same shape as pool_k
    pages:   (B, n_pages) int32 page-table rows into the pool
    pos:     (B,) int32 current position (slots > pos are masked)

    ``pages_per_step`` pages are fetched per grid step (the pool is
    bound once per page slot, so page-table rows stay arbitrary — no
    contiguity requirement on the allocator).

    Returns (B, kv_heads, q_per_kv, head_dim) float32 — bit-identical
    to the dense-gather reference over ``pool[pages]``.
    """
    B, kv, g, hd = q.shape
    page_size = pool_k.shape[1]
    n_pages = pages.shape[1]
    if pages_per_step < 1 or n_pages % pages_per_step:
        raise ValueError(f"pages_per_step {pages_per_step} must divide "
                         f"page-table width {n_pages}")
    n_steps = n_pages // pages_per_step
    sm_scale = 1.0 / math.sqrt(hd)

    def page_map(i):
        def index_map(b, j, pages_ref, pos_ref):
            del pos_ref
            return (pages_ref[b, j * pages_per_step + i], 0, 0, 0)
        return index_map

    def q_map(b, j, pages_ref, pos_ref):
        del pages_ref, pos_ref
        return (b, 0, 0, 0)

    page_block = (1, page_size, kv, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_steps),
        in_specs=(
            [pl.BlockSpec((1, kv, g, hd), q_map)]
            + [pl.BlockSpec(page_block, page_map(i))
               for i in range(pages_per_step)]
            + [pl.BlockSpec(page_block, page_map(i))
               for i in range(pages_per_step)]
        ),
        out_specs=pl.BlockSpec((1, kv, g, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((kv, n_pages * page_size, hd), pool_k.dtype),
            pltpu.VMEM((kv, n_pages * page_size, hd), pool_v.dtype),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, pages_per_step=pages_per_step, page_size=page_size,
        n_pages=n_pages, sm_scale=sm_scale)
    pools = [pool_k] * pages_per_step + [pool_v] * pages_per_step
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv, g, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="paged_kernel",
    )(pages, pos, q, *pools)
