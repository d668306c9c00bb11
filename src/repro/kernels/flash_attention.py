"""Causal GQA flash attention — Pallas TPU kernel.

TPU-native adaptation (HBM->VMEM tiling, MXU-aligned 128x128 blocks,
f32 running-softmax state in VMEM scratch, sequential kv grid dim).

RealProbe tie-in: the kernel optionally emits a **decoupled probe
output** — per (batch, head, q-block) counters of kv blocks visited vs
actually computed (causal skip). Exactly like the paper's profiler IP,
the counters live in separate storage, are written on "control events"
only (block entry), and do not touch the datapath, so enabling them
cannot change the attention output.

Grid: (B, H, num_q_blocks, num_kv_blocks); the kv dim is innermost and
sequential ("arbitrary") so the scratch accumulator carries across it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = float("-inf")

_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  block_q: int, block_k: int, pipeline: int, causal: bool,
                  sm_scale: float, with_probe: bool):
    probe_ref = rest[0] if with_probe else None
    acc_ref, m_ref, l_ref = rest[-3:]
    iq = pl.program_id(2)
    ig = pl.program_id(3)            # kv DMA-group index (pipeline blocks)
    ng = pl.num_programs(3)
    nk = ng * pipeline               # total kv blocks

    # named scopes below are RealProbe grid-step markers: pure trace
    # metadata (the emitted equations are identical with probing off),
    # picked up by hierarchy extraction under ProbeConfig(kernel_probes)
    with jax.named_scope("init"):
        @pl.when(ig == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            if with_probe:
                probe_ref[...] = jnp.zeros_like(probe_ref)

    # each grid step fetches `pipeline` kv blocks in one DMA group and
    # runs the MXU tiles over them back to back (statically unrolled)
    for p in range(pipeline):
        with jax.named_scope("kv_block"):
            ik = ig * pipeline + p
            # causal skip decided by the q block's LAST row: any kv block
            # starting at or before it intersects the causal triangle
            should_compute = ((iq + 1) * block_q - 1 >= ik * block_k) \
                if causal else True

            if with_probe:
                # control-event counters in one (8, 128) tile, row 0:
                # lane 0 = blocks visited, lane 1 = blocks computed
                tile = probe_ref.shape[2:]
                row = jax.lax.broadcasted_iota(jnp.int32, tile, 0)
                lane = jax.lax.broadcasted_iota(jnp.int32, tile, 1)
                computed = jnp.where(should_compute, 1, 0)
                probe_ref[0, 0] += jnp.where(
                    row == 0, jnp.where(lane == 0, 1,
                                        jnp.where(lane == 1, computed, 0)),
                    0).astype(probe_ref.dtype)

            @pl.when(should_compute)
            def _compute(p=p, ik=ik):
                q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
                k = k_ref[0, 0, p * block_k:(p + 1) * block_k].astype(
                    jnp.float32)                               # (bk, D)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if causal:
                    q_pos = iq * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 0)
                    k_pos = ik * block_k + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, block_k), 1)
                    s = jnp.where(q_pos >= k_pos, s, NEG_INF)
                m_prev = m_ref[...]                            # (bq, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
                p_ = jnp.exp(s - m_safe)
                corr = jnp.where(jnp.isneginf(m_prev), 0.0,
                                 jnp.exp(m_prev - m_safe))
                l_ref[...] = l_ref[...] * corr + p_.sum(axis=-1,
                                                        keepdims=True)
                v = v_ref[0, 0, p * block_k:(p + 1) * block_k].astype(
                    jnp.float32)                               # (bk, D)
                pv = jax.lax.dot_general(
                    p_, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc_ref[...] = acc_ref[...] * corr + pv
                m_ref[...] = m_new

    with jax.named_scope("finalize"):
        # last group holding the causal diagonal of this q block — based
        # on the block's LAST row (its first row under-counts when
        # bq > bk)
        last_g = (jnp.minimum(((iq + 1) * block_q - 1) // block_k, nk - 1)
                  // pipeline) if causal else ng - 1

        @pl.when(ig == last_g)
        def _finalize():
            l = jnp.maximum(l_ref[...], 1e-37)
            o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    pipeline: int = 1,
                    with_probe: bool = False,
                    interpret: bool = False):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D), H % Hkv == 0.

    ``pipeline`` is the kv software-pipelining depth: each grid step
    DMAs ``pipeline`` consecutive kv blocks into VMEM and sweeps the
    MXU tiles over them (fewer, larger transfers; same math).

    Returns (B, H, S, D) [, probe (B, H, nq, 2) int32 if with_probe].
    """
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    if H % Hkv:
        raise ValueError(f"H {H} % Hkv {Hkv}")
    qpk = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S {S} not divisible by blocks ({block_q},{block_k})")
    if pipeline < 1:
        raise ValueError(f"pipeline {pipeline} < 1")
    nq, nk = S // block_q, S // block_k
    if nk % pipeline:
        raise ValueError(f"kv blocks {nk} not divisible by pipeline "
                         f"{pipeline}")
    ng = nk // pipeline
    sm_scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, pipeline=pipeline,
        causal=causal, sm_scale=sm_scale, with_probe=with_probe)

    out_shape = [jax.ShapeDtypeStruct((B, H, S, D), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, i, j: (b, h, i, 0))]
    if with_probe:
        # one (8, 128) int32 tile per q block keeps the probe block on
        # the TPU tiling; the counters are row 0, lanes 0-1
        out_shape.append(jax.ShapeDtypeStruct((B, H, nq * 8, 128),
                                              jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1, 8, 128),
                                      lambda b, h, i, j: (b, h, i, 0)))

    grid = (B, H, nq, ng)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k * pipeline, D),
                         lambda b, h, i, j: (b, h // qpk, j, 0)),
            pl.BlockSpec((1, 1, block_k * pipeline, D),
                         lambda b, h, i, j: (b, h // qpk, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="flash_kernel",
    )(q, k, v)
    if with_probe:
        out, probe = res
        return out, probe.reshape(B, H, nq, 8, 128)[:, :, :, 0, :2]
    return res[0]
