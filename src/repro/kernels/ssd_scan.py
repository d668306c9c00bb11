"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

TPU-native adaptation of the SSD algorithm [arXiv:2405.21060]: the
sequence is tiled into VMEM-resident chunks; each grid step computes the
intra-chunk quadratic term on the MXU and carries the running SSM state
(P x N, f32) in VMEM scratch across the *sequential* chunk grid
dimension — the TPU analogue of the GPU kernel's cross-CTA state passing
(no TPU equivalent of grid-sync exists; the sequential-innermost-grid-dim
contract replaces it, as documented in DESIGN.md).

Grid: (B, H, n_chunks), chunk dim innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _ssd_kernel(x_ref, acs_ref, b_ref, c_ref, y_ref, state_scratch,
                *, chunk: int, pipeline: int):
    ci = pl.program_id(2)

    # named scopes are RealProbe grid-step markers (trace metadata only;
    # identical equations with probing off) — see core.kernelprobe
    with jax.named_scope("init"):
        @pl.when(ci == 0)
        def _init():
            state_scratch[...] = jnp.zeros_like(state_scratch)

    # the VMEM tile is `chunk` long; the quadratic intra-chunk term is
    # evaluated over `pipeline` sub-chunks of length Q = chunk/pipeline,
    # carrying the SSM state across them — O(chunk^2)/pipeline FLOPs at
    # unchanged DMA granularity
    sub = chunk // pipeline
    for p in range(pipeline):
        with jax.named_scope("sub_chunk"):
            lo, hi = p * sub, (p + 1) * sub
            x = x_ref[0, 0, lo:hi].astype(jnp.float32)     # (Q, P)
            # sub-chunk-local cumsum of a, as a column (from the wrapper)
            a_cs = acs_ref[0, 0, lo:hi]                    # (Q, 1)
            b = b_ref[0, 0, lo:hi].astype(jnp.float32)     # (Q, N)
            c = c_ref[0, 0, lo:hi].astype(jnp.float32)     # (Q, N)

            # intra-chunk:
            #   y_diag[q] = sum_{k<=q} exp(a_cs[q]-a_cs[k]) (c_q.b_k) x_k
            cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            qi = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
            ki = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
            # the row form of a_cs, read off the diagonal (exact: one
            # nonzero term per column)
            a_row = jnp.sum(jnp.where(qi == ki, a_cs, 0.0), axis=0,
                            keepdims=True)                 # (1, Q)
            decay = jnp.where(qi >= ki, jnp.exp(a_cs - a_row), 0.0)
            y_diag = jax.lax.dot_general(cb * decay, x,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
            # inter-chunk: y_off[q] = exp(a_cs[q]) * c_q . state ((P, N))
            state = state_scratch[...]
            y_off = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
            y_off = y_off * jnp.exp(a_cs)
            y_ref[0, 0, lo:hi] = (y_diag + y_off).astype(y_ref.dtype)
            # state': exp(a_cs[-1]) * state + sum_k d_k x_k b_k^T
            a_last = a_cs[sub - 1:sub]                     # (1, 1)
            decay_states = jnp.exp(a_last - a_cs)          # (Q, 1)
            xb = jax.lax.dot_general(x * decay_states, b,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            # exp(a_cs[-1]) as a (1, N) row, read off the last row of a
            # lane broadcast: Mosaic cannot broadcast (1, 1) to (P, N)
            n_state = xb.shape[1]
            rows = jax.lax.broadcasted_iota(jnp.int32, (sub, n_state), 0)
            last_row = jnp.sum(
                jnp.where(rows == sub - 1,
                          jnp.broadcast_to(a_cs, (sub, n_state)), 0.0),
                axis=0, keepdims=True)                     # (1, N)
            state_scratch[...] = state * jnp.exp(last_row) + xb


def ssd_scan(x, a, b, c, *, chunk: int = 256, pipeline: int = 1,
             interpret: bool = False):
    """x: (B, H, L, P); a: (B, H, L); b, c: (B, G, L, N), H % G == 0.

    ``pipeline`` subdivides each VMEM-resident chunk into that many
    sequentially-scanned sub-chunks (state carried in scratch), cutting
    the quadratic intra-chunk FLOPs without shrinking the DMA tile.

    Returns y (B, H, L, P) in x.dtype. L % chunk and chunk % pipeline
    must be 0.
    """
    B, H, L, P = x.shape
    G, N = b.shape[1], b.shape[3]
    if H % G:
        raise ValueError(f"H {H} % G {G}")
    e = H // G
    if L % chunk:
        raise ValueError(f"L {L} % chunk {chunk}")
    if pipeline < 1 or chunk % pipeline:
        raise ValueError(f"chunk {chunk} % pipeline {pipeline}")
    nc = L // chunk
    sub = chunk // pipeline
    # the per-sub-chunk cumsum of a runs here in XLA: it reaches the
    # kernel as a (L, 1) column, a block layout the TPU tiling accepts
    a_cs = jnp.cumsum(a.astype(jnp.float32).reshape(B, H, L // sub, sub),
                      axis=-1).reshape(B, H, L, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, pipeline=pipeline)
    grid = (B, H, nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bi, h, ci: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, h, ci: (bi, h // e, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, h, ci: (bi, h // e, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P),
                               lambda bi, h, ci: (bi, h, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="ssd_kernel",
    )(x, a_cs, b, c)
