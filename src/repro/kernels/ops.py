"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode (the
kernel body runs as traced JAX ops — bit-identical math, CPU-validatable),
which is how the test suite sweeps shapes/dtypes against ``ref.py``. On
the TPU they compile; any other backend is an error, so a run meant for
the chip never lands in the interpreter unnoticed.

Tile/pipeline arguments left as ``None`` resolve through the tuned-
defaults registry (``repro.kernels.tuning``), so a ``repro.tune`` run
(or ``serve.py --autotune``) transparently re-tiles the model's kernels.
"""
from __future__ import annotations

import functools
import math

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import tuning


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend in ("tpu", "cpu"):
        return backend == "cpu"
    raise RuntimeError(f"Pallas TPU kernels run compiled on 'tpu' or "
                       f"interpreted on 'cpu'; the default backend is "
                       f"{backend!r}")


def _fit_block(size: int, want: int) -> int:
    """Largest usable tile <= ``want`` for an axis of length ``size``:
    clamp, then drop to gcd so the tile always divides the axis (tuned
    configs must stay usable at shapes they weren't tuned for)."""
    b = min(want, size)
    return b if size % b == 0 else math.gcd(size, b)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "pipeline", "with_probe",
                                             "interpret"))
def _flash_jit(q, k, v, *, causal, block_q, block_k, pipeline, with_probe,
               interpret):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, pipeline=pipeline,
                               with_probe=with_probe, interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int | None = None, block_k: int | None = None,
                    pipeline: int | None = None, with_probe: bool = False,
                    interpret: bool | None = None):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D). See kernels.flash_attention.

    ``block_q``/``block_k``/``pipeline`` default to the tuned registry
    (falling back to 128/128/1). Registry-derived values are fitted to
    shapes they weren't tuned for (gcd tile, pipeline dropped); explicit
    arguments are passed through untouched, so an invalid combination
    still fails loudly in the kernel."""
    if interpret is None:
        interpret = _interpret_default()
    S = q.shape[2]
    if block_q is None:
        block_q = _fit_block(S, tuning.tuned_value(
            "flash_attention", "block_q", _fa.DEFAULT_BLOCK_Q))
    if block_k is None:
        block_k = _fit_block(S, tuning.tuned_value(
            "flash_attention", "block_k", _fa.DEFAULT_BLOCK_K))
    if pipeline is None:
        pipeline = tuning.tuned_value("flash_attention", "pipeline", 1)
        if (S // min(block_k, S)) % pipeline:
            pipeline = 1
    return _flash_jit(q, k, v, causal=causal, block_q=block_q,
                      block_k=block_k, pipeline=pipeline,
                      with_probe=with_probe, interpret=interpret)


def flash_attention_gqa(q, k, v, *, causal: bool = True,
                        interpret: bool | None = None):
    """Model-layout adapter: q (B,S,kv,qpk,hd); k,v (B,S,kv,hd)."""
    B, S, KV, G, HD = q.shape
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B, KV * G, S, HD)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    o = flash_attention(qf, kf, vf, causal=causal, interpret=interpret)
    return o.reshape(B, KV, G, S, HD).transpose(0, 3, 1, 2, 4)


@functools.partial(jax.jit, static_argnames=("chunk", "pipeline", "h_per_g",
                                             "interpret"))
def _ssd_jit(x, a, b, c, *, chunk, pipeline, h_per_g, interpret):
    xk = x.transpose(0, 2, 1, 3)
    ak = a.transpose(0, 2, 1)
    bk = b.transpose(0, 2, 1, 3)
    ck = c.transpose(0, 2, 1, 3)
    y = _ssd.ssd_scan(xk, ak, bk, ck, chunk=chunk, pipeline=pipeline,
                      interpret=interpret)
    return y.transpose(0, 2, 1, 3)


def resolve_ssd_chunk(L: int, default: int = 256) -> int:
    """Tuned-registry resolution for ``ssd_scan``'s chunk, clamped to
    the sequence — the single place the 'explicit > tuned > default'
    policy lives. Callers that pad to a multiple of the result (the
    model layer) use this directly; unpadded calls additionally fit it
    to divide ``L`` (see ``ssd_scan``)."""
    return min(tuning.tuned_value("ssd_scan", "chunk", default), L)


def ssd_scan(x, a, b, c, *, chunk: int | None = None,
             pipeline: int | None = None, h_per_g: int | None = None,
             interpret: bool | None = None):
    """Model-layout adapter: x (B,L,H,P); a (B,L,H); b,c (B,L,G,N).

    ``chunk``/``pipeline`` default to the tuned registry (256/1);
    registry-derived values are fitted to the sequence, explicit
    arguments pass through untouched (invalid ones fail loudly).
    Returns y (B, L, H, P).
    """
    if interpret is None:
        interpret = _interpret_default()
    L = x.shape[1]
    if chunk is None:
        chunk = _fit_block(L, resolve_ssd_chunk(L))
    if pipeline is None:
        pipeline = tuning.tuned_value("ssd_scan", "pipeline", 1)
        if chunk % pipeline:
            pipeline = 1
    return _ssd_jit(x, a, b, c, chunk=chunk, pipeline=pipeline,
                    h_per_g=h_per_g, interpret=interpret)
