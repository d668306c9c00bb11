"""Pure-jnp oracles for the Pallas kernels.

These are the cycle-level ground truth the kernels are validated against
(shape/dtype sweeps in tests/test_kernels.py) — the same role the ILA
plays for RealProbe in the paper.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True):
    """Naive softmax attention.

    q: (B, H, S, D); k, v: (B, Hkv, S, D) with H % Hkv == 0.
    Returns (B, H, S, D) in q.dtype; f32 softmax internally.
    """
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(D))
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_attention_ref(q, pool_k, pool_v, pages, pos):
    """Dense-gather paged decode attention.

    q: (B, kv_heads, q_per_kv, head_dim); pool_k, pool_v: (num_pool_pages,
    page_size, kv_heads, head_dim); pages: (B, n_pages) page-table rows;
    pos: (B,) current positions (slots > pos are masked). Returns
    (B, kv_heads, q_per_kv, head_dim) float32. bf16 operands with f32
    accumulation, one global softmax: the math the engine's XLA decode
    runs, and the kernel's exactness contract.
    """
    B, kv, _, hd = q.shape
    s_max = pages.shape[1] * pool_k.shape[1]
    kd = pool_k[pages].reshape(B, s_max, kv, hd)
    vd = pool_v[pages].reshape(B, s_max, kv, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", q.astype(jnp.bfloat16),
                   kd.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(hd))
    mask = jnp.arange(s_max)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    return jnp.einsum("bkgs,bskh->bkgh",
                      (p / p.sum(-1, keepdims=True)).astype(jnp.bfloat16),
                      vd.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def ssd_ref(x, a, b, c):
    """Sequential (exact) SSD recurrence.

    x: (B, H, L, P) — discretized inputs (x * dt)
    a: (B, H, L)    — discretized log decay (A * dt)
    b, c: (B, G, L, N) with H % G == 0
    Returns y (B, H, L, P) f32, final_state (B, H, P, N) f32.
    """
    B, H, L, P = x.shape
    G, N = b.shape[1], b.shape[3]
    rep = H // G
    b = jnp.repeat(b, rep, axis=1)          # (B, H, L, N)
    c = jnp.repeat(c, rep, axis=1)

    def step(state, inp):
        x_t, a_t, b_t, c_t = inp            # (B,H,P) (B,H) (B,H,N) (B,H,N)
        da = jnp.exp(a_t.astype(jnp.float32))[..., None, None]
        state = state * da + jnp.einsum("bhp,bhn->bhpn",
                                        x_t.astype(jnp.float32),
                                        b_t.astype(jnp.float32))
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t.astype(jnp.float32))
        return state, y_t

    init = jnp.zeros((B, H, P, N), jnp.float32)
    xs = (x.transpose(2, 0, 1, 3), a.transpose(2, 0, 1),
          b.transpose(2, 0, 1, 3), c.transpose(2, 0, 1, 3))
    final, ys = jax.lax.scan(step, init, xs)
    return ys.transpose(1, 2, 0, 3), final
