"""Parameter schema machinery + core layers (RMSNorm, RoPE/M-RoPE, MLP).

Parameters are described by a nested-dict *schema* of ``Param`` records
(shape, logical axes, initializer). The same schema yields:

- ``materialize(schema, key, dtype)``  -> concrete params (smoke tests, examples)
- ``abstract(schema, dtype)``          -> ShapeDtypeStruct tree (dry-run)
- ``axes_tree(schema)``                -> logical-axis tuples (sharding rules)

Logical axis names used across the code base:
  vocab, embed, q_heads, kv_heads, q_per_kv, head_dim, ff, expert,
  ssm_inner, ssm_state, ssm_heads, ssm_head_dim, conv, layers
(resolution to mesh axes lives in ``repro.distributed.sharding``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard


class Param(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Any, ...]          # logical axis names (len == len(shape))
    init: str = "normal"           # normal | zeros | ones | embed | ssm_a | ssm_dt
    scale: float = 1.0             # fan-in scaling multiplier


def _is_param(x) -> bool:
    return isinstance(x, Param)


def map_schema(fn, schema):
    """Map ``fn`` over every Param leaf of a nested-dict schema."""
    return jax.tree_util.tree_map(fn, schema, is_leaf=_is_param)


def abstract(schema, dtype) -> Any:
    return map_schema(lambda p: jax.ShapeDtypeStruct(p.shape, dtype), schema)


def axes_tree(schema) -> Any:
    return map_schema(lambda p: p.axes, schema)


def _init_leaf(p: Param, key, dtype):
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "ssm_a":
        # A_log init: log of uniform [1, 16] (mamba2 convention)
        u = jax.random.uniform(key, p.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if p.init == "ssm_dt":
        # dt bias: inverse softplus of uniform-log [1e-3, 1e-1]
        u = jax.random.uniform(key, p.shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    fan_in = p.shape[0] if p.init == "embed" else (
        math.prod(p.shape[:-1]) if len(p.shape) > 1 else p.shape[0])
    std = p.scale / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, p.shape, jnp.float32) * std).astype(dtype)


def materialize(schema, key, dtype) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(schema, is_leaf=_is_param)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(p, k, dtype) for p, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def stack_schema(schema, n: int, axis_name="layers"):
    """Prepend a stacked (scan) dimension to every Param in a schema."""
    return map_schema(
        lambda p: Param((n,) + p.shape, (axis_name,) + p.axes, p.init, p.scale),
        schema)


# ---------------------------------------------------------------- layers

def rmsnorm(x, scale, eps: float):
    """RMSNorm with f32 statistics but an input-dtype multiply path.

    Multiplying in f32 (the common x.astype(f32) * rsqrt pattern) makes
    the BACKWARD cotangent of the residual stream f32 — every sequence-
    parallel boundary collective then moves 2x the bytes (§Perf
    iteration: the dominant all-gather/all-reduce class on train cells).
    """
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + scale).astype(x.dtype)


def rmsnorm_schema(d: int) -> Param:
    return Param((d,), ("embed",), init="zeros")


# ------------------------------------------------------------------ RoPE

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, n_heads, head_dim); positions: broadcastable to (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    sin = jnp.sin(angles)[..., None, :]                 # (..., seq, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: Tuple[int, int, int]):
    """Qwen2-VL M-RoPE. x: (..., seq, n, hd); positions3: (3, ..., seq).

    The rotary half-dim is partitioned into (temporal, h, w) sections; each
    section rotates by its own position stream.
    """
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(hd, theta)                       # (half,)
    # build per-frequency position selector
    section_id = jnp.repeat(
        jnp.arange(3), jnp.array(sections), total_repeat_length=half)  # (half,)
    # angles_k for each stream k: (..., seq, half)
    angles = positions3[..., None].astype(jnp.float32) * freqs  # (3, ..., seq, half)
    sel = jax.nn.one_hot(section_id, 3, dtype=jnp.float32)      # (half, 3)
    angles = jnp.einsum("k...f,fk->...f", angles, sel)
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- MLP

def mlp_schema(d: int, ff: int, use_bias: bool) -> Dict[str, Param]:
    s: Dict[str, Param] = {
        "wi": Param((d, ff), ("embed", "ff")),
        "wg": Param((d, ff), ("embed", "ff")),
        "wo": Param((ff, d), ("ff", "embed")),
    }
    if use_bias:
        s["bi"] = Param((ff,), ("ff",), init="zeros")
        s["bg"] = Param((ff,), ("ff",), init="zeros")
        s["bo"] = Param((d,), ("embed",), init="zeros")
    return s


def mlp_apply(params, x):
    """SwiGLU MLP. x: (..., d)."""
    h = jnp.einsum("...d,df->...f", x, params["wi"])
    g = jnp.einsum("...d,df->...f", x, params["wg"])
    if "bi" in params:
        h = h + params["bi"]
        g = g + params["bg"]
    h = jax.nn.silu(g) * h
    h = shard(h, "batch", "seq", "ff")
    out = jnp.einsum("...f,fd->...d", h, params["wo"])
    if "bo" in params:
        out = out + params["bo"]
    return out
