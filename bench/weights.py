"""Seeded random weights of the dense family, in one canonical layout.

The harness and the plain reference both draw every tensor from here,
each on its own: the harness turns them into the program's parameter
tree (``families/dense.py``'s ``program_params``), and the reference
(``reference/dense.py``) rebuilds one layer at a time from the same
seed. Neither takes the other's arrays.

Canonical tensors, per layer ``l`` (``dims`` from ``families/dense.py``):

    input_norm (d,)        post_norm (d,)      RMSNorm weight = 1 + tensor
    q (d, H, hd)   k (d, kv, hd)   v (d, kv, hd)   o (H, hd, d)
    gate (d, ff)   up (d, ff)      down (ff, d)     SwiGLU: silu(x@gate)*(x@up)

and once: ``embed`` (V, d), ``final_norm`` (d,), and ``lm_head`` (d, V)
when the embeddings are not tied. Each tensor is
``normal(key(seed, l, name)) * std`` rounded to ``dtype``: a pure
function of the seed, the layer and the name, so a layer can be rebuilt
alone. Stds are set so that every projection sees unit-variance inputs
and the logits spread by a few units, as a trained model's do.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

LAYER_NAMES = ("input_norm", "q", "k", "v", "o", "post_norm", "gate", "up",
               "down")
NORM_STD = 0.1          # spread of the RMSNorm weights around 1
EMBED_STD = 0.05        # logits spread ~ sqrt(d) * EMBED_STD


def seed_key(seed: int):
    """A key from a seed of any size: 32 bits at a time (``jax.random.key``
    alone drops the bits above 64 and wraps large ints)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    seed >>= 32
    while seed:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
    return key


def _shapes(dims) -> Dict[str, tuple]:
    d, H, kv, hd, ff = (dims[k] for k in ("d", "H", "kv", "hd", "ff"))
    return {"input_norm": (d,), "q": (d, H, hd), "k": (d, kv, hd),
            "v": (d, kv, hd), "o": (H, hd, d), "post_norm": (d,),
            "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def _std(name: str, dims) -> float:
    if name.endswith("norm"):
        return NORM_STD
    if name == "o":
        return 1.0 / math.sqrt(dims["H"] * dims["hd"])
    if name == "down":
        return 1.0 / math.sqrt(dims["ff"])
    return 1.0 / math.sqrt(dims["d"])


def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_weights(key, layer, dims, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Layer ``layer``'s tensors (``layer`` may be traced)."""
    lk = jax.random.fold_in(key, layer)
    return {name: _draw(jax.random.fold_in(lk, i), shape, _std(name, dims),
                        dtype)
            for i, (name, shape) in enumerate(_shapes(dims).items())}


def global_weights(key, dims, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Embedding, final norm and (untied) output head."""
    gk = jax.random.fold_in(key, dims["L"])       # past every layer index
    d, V = dims["d"], dims["V"]
    out = {"embed": _draw(jax.random.fold_in(gk, 0), (V, d), EMBED_STD,
                          dtype),
           "final_norm": _draw(jax.random.fold_in(gk, 1), (d,), NORM_STD,
                               dtype)}
    if not dims["tied"]:
        out["lm_head"] = _draw(jax.random.fold_in(gk, 2), (d, V),
                               EMBED_STD, dtype)
    return out


def stacked_layers(key, dims, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Every layer's tensors stacked on a leading (L,) axis, one layer at
    a time (``lax.map``) so that only one layer's float32 draw is live."""
    return jax.lax.map(lambda l: layer_weights(key, l, dims, dtype),
                       jnp.arange(dims["L"], dtype=jnp.int32))
