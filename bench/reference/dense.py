"""Plain float32 forward of a dense Llama-family decoder (the reference).

Follows the published description of the models the benchmark serves
(Granite 3.0, MiniCPM, Llama): RMSNorm with weight ``1 + tensor``
(``weights.py``'s layout), rotary embedding by half-split rotation,
grouped-query causal softmax attention with scale ``1/sqrt(head_dim)``,
a SwiGLU MLP, a final RMSNorm and logits over the whole vocabulary. No
cache, no paging, no batching across requests and no kernels: each
sequence runs alone over its whole length, every matmul in float32 at
``Precision.HIGHEST``. It imports nothing of the program under test and
rebuilds its weights from the seed one layer at a time
(``weights.layer_weights``), so it takes nothing the program made.

The configuration file lists under ``departures`` the published scalar
multipliers that this forward, like the program, leaves out.

``control=True`` runs a second forward beside it in the step that would
tempt a later change: every linear layer in fp8 (e4m3), weights scaled
per output channel and activations per token, attention in float32.
That forward must fail the benchmark's comparison (``correct.py``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                 # largest finite float8_e4m3fn


def _fp8(x, axes):
    """Round ``x`` to e4m3 with one scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (S, n, hd), positions 0..S-1."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs     # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, w, dims, lin):
    """One decoder layer over one sequence h (S, d)."""
    S = h.shape[0]
    kv, hd = dims["kv"], dims["hd"]
    g = dims["H"] // kv
    x = _rmsnorm(h, w["input_norm"], dims["eps"])
    q = _rope(lin("sd,dnh->snh", x, w["q"], (0,)), dims["theta"])
    k = _rope(lin("sd,dnh->snh", x, w["k"], (0,)), dims["theta"])
    v = lin("sd,dnh->snh", x, w["v"], (0,))
    q = q.reshape(S, kv, g, hd)
    s = jnp.einsum("qkgh,tkh->kgqt", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqt,tkh->qkgh", p, v, precision=HI)
    o = o.reshape(S, dims["H"], hd)
    h = h + lin("snh,nhd->sd", o, w["o"], (0, 1))
    x = _rmsnorm(h, w["post_norm"], dims["eps"])
    a = jax.nn.silu(lin("sd,df->sf", x, w["gate"], (0,)))
    u = lin("sd,df->sf", x, w["up"], (0,))
    return h + lin("sf,fd->sd", a * u, w["down"], (0,))


def _lin_f32(spec, x, w, w_in_axes):
    return jnp.einsum(spec, x, w, precision=HI)


def _lin_fp8(spec, x, w, w_in_axes):
    x_axes = tuple(range(1, x.ndim))           # one scale per token
    return jnp.einsum(spec, _fp8(x, x_axes), _fp8(w, w_in_axes),
                      precision=HI)


class Reference:
    """Logit gaps of served tokens for ``n`` sequences of ``S`` tokens.

    ``gaps(seed, tokens)`` returns, for every position t < S - 1 of each
    sequence, ``served``: how far the reference's logit of token t + 1
    lies below the reference's best logit at t; and with ``control``,
    ``control``: the same gap for the token the fp8 forward puts first.
    Sequences run one at a time inside each layer and logits in blocks of
    ``rows`` positions, so the float32 forward fits beside nothing else
    on one chip at the served lengths.
    """

    def __init__(self, dims: Dict, control: bool = False, rows: int = 256):
        self.dims, self.control, self.rows = dims, control, rows
        self._embed = jax.jit(self._embed_impl)
        self._layer = jax.jit(self._layer_impl)
        self._head = jax.jit(self._head_impl)

    def _streams(self):
        return (("ref", _lin_f32),) + ((("ctl", _lin_fp8),)
                                      if self.control else ())

    def _embed_impl(self, key, tokens):
        g = W.global_weights(key, self.dims)
        h = g["embed"].astype(jnp.float32)[tokens]          # (n, S, d)
        return {name: h for name, _ in self._streams()}

    def _layer_impl(self, key, layer, hs):
        w = {k: v.astype(jnp.float32)
             for k, v in W.layer_weights(key, layer, self.dims).items()}
        out = {}
        for name, lin in self._streams():
            out[name] = jax.lax.map(
                lambda h, lin=lin: _layer(h, w, self.dims, lin), hs[name])
        return out

    def _head_impl(self, key, hs, tokens):
        g = {k: v.astype(jnp.float32)
             for k, v in W.global_weights(key, self.dims).items()}
        head = g["lm_head"] if "lm_head" in g else g["embed"].T   # (d, V)
        n, S = tokens.shape
        nxt = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
        R = self.rows
        assert S % R == 0, (S, R)

        def block(args):
            xs, t = args                   # {name: (R, d)}, (R,)
            out = {}
            ref = jnp.einsum("sd,dv->sv", _rmsnorm(
                xs["ref"], g["final_norm"], self.dims["eps"]), head,
                precision=HI)
            top = ref.max(axis=-1)
            out["served"] = top - jnp.take_along_axis(ref, t[:, None],
                                                      axis=1)[:, 0]
            if self.control:
                ctl = _lin_fp8("sd,dv->sv", _rmsnorm(
                    xs["ctl"], g["final_norm"], self.dims["eps"]), head,
                    (0,))
                pick = jnp.argmax(ctl, axis=-1)
                out["control"] = top - jnp.take_along_axis(
                    ref, pick[:, None], axis=1)[:, 0]
            return out

        def seq(args):
            h, t = args                    # {name: (S, d)}, (S,)
            blocks = ({k: v.reshape(S // R, R, -1) for k, v in h.items()},
                      t.reshape(S // R, R))
            return jax.tree_util.tree_map(lambda a: a.reshape(S),
                                          jax.lax.map(block, blocks))

        return jax.lax.map(seq, (hs, nxt))

    def gaps(self, seed: int, tokens: np.ndarray) -> Dict[str, np.ndarray]:
        key = W.seed_key(seed)
        tokens = jnp.asarray(tokens, jnp.int32)
        hs = self._embed(key, tokens)
        for layer in range(self.dims["L"]):
            hs = self._layer(key, jnp.int32(layer), hs)
        out = self._head(key, hs, tokens)
        return {k: np.asarray(v)[:, :-1] for k, v in out.items()}
