"""Every use the benchmark makes of the program under test.

The benchmark drives ``repro.engine.InferenceEngine`` through its public
surface: ``submit``, ``run``, ``drain``, ``stats()``, the ``bus=`` hook
(a ``repro.telemetry.TelemetryBus`` whose ``publish_phase`` runs after
each step is dispatched, and ``publish_request`` as a request finishes)
and the ``Request`` objects. The engine has no per-token callback, so
this file reaches into it at exactly these places, each listed in
PERF.md; a change to any of them breaks the benchmark here first:

- ``InferenceEngine._waiting``: the queue ``submit`` appends to; its
  last element is the ``Request`` just submitted.
- ``Request.out_tokens``: replaced, before the request runs, by a list
  that stamps the host clock on each ``append`` (the engine appends each
  token once it is on the host).
- ``InferenceEngine._active`` / ``_prefilling``: with ``_waiting``, the
  requests the engine still holds (a check that none is left behind).
- ``InferenceEngine.pool_k`` / ``pool_v``: the KV pool, deleted in
  ``release`` so that the reference finds the chip's memory free.
- ``Model.abstract_params()``: the parameter tree the weights must fill.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.registry import get_config  # noqa: E402
from repro.engine import EngineConfig, InferenceEngine  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.telemetry import TelemetryBus  # noqa: E402

import weights as W  # noqa: E402

# published key -> ModelConfig field it must equal
_SAME = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
         "num_attention_heads": "num_heads",
         "num_key_value_heads": "num_kv_heads",
         "intermediate_size": "d_ff", "vocab_size": "vocab_size",
         "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
         "tie_word_embeddings": "tie_embeddings"}


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file: the preset
    it names, with its ``program_overrides``, checked key by key against
    the published numbers the file holds."""
    cfg = get_config(conf["program_preset"]).replace(
        **conf.get("program_overrides", {}))
    pub = conf["config"]
    for key, field in _SAME.items():
        if pub[key] != getattr(cfg, field):
            raise ValueError(f"{conf['name']}: published {key}={pub[key]} "
                             f"but the program runs {field}="
                             f"{getattr(cfg, field)}")
    if cfg.resolved_head_dim != W.dense_dims(pub)["hd"]:
        raise ValueError(f"{conf['name']}: head_dim differs")
    if cfg.family != "dense" or cfg.use_bias or cfg.pos_emb != "rope":
        raise ValueError(f"{conf['name']}: not the dense family the "
                         "reference implements")
    return cfg


def program_params(model: Model, dims: Dict, seed: int):
    """The program's parameter tree filled from ``weights.py``, made on
    the device in one jitted call, in the configuration's param dtype."""
    cfg = model.cfg
    want = model.abstract_params()
    dtype = jnp.dtype(cfg.param_dtype)
    Hp, H, Vp = cfg.resolved_padded_heads, cfg.num_heads, \
        cfg.padded_vocab_size

    def make(key):
        lw = W.stacked_layers(key, dims, dtype)
        g = W.global_weights(key, dims, dtype)
        pad_h = [(0, 0), (0, 0), (0, Hp - H), (0, 0)]
        attn = {"wq": jnp.pad(lw["q"], pad_h), "wk": lw["k"],
                "wv": lw["v"],
                "wo": jnp.pad(lw["o"], [(0, 0), (0, Hp - H), (0, 0),
                                        (0, 0)])}
        tree = {"embed": jnp.pad(g["embed"], [(0, Vp - dims["V"]), (0, 0)]),
                "stack": {"layers": {
                    "ln1": lw["input_norm"], "attn": attn,
                    "ln2": lw["post_norm"],
                    "mlp": {"wg": lw["gate"], "wi": lw["up"],
                            "wo": lw["down"]}},
                    "ln_f": g["final_norm"]}}
        if "lm_head" in g:
            tree["unembed"] = jnp.pad(g["lm_head"],
                                      [(0, 0), (0, Vp - dims["V"])])
        return tree

    key = W.seed_key(seed)
    got = jax.eval_shape(make, key)
    if jax.tree_util.tree_structure(got) != \
            jax.tree_util.tree_structure(want) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                    jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want))):
        raise ValueError("the program's parameter tree changed: "
                         f"{jax.tree_util.tree_map(lambda a: a.shape, want)}")
    return jax.block_until_ready(jax.jit(make)(key))


def engine_config(spec: Dict) -> EngineConfig:
    """``EngineConfig`` from a cell file's ``engine`` section."""
    e = dict(spec)
    lanes = e.pop("lanes_in_pool")
    e["buckets"] = tuple(e["buckets"])
    e["pool_pages"] = lanes * e["max_pages"] + 1     # + the null page
    return EngineConfig(**e)


class HookBus(TelemetryBus):
    """The engine's telemetry bus with a callback after each event:
    ``hook("phase", phase)`` after a step is dispatched and
    ``hook("request", rid)`` as a request finishes."""

    def __init__(self, hook: Callable[[str, object], None]):
        super().__init__()
        self.hook = hook

    def publish_phase(self, phase, **kw):
        super().publish_phase(phase, **kw)
        self.hook("phase", phase)

    def publish_request(self, info):
        super().publish_request(info)
        self.hook("request", info["rid"])


class TimedTokens(list):
    """An output-token list that records when each token reached the
    host (``time.perf_counter`` at the engine's ``append``)."""

    def __init__(self):
        super().__init__()
        self.times: List[float] = []

    def append(self, tok):
        self.times.append(time.perf_counter())
        super().append(tok)


def build_engine(model: Model, params, spec: Dict,
                 hook: Callable[[str, object], None]) -> InferenceEngine:
    return InferenceEngine(model, params, engine_config(spec),
                           bus=HookBus(hook))


def submit(engine: InferenceEngine, prompt: List[int], max_new: int):
    """Queue one request; returns its ``Request`` with timed tokens."""
    rid = engine.submit(prompt, max_new)
    req = engine._waiting[-1]
    if req.rid != rid:
        raise RuntimeError("InferenceEngine._waiting no longer ends with "
                           "the request just submitted")
    req.out_tokens = TimedTokens()
    return req


def queued(engine: InferenceEngine) -> int:
    """Requests the engine still holds (waiting, prefilling, decoding)."""
    return len(engine._waiting) + len(engine._active) + \
        len(engine._prefilling)


def decode_steps(stats: Dict) -> int:
    return stats["phases"]["decode"]["steps"]


def release(engine: InferenceEngine, params):
    """Free the pool and the weights on the device."""
    engine.close()
    for a in [engine.pool_k, engine.pool_v] + jax.tree_util.tree_leaves(
            params):
        a.delete()


def make_model(conf: Dict) -> Model:
    return Model(model_config(conf))

