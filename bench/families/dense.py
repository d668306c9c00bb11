"""The dense family: a Llama-style decoder (RMSNorm, rotary GQA, SwiGLU).

Its reference is ``bench/reference/dense.py``; its weights are
``weights.py``'s canonical layout; its counts are ``costs.py``'s. The
interface is ``family.py``'s.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

import weights as W
from costs import decode_step, prefill_flops, token_flops  # noqa: F401
from repro.configs.registry import get_config

# published key -> ModelConfig field it must equal
_SAME = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
         "num_attention_heads": "num_heads",
         "num_key_value_heads": "num_kv_heads",
         "intermediate_size": "d_ff", "vocab_size": "vocab_size",
         "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
         "tie_word_embeddings": "tie_embeddings"}


def dims(cfg: Dict) -> Dict[str, int]:
    """Shape numbers of a configuration file's published keys."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": H,
            "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // H,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "tied": bool(cfg["tie_word_embeddings"]),
            "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"]}


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
    if cfg.resolved_head_dim != dims(pub)["hd"]:
        raise ValueError(f"{conf['name']}: head_dim differs")
    if cfg.family != "dense" or cfg.use_bias or cfg.pos_emb != "rope":
        raise ValueError(f"{conf['name']}: not the dense family the "
                         "reference implements")
    return cfg


def program_params(model, dims: Dict, seed: int):
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
