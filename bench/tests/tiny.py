"""A throwaway benchmark at smoke size, made only of new files.

``make_root(tmp)`` copies ``bench/`` and ``BENCHMARK.json`` into ``tmp``
and adds a configuration, two traffic mixes, two cells and a per-layer
metric without editing any file that was there: the way a later change
adds a cell. The harness then runs it on the CPU.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

CONFIG = {
    "name": "tiny-dense", "source": "test", "reference": "dense",
    "program_preset": "granite-3-2b",
    "program_overrides": {"param_dtype": "bfloat16", "tie_embeddings": True,
                          "num_layers": 2, "d_model": 64, "num_heads": 4,
                          "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                          "vocab_size": 300, "attn_chunk": 64},
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "vocab_size": 300,
               "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
               "tie_word_embeddings": True},
}
MIXES = {
    "tiny-open": {"loop": "open", "rate_rps": 6,
                  "prompt_tokens": {"dist": "lognormal", "median": 24,
                                    "sigma": 0.5, "min": 4, "max": 48},
                  "output_tokens": {"dist": "uniform", "min": 3, "max": 10}},
    "tiny-closed": {"loop": "closed", "clients": 3, "block": 8,
                    "prompt_tokens": {"dist": "uniform", "min": 4, "max": 40},
                    "output_tokens": {"dist": "uniform", "min": 10,
                                      "max": 30}},
}
# Limit from CPU readings of the closed cell at this size, seeds 1-10,
# some 160 served tokens each: sound runs read at most 0.0233, the fp8
# control at least 0.108.
CELL = {"engine": {"page_size": 8, "max_pages": 12, "lanes_in_pool": 4,
                   "buckets": [1, 2, 4]},
        "programs": {"decode": "decode", "prefill": "prefill",
                     "cache": "scatter"},
        "trace": {"at_s": 0.5, "seconds": 1},
        "check": {"requests": 8, "max_gap": 0.05}}
METRIC = '''"""Prompt tokens prefilled in the traced window (host records)."""


def read(ctx):
    return float(sum(ctx.traced["prefill"])) or None
'''
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELLS = ("tiny-dense.open", "tiny-dense.closed")


def make_root(tmp: Path) -> Path:
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    b = root / "bench"

    def add(path: Path, text: str):
        assert not path.exists(), f"{path} exists: a new cell edits nothing"
        path.write_text(text)

    add(b / "configs" / "tiny-dense.json", json.dumps(CONFIG))
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": "bench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    for mix, spec in MIXES.items():
        add(b / "traffic" / f"{mix}.json", json.dumps(spec))
    for cell, mix in zip(CELLS, MIXES):
        add(b / "cells" / f"{cell}.json", json.dumps(CELL))
        bench["workloads"].append({"name": cell, "config": "tiny-dense",
                                   "traffic": mix, "chips": 1, "why": "test"})
    add(b / "metrics" / "tiny_prompt_tokens.py", METRIC)
    bench["per_layer"].append({
        "name": "tiny_prompt_tokens", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": list(CELLS)})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
