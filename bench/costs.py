"""Operations and bytes of the dense family's serving work, from shapes,
and the chip's peaks and roofline, which every family shares.

``families/dense.py`` serves these counts as its own; a family that
differs brings its counts in its own file. The same work is counted
whatever implements it: a decode step reads every weight once and the
KV of each lane's live positions, and writes one KV row per lane.
Bytes that an implementation moves beyond that (a gather of every page
in the page table, a copied pool) are not work.
FLOPs count multiply-adds as two: the matmuls of every linear layer and
the output head, and attention's scores and weighted sum over the live
context. Embedding lookups, norms and softmax are not counted.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; a device not in ``peaks.json`` is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def matmul_params(dims: Dict) -> int:
    """Weights a token multiplies through: every layer plus the head."""
    d, H, kv, hd, ff = (dims[k] for k in ("d", "H", "kv", "hd", "ff"))
    per_layer = d * H * hd * 2 + d * kv * hd * 2 + 3 * d * ff
    return dims["L"] * per_layer + d * dims["V"]


def weight_bytes(dims: Dict, itemsize: int = 2) -> int:
    """Bytes of every weight a decode step reads: layers, norms, head
    (the embedding when tied), not the rows of the embedding lookup."""
    norms = (2 * dims["L"] + 1) * dims["d"]
    return (matmul_params(dims) + norms) * itemsize


def kv_bytes_per_token(dims: Dict, itemsize: int = 2) -> int:
    return dims["L"] * 2 * dims["kv"] * dims["hd"] * itemsize


def attn_flops(dims: Dict, context: int) -> int:
    """Scores and weighted sum of one query over ``context`` positions."""
    return 4 * dims["L"] * dims["H"] * dims["hd"] * context


def token_flops(dims: Dict, context: int) -> int:
    """One token's forward with ``context`` positions visible."""
    return 2 * matmul_params(dims) + attn_flops(dims, context)


def prefill_flops(dims: Dict, prompt_len: int) -> int:
    """A prompt's causal forward: position t sees t + 1 positions."""
    P = prompt_len
    return 2 * matmul_params(dims) * P + attn_flops(dims, 1) * P * (P + 1) \
        // 2


def decode_step(dims: Dict, contexts: Iterable[int],
                kv_itemsize: int = 2, w_itemsize: int = 2):
    """(flops, bytes) of one decode step whose lanes see ``contexts``
    positions each (the new one included)."""
    contexts = list(contexts)
    kvb = kv_bytes_per_token(dims, kv_itemsize)
    flops = sum(token_flops(dims, c) for c in contexts)
    nbytes = weight_bytes(dims, w_itemsize) + kvb * (sum(contexts)
                                                     + len(contexts))
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peak: Dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
