"""How ``correct`` is decided for a served model.

After the window, a sample of the finished requests, drawn from the
seed and always holding the one with the most served tokens, goes to the
plain reference (``bench/reference/<reference>.py``) as prompt plus
served tokens. At each position that produced a served token the
reference gives its best logit and its logit for the served token; the
gap between them is 0 where the served token is the reference's greedy
choice and small where rounding flipped a near-tie. The run is correct
when every request finished with all its tokens and the widest gap over
the sample is within the cell's limit (``cells/<cell>.json``, set in
PERF.md from sound runs and from the fp8 control).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

BENCH = Path(__file__).resolve().parent
ROWS = 256                     # the reference's logit block; S pads to it


def load_reference(name: str, bench_dir: Path = BENCH):
    """``Reference`` of ``<bench_dir>/reference/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"reference_{name}", bench_dir / "reference" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Reference


def sample(finished: Sequence, k: int, seed: int) -> List:
    """``k`` finished requests: the one with the most served tokens, and
    the rest drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.out_tokens),
                                            -len(r.prompt), r.rid))
    rest = order[1:]
    rng = np.random.default_rng([seed, 0xC0C0])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def ref_length(max_sequence: int) -> int:
    return -(-max_sequence // ROWS) * ROWS


def served_gaps(Reference, dims: Dict, seed: int, reqs: Sequence,
                seq_len: int, control: bool = False) -> Dict[str, float]:
    """The widest gap over ``reqs`` (and the control's, if asked), with
    the number of served tokens compared."""
    toks = np.zeros((len(reqs), seq_len), np.int32)
    for i, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.out_tokens)
        toks[i, :len(seq)] = seq
    ref = Reference(dims, control=control, rows=ROWS)
    g = ref.gaps(seed, toks)
    out = {"tokens": 0, "max_gap": 0.0}
    if control:
        out["control_max_gap"] = 0.0
    for i, r in enumerate(reqs):
        P, n = len(r.prompt), len(r.out_tokens)
        sl = slice(P - 1, P - 1 + n)     # position t predicts token t + 1
        out["tokens"] += n
        out["max_gap"] = max(out["max_gap"], float(g["served"][i, sl].max()))
        if control:
            out["control_max_gap"] = max(out["control_max_gap"],
                                         float(g["control"][i, sl].max()))
    return out
