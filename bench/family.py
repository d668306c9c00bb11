"""A model family: the harness's side of one kind of model, found by name.

A configuration file's ``reference`` key names its family ``<r>``. The
plain reference is ``bench/reference/<r>.py`` (``correct.py``); the
harness's side is ``bench/families/<r>.py``, loaded by path as readers
and references are. A family that a later change brings is new files
only: nothing here or in ``run.py`` learns its name.

A family module holds these callables, and nothing else is asked of it:

``dims(published) -> dict``
    Shape numbers from the configuration file's published keys
    (``config``); holds ``"V"``, the vocabulary the traffic draws from.
    The same dict goes to the reference and to the counts below.
``model_config(conf) -> ModelConfig``
    The program's configuration: the preset the file names
    (``program_preset``) plus its ``program_overrides``, checked against
    the published keys; raises where the program would run another
    model.
``program_params(model, dims, seed)``
    The program's parameter tree, filled from the family's seeded
    canonical weights, the same ones its reference draws; made on the
    device in the configuration's parameter dtype.
``decode_step(dims, contexts) -> (flops, bytes)``
    The work of one decode step whose lanes see ``contexts`` positions
    each, the new one included (``decode_roofline``).
``token_flops(dims, context)`` and ``prefill_flops(dims, prompt_len)``
    One token's forward with ``context`` positions visible, and a
    prompt's causal forward (``serve_mfu``).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CALLABLES = ("dims", "model_config", "program_params", "decode_step",
             "token_flops", "prefill_flops")


def load(name: str, bench_dir: Path = BENCH):
    """The module ``<bench_dir>/families/<name>.py``; a missing file or
    callable is an error that names the file."""
    path = Path(bench_dir) / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model family {name!r}: {path} is "
                                "missing")
    spec = importlib.util.spec_from_file_location(
        "family_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [c for c in CALLABLES if not callable(getattr(mod, c, None))]
    if missing:
        raise AttributeError(f"{path} lacks {missing}")
    return mod
