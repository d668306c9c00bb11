#!/usr/bin/env python3
"""Smoke run of the serving path at full width on TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one four-chip host

One chip: tinyllama-1.1b at its published widths (random weights from
seed 0) serves 4 prompts of 128 tokens, 32 new tokens each, through the
continuous-batching engine in three phases:

  (a) XLA paged decode, unprobed, pool donation on (its default off CPU);
  (b) the Pallas paged-attention decode kernel, compiled, not interpreted;
  (c) probed: per-phase and per-request bills in model-clock cycles.

The legacy lock-step loop (``serve --no-engine``) runs as a second
reference. Checks: 0 retraces, finite logits, (b), (c) and the legacy
loop within ``LOGIT_RTOL`` of (a), ``tpu_custom_call`` in (b)'s lowered
decode step, the compiled kernel within a bf16 rounding bound of the
dense-gather reference (``attention_gap``), nonzero probe rows, no rows
dropped by the streaming sink, and one probed decode step
integer-equal to the ``CycleOracle`` replay. Token ids are compared
and printed with (a)'s top-2 logit margin where they first differ; a
mismatch does not fail the run (a random-init model's argmax can tie).

``--four-chips`` runs only the mesh-probed decode of
``serve --profile --mesh 4`` at the same widths and batch 4, and the
same decode unsharded on device 0 as its reference.

A smoke run, not a benchmark: the times printed are one cold run each.
Any failed check exits nonzero; so does a run where JAX finds no TPU.
The last line of output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ShapeConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.instrument import decode_record  # noqa: E402
from repro.distributed.steps import (build_decode_step,  # noqa: E402
                                     build_prefill_step)
from repro.engine import InferenceEngine  # noqa: E402
from repro.kernels.paged_attention import paged_attention  # noqa: E402
from repro.kernels.ref import paged_attention_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import engine_config  # noqa: E402
from repro.models.model import Model  # noqa: E402

ARCH = "tinyllama-1.1b"
BATCH, PROMPT, NEW = 4, 128, 32
# Largest |logit difference| allowed against phase (a), as a fraction
# of (a)'s largest |logit|, over every token whose inputs equal (a)'s.
# A coarse end-to-end limit: each of the 22 layers rounds to bf16, so
# correct paths that reduce in another order drift apart by percents.
# The decode kernel is held to the tight bound of ``attention_gap``.
LOGIT_RTOL = 5e-2


class Checks:
    """Named pass/fail results; the run fails if any check failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[{'ok' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


class CompileEvents:
    """Sorts every program JAX compiles or loads by what the persistent
    cache did with it, from JAX's monitoring events: one program's
    cache events precede its compile-duration event."""

    KINDS = ("hit", "written", "not written", "not looked up")

    def __init__(self):
        self.programs = []              # (kind, seconds, name)
        self._seen = set()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def compile_s(self) -> float:
        return sum(s for _, s, _ in self.programs)

    def _event(self, event: str, **_):
        self._seen.add(event.rsplit("/", 1)[-1])

    def _duration(self, event: str, secs: float, fun_name: str = "", **_):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        seen, self._seen = self._seen, set()
        # jax 0.9.0 records "cache_misses" only when it writes the entry
        kind = ("hit" if "cache_hits" in seen
                else "written" if "cache_misses" in seen
                else "not written" if "compile_requests_use_cache" in seen
                else "not looked up")
        self.programs.append((kind, secs, fun_name))

    def report(self, cache_dir: str) -> str:
        parts = []
        for kind in self.KINDS:
            secs = [s for k, s, _ in self.programs if k == kind]
            parts.append(f"{kind} {len(secs)} ({sum(secs):.1f} s)")
        slow = sorted((p for p in self.programs if p[0] != "hit"),
                      key=lambda p: -p[1])[:5]
        return (f"compile cache {cache_dir}: {len(self.programs)} programs "
                f"compiled or loaded in {self.compile_s:.1f} s; by cache "
                f"outcome: {', '.join(parts)}\nslowest compiles not taken "
                f"from the cache: " + ", ".join(
                    f"{n} {s:.1f} s ({k})" for k, s, n in slow))


def real_logits(model, logits) -> np.ndarray:
    """Host copy of the logits over the real vocabulary (padded vocab
    columns are -inf by construction)."""
    return np.asarray(logits)[:, :model.cfg.vocab_size]


class LogitTap(InferenceEngine):
    """The engine, keeping the logits behind every token it emits."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.prefill_logits = []
        self.decode_logits = []

    def _step(self, phase, size, *args):
        out, delta = super()._step(phase, size, *args)
        if phase == "prefill":
            self.prefill_logits.append(real_logits(self.model, out[0]))
        elif phase == "decode":
            self.decode_logits.append(real_logits(self.model, out[0]))
        return out, delta

    def token_logits(self) -> np.ndarray:
        """(BATCH, NEW, vocab): the logits each request's tokens were
        drawn from. All requests are prefilled before the first decode
        round and decode together, so lane i of round t is token t + 1
        of request i."""
        assert len(self.prefill_logits) == BATCH, len(self.prefill_logits)
        assert len(self.decode_logits) == NEW - 1, len(self.decode_logits)
        return np.stack([np.concatenate(self.prefill_logits)]
                        + self.decode_logits, axis=1)


def rel_diff(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def compare_tokens(label, logits, toks, ref_logits, ref_toks, check):
    """Hold ``logits`` to ``LOGIT_RTOL`` of (a)'s over every token whose
    inputs equal (a)'s, and show (a)'s top-2 logit margin where the
    token ids first differ."""
    same_inputs = np.ones(toks.shape, bool)
    same_inputs[:, 1:] = np.cumprod(toks[:, :-1] == ref_toks[:, :-1],
                                    axis=1).astype(bool)
    row_diff = np.abs(logits - ref_logits).max(axis=-1)
    scale = np.abs(ref_logits).max(axis=-1)
    d = float(row_diff[same_inputs].max() / scale[same_inputs].max())
    check(f"{label}: logits vs a", d <= LOGIT_RTOL,
          f"max |diff| / max |logit| = {d:.3e} over {int(same_inputs.sum())}"
          f" tokens with (a)'s inputs (tolerance {LOGIT_RTOL:g})")
    n = int((toks != ref_toks).sum())
    if not n:
        print(f"token ids {label} == a: True")
        return
    first = np.argwhere(same_inputs & (toks != ref_toks))
    r, t = first[np.argmin(first[:, 1])]
    top2 = np.sort(ref_logits, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    print(f"token ids {label} == a: False ({n} of {toks.size} differ); "
          f"first at request {r} token {t}: (a)'s top-2 logit margin "
          f"{margin[r, t]:.6g} against a max |diff| of {row_diff[r, t]:.6g} "
          f"in that logit row (median margin over (a)'s tokens "
          f"{float(np.median(margin)):.6g}, logits up to "
          f"{float(scale.max()):.6g})")


def attention_gap(attend, cfg, n_pages: int, page: int = 16,
                  seed: int = 2):
    """Largest |attend - paged_attention_ref| at ``cfg``'s decode widths
    over random queries and a random bf16 pool behind a shuffled page
    table, and its limit.

    Both multiply the same bf16 operands with f32 accumulation and
    differ only in reduction order, which can flip the bf16 rounding of
    an attention weight w by one bf16 ulp, at most 2^-7 w. The output
    then moves by at most sum_s 2^-7 w_s |v_s| <= 2^-7 max|v|: that is
    the limit. A wrong page, kv head or mask moves it by a sizeable
    fraction of |v| (``tests/test_kernels.py`` plants each).
    """
    kv, g, hd = cfg.num_kv_heads, cfg.q_per_kv, cfg.resolved_head_dim
    pool = BATCH * n_pages + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (BATCH, kv, g, hd), jnp.float32)
    pk = jax.random.normal(ks[1], (pool, page, kv, hd)).astype(jnp.bfloat16)
    pv = jax.random.normal(ks[2], (pool, page, kv, hd)).astype(jnp.bfloat16)
    pages = jax.random.permutation(ks[3], pool)[:BATCH * n_pages].reshape(
        BATCH, n_pages).astype(jnp.int32)
    s_max = n_pages * page
    pos = jnp.array([0, page + 5, s_max // 2, s_max - 1], jnp.int32)
    got = np.asarray(attend(q, pk, pv, pages, pos))
    want = np.asarray(paged_attention_ref(q, pk, pv, pages, pos))
    limit = 2.0 ** -7 * float(jnp.abs(pv.astype(jnp.float32)).max())
    return float(np.abs(got - want).max()), limit


def decode_batch(tokens, max_pages: int):
    """A decode batch for the engine's bucket-``BATCH`` step: each lane
    at position ``PROMPT`` over its own run of pages."""
    pages = 1 + np.arange(BATCH * max_pages, dtype=np.int32).reshape(
        BATCH, max_pages)
    return {"tokens": jnp.asarray(np.asarray(tokens, np.int32)[:, None]),
            "pos": jnp.full((BATCH,), PROMPT, jnp.int32),
            "pages": jnp.asarray(pages)}


def oracle_equal(record, oracle) -> bool:
    """Integer equality of a decoded counter record and a replay."""
    return (int(record["cycle"]) == oracle.cycle and all(
        [int(v) for v in record[k]] == list(getattr(oracle, k))
        for k in ("totals", "calls", "starts", "ends")))


def serve_phase(label, model, params, prompts, events, **knobs):
    """Serve the prompts through a fresh engine; returns the engine,
    the finished requests and the phase's line of figures."""
    eng = LogitTap(model, params, engine_config(BATCH, PROMPT, NEW, **knobs))
    c0 = events.compile_s
    t0 = time.perf_counter()
    for p in prompts:
        eng.submit(p.tolist(), NEW)
    done = eng.run()
    wall = time.perf_counter() - t0
    st = eng.stats()
    logits = eng.token_logits()
    finite = bool(np.isfinite(logits).all())
    print(f"phase {label}: compile {events.compile_s - c0:.1f} s, served "
          f"{st['tokens_out']} tokens in {wall:.1f} s (cold, compile "
          f"included), retraces {st['retraces']}, logits of every step "
          f"finite {finite}", flush=True)
    toks = np.array([r.out_tokens for r in done])
    return eng, done, st, finite, logits, toks


def legacy_serve(model, params, prompts):
    """The ``serve --no-engine`` loop: dense-cache prefill, then one
    decode step per token. Returns the (BATCH, NEW, vocab) logits each
    token was drawn from, and the (BATCH, NEW) token ids."""
    prefill = jax.jit(build_prefill_step(
        model, ShapeConfig("pf", PROMPT + NEW, BATCH, "prefill")))
    decode = jax.jit(build_decode_step(model), donate_argnums=(1,))
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    steps, toks = [real_logits(model, logits)], [np.asarray(tok)]
    for i in range(NEW - 1):
        logits, cache, tok = decode(params, cache, {
            "tokens": tok[:, None], "pos": jnp.int32(PROMPT + i)})
        steps.append(real_logits(model, logits))
        toks.append(np.asarray(tok))
    return np.stack(steps, axis=1), np.stack(toks, axis=1)


def one_chip(model, params, prompts, events, check):
    # (a) XLA paged decode, unprobed, donation auto-on off CPU
    eng, done, st, finite, ref_logits, ref_toks = serve_phase(
        "a (xla decode, unprobed)", model, params, prompts, events)
    check("a: retraces 0", st["retraces"] == 0)
    check("a: logits finite", finite)
    check("a: pool donated", eng._donate)
    eng.drain()
    del eng

    # (b) the Pallas paged-attention kernel, compiled for the chip
    eng, done, st, finite, logits, toks_b = serve_phase(
        "b (pallas decode kernel)", model, params, prompts, events,
        use_kernel=True)
    check("b: retraces 0", st["retraces"] == 0)
    check("b: logits finite", finite)
    c = eng.config
    hlo = eng._entry("decode", BATCH).lower(
        params, eng.pool_k, eng.pool_v,
        decode_batch(ref_toks[:, 0], c.max_pages)).as_text()
    check("b: lowered decode step has tpu_custom_call",
          "tpu_custom_call" in hlo)
    gap, limit = attention_gap(jax.jit(functools.partial(
        paged_attention, pages_per_step=c.pages_per_step)), model.cfg,
        c.max_pages, c.page_size)
    check("b: kernel attention vs dense-gather reference", gap <= limit,
          f"max |diff| {gap:.6g} (bound 2^-7 max|v| = {limit:.6g})")
    compare_tokens("b", logits, toks_b, ref_logits, ref_toks, check)
    eng.drain()
    del eng

    # the legacy lock-step loop
    t0 = time.perf_counter()
    c0 = events.compile_s
    logits, toks = legacy_serve(model, params, prompts)
    print(f"legacy loop: compile {events.compile_s - c0:.1f} s, served "
          f"{toks.size} tokens in {time.perf_counter() - t0:.1f} s (cold, "
          f"compile included)", flush=True)
    compare_tokens("legacy", logits, toks, ref_logits, ref_toks, check)

    # (c) probed: per-phase and per-request bills
    eng, done, st, finite, logits, toks = serve_phase(
        "c (probed)", model, params, prompts, events, probe=True)
    check("c: retraces 0", st["retraces"] == 0)
    check("c: logits finite", finite)
    compare_tokens("c", logits, toks, ref_logits, ref_toks, check)
    print("\n# per-phase bill (model-clock cycles of the cost model, "
          "not device time)")
    print(eng.phase_table())
    print("\n# per-request bill (model-clock cycles)")
    print(eng.request_table(done))
    check("c: every phase billed nonzero cycles",
          all(eng.phase_stats[p]["cycles"] > 0
              for p in ("prefill", "cache", "decode")))
    check("c: every request billed nonzero cycles in every phase",
          all(v > 0 for r in done for v in r.phase_cycles.values()))
    sessions = dict(eng._steps)
    rows = dropped = 0
    for sess in sessions.values():
        snap = sess.snapshot()                  # flushes the sink
        rows += sum(r.total_cycles > 0 for r in snap.rows)
        dropped += sess.sink.dropped
    check("c: probe rows nonzero", rows > 0, f"{rows} rows with cycles")
    check("c: streaming sink dropped 0 rows", dropped == 0,
          f"dropped {dropped}")
    sess = sessions[("decode", BATCH)]
    args = (params, eng.pool_k, eng.pool_v,
            decode_batch(ref_toks[:, 0], eng.config.max_pages))
    _, state = sess.pf.stateful_call(sess.pf.init_state(), *args)
    record = decode_record(jax.device_get(state))
    check("c: decode step record == CycleOracle replay",
          oracle_equal(record, sess.pf.oracle(*args)),
          f"span {int(record['cycle'])} model-clock cycles, "
          f"{len(sess.pf.probe_paths())} probes")
    eng.drain()
    eng.close()
    print("first request's token ids (a):", ref_toks[0].tolist())


def four_chips(model, params, prompts, events, check):
    from repro.launch.serve import _mesh_decode_session
    from repro.core.meshprobe import decode_mesh_record
    n = len(jax.devices())
    check("four chips visible", n == 4, f"{n} devices")
    cache_len = PROMPT + NEW
    session = _mesh_decode_session(
        model, ShapeConfig("pf", cache_len, BATCH, "decode"), (4,), False,
        ("",), 16, 8)
    prefill = jax.jit(build_prefill_step(
        model, ShapeConfig("pf", cache_len, BATCH, "prefill")))
    unsharded = jax.jit(build_decode_step(model))
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    ref_cache, ref_tok = cache, tok
    first_args = None
    mesh_toks, ref_toks = [np.asarray(tok)], [np.asarray(tok)]
    t0, c0 = time.perf_counter(), events.compile_s
    for i in range(NEW - 1):
        pos = jnp.int32(PROMPT + i)
        args = (params, cache, {"tokens": tok[:, None], "pos": pos})
        if first_args is None:
            first_args = args
        logits, cache, tok = session.step(*args)
        ref_logits, ref_cache, ref_tok = unsharded(
            params, ref_cache, {"tokens": ref_tok[:, None], "pos": pos})
        if i == 0:
            d = rel_diff(real_logits(model, logits),
                         real_logits(model, ref_logits))
        mesh_toks.append(np.asarray(tok))
        ref_toks.append(np.asarray(ref_tok))
    print(f"mesh decode: compile {events.compile_s - c0:.1f} s, "
          f"{BATCH} x {NEW - 1} steps in {time.perf_counter() - t0:.1f} s "
          f"(cold, compile included), {session.steps} probed steps",
          flush=True)
    spans = {len(v.sharding.device_set) for v in session._state.values()}
    check("counter state spans 4 devices", spans == {4},
          f"device-set sizes {sorted(spans)}")
    check("mesh logits vs unsharded (step 1)", d <= LOGIT_RTOL,
          f"max |diff| / max |logit| = {d:.3e} (tolerance {LOGIT_RTOL:g})")
    mpf = session.mpf
    _, state = mpf.stateful_call(mpf.init_state(), *first_args)
    rec = decode_mesh_record(state, mpf.mesh_axes, mpf.mesh_shape,
                             mpf.assignment.paths)
    for dev in range(4):
        check(f"dev{dev} record == ShardOracle replay",
              oracle_equal(rec.device(dev),
                           mpf.oracle(*first_args, device=dev)),
              f"span {int(rec.cycle[dev])} model-clock cycles")
    snap = session.close()
    print("\n# per-device cycle records (model-clock cycles)")
    print(snap.device_table())
    mesh_toks = np.stack(mesh_toks, axis=1)
    ref_toks = np.stack(ref_toks, axis=1)
    same = bool(np.array_equal(mesh_toks, ref_toks))
    print(f"token ids mesh == unsharded on device 0: {same}")
    print("mesh      first request:", mesh_toks[0].tolist())
    print("unsharded first request:", ref_toks[0].tolist())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-probed decode on a (4,) mesh")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    events = CompileEvents()
    check = Checks()

    cfg = get_config(ARCH)
    print(f"{ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads (kv {cfg.num_kv_heads}), d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}; {BATCH} prompts x {PROMPT} tokens, "
          f"{NEW} new each; device {dev.device_kind} x "
          f"{len(jax.devices())}", flush=True)
    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (BATCH, PROMPT), 0, cfg.vocab_size))
    print(f"random weights (seed 0) in {time.perf_counter() - t0:.1f} s",
          flush=True)

    (four_chips if args.four_chips else one_chip)(
        model, params, prompts, events, check)
    print(events.report(cache_dir))
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
