"""Probe-attributed continuous-batching inference engine.

The serving analogue of the paper's always-on in-fabric profiler: a
request scheduler whose every phase — prefill, KV-cache management,
batched decode — runs under the same cycle-probe machinery as the rest
of the repo, so each request leaves with a per-phase cycle bill.

Scheduling model (all host-side; device work is the pre-traced steps
from :mod:`repro.engine.step`):

- **FCFS admission.** Requests wait in arrival order; the head of the
  queue is admitted as soon as its pages fit and a decode slot is open.
  Later requests never jump the head, so no request starves.
- **All pages up front.** Admission allocates every page the request
  will ever touch (prompt + ``max_new`` growth), so decode can never
  fail mid-request. Full prompt pages found in the prefix tree are
  shared by refcount instead of allocated.
- **Bucketed batching, zero retraces.** Decode runs at the smallest
  configured batch bucket covering the runnable set; padded lanes point
  at the null page. Each (phase, shape) step is traced exactly once —
  ``retraces()`` counts compile-cache growth beyond that and the test
  suite asserts it stays 0.
- **Per-phase attribution.** With ``probe=True`` each step family runs
  inside a :class:`~repro.core.streaming.ProbeSession`; the engine takes
  device model-clock deltas around every call. Prefill and cache cycles
  are exclusive to one request; a decode delta is shared by its batch
  (each rider logs the bucket width in ``decode_batches``).
- **Chunked prefill.** With ``prefill_chunk_pages=K`` a prompt wider
  than ``K`` pages prefills one page-aligned chunk per scheduler round,
  interleaved with decode rounds, so a long prompt never head-of-line
  blocks the running decode batch (``hol_blocked_steps`` counts the
  decode rounds a whole-prompt prefill *would* have displaced beyond
  one chunk quantum). Chunk continuations replay the whole-prompt flash
  row plan against pool-gathered context, so outputs stay bit-identical
  — see :func:`repro.engine.step.build_chunk_prefill`. Chunk traces are
  pinned per (ctx pages, chunk pages) pair at warmup.
- **Prefix-aware eviction.** Under pool pressure admission reclaims
  prefix-cache pages through :meth:`PrefixTree.evict` — leaf-first,
  least-recently-matched first, never a page a live request still
  references — so hot shared prefixes survive and
  :class:`PagePoolExhausted` is reachable only when live requests alone
  exceed the pool. ``evict_policy="clear"`` keeps the legacy
  all-or-nothing behavior for A/B benchmarking.
- **Lane-dense pool.** ``pool_k``/``pool_v`` are
  ``(num_layers, pool_pages, page_size, kv_heads*head_dim)``; each step
  family reshapes at its edges, and decode writes only the rows it
  changes (:func:`repro.engine.step.build_paged_decode`).
- **Donated pool buffers.** Off probe mode, steps that return an
  updated pool (cache scatter, decode) are jitted with
  ``donate_argnums`` so the paged KV pool updates in place instead of
  allocating a fresh copy per step. The engine immediately rebinds
  ``pool_k``/``pool_v`` to each step's outputs; the donated inputs are
  dead the moment the step is called and must never be re-read.

- **Spans and counters.** Each scheduler round, admission, prefill,
  decode dispatch, blocking device-to-host read, token emission, bus
  publish and step program's first call runs under a profiler span
  (``engine.*``, :mod:`repro.telemetry.spans`), so a profile attributes
  the device's idle gaps to the host work under them. The same sites
  keep cumulative host seconds in ``stats()`` (``rounds``, ``host_s``,
  ``sync_s``, ``publish_s``, ``first_calls``, ``first_call_s``,
  ``gc_s``), and each request carries host-clock stamps
  (``t_submit``, ``t_admit``, ``t_first``, ``token_times``).

Outputs are bit-identical to the unbatched reference serving path
(asserted in tests/test_engine.py) — batching, paging, padding, and
prefix sharing are all exact-arithmetic-preserving transformations.
"""
from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.pagetable import (NULL_PAGE, PagePoolExhausted, PageTable,
                                    PrefixTree)
from repro.engine.step import (build_chunk_prefill, build_engine_prefill,
                               build_page_scatter, build_paged_decode,
                               donation_argnums, engine_compatible)
from repro.telemetry.spans import gc_seconds, timed

PHASES = ("prefill", "cache", "decode")


def _size_tag(size) -> str:
    """A step's size as one token: ``8`` or, for a chunk, ``8x2``."""
    return str(size) if isinstance(size, int) \
        else "x".join(str(s) for s in size)


@dataclass
class Request:
    """One serving request and its lifetime accounting."""
    rid: int
    prompt: List[int]
    max_new: int
    out_tokens: List[int] = field(default_factory=list)
    phase_cycles: Dict[str, int] = field(
        default_factory=lambda: {p: 0 for p in PHASES})
    decode_batches: List[int] = field(default_factory=list)
    shared_pages: int = 0
    # scheduler-internal
    pages: List[int] = field(default_factory=list)
    pos: int = -1                     # last cache position written
    last_tok: int = -1
    done: bool = False
    # host perf_counter stamps: queued, admitted, first token, and each
    # token's arrival on the host (one per out_tokens entry)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    token_times: List[float] = field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclass
class _PrefillJob:
    """An admitted request mid chunked-prefill: pages are allocated,
    ``next_page`` is the first prompt page the next chunk will write."""
    req: Request
    page_tokens: List[Tuple[int, ...]]
    pp: int                           # total prompt pages
    next_page: int


@dataclass(frozen=True)
class EngineConfig:
    """Engine shape/bucket/probe knobs (all trace-shape determining)."""
    page_size: int = 16
    pool_pages: int = 64              # device pool size incl. null page
    max_pages: int = 8                # page-table width per request
    buckets: Tuple[int, ...] = (1, 2, 4)
    use_kernel: bool = False          # paged_attention Pallas kernel
    pages_per_step: int = 1           # kernel pipelining depth (DSE axis)
    probe: bool = False
    probe_targets: Tuple[str, ...] = ("",)
    probe_max_probes: int = 16
    prefix_cache: bool = True
    prefill_chunk_pages: int = 0      # 0 = whole-prompt prefill (DSE axis)
    evict_policy: str = "lru"         # "lru" | "clear" (legacy)
    donate: Optional[bool] = None     # None = auto (off probe / off CPU)


class InferenceEngine:
    """Continuous-batching engine over one model + parameter set.

    Usage::

        eng = InferenceEngine(model, params, EngineConfig(probe=True))
        eng.submit([1, 2, 3], max_new=8)
        done = eng.run()          # list of finished Requests, rid order
        print(eng.phase_table()); print(eng.request_table(done))
        eng.drain()               # release prefix-cache pages

    ``pool_k``/``pool_v`` hold the paged KV cache, each
    ``(num_layers, pool_pages, page_size, kv_heads*head_dim)`` in the
    model's ``kv_cache_dtype``; page ``NULL_PAGE`` is never handed out.
    """

    def __init__(self, model, params, config: EngineConfig = EngineConfig(),
                 *, bus=None):
        cfg = model.cfg
        # optional telemetry bus: phase/request bills (and, with
        # probe=True, each step family's duration stream) publish to it
        # decode-side, making the engine observable over the status
        # server (docs/telemetry.md). None = exactly the old behavior.
        self.bus = bus
        if not engine_compatible(cfg):
            raise ValueError(
                f"engine requires an attention-family token model; got "
                f"family={cfg.family!r} frontend={cfg.frontend!r}")
        if tuple(sorted(config.buckets)) != tuple(config.buckets) \
                or not config.buckets:
            raise ValueError(f"buckets must be sorted non-empty, "
                             f"got {config.buckets}")
        if config.max_pages > config.pool_pages - 1:
            raise ValueError(f"max_pages {config.max_pages} exceeds pool "
                             f"capacity {config.pool_pages - 1}")
        if config.use_kernel and config.max_pages % config.pages_per_step:
            raise ValueError(f"max_pages {config.max_pages} not divisible "
                             f"by pages_per_step {config.pages_per_step}")
        if config.prefill_chunk_pages < 0:
            raise ValueError(f"prefill_chunk_pages must be >= 0, "
                             f"got {config.prefill_chunk_pages}")
        if config.prefill_chunk_pages and cfg.moe is not None \
                and cfg.moe.impl != "ragged":
            raise ValueError(
                "chunked prefill requires dropless (ragged) MoE routing; "
                f"impl={cfg.moe.impl!r} drops tokens by total count, which "
                "breaks chunk/whole-prompt bit-identity")
        if config.evict_policy not in ("lru", "clear"):
            raise ValueError(f"evict_policy must be 'lru' or 'clear', "
                             f"got {config.evict_policy!r}")
        if config.donate and config.probe:
            raise ValueError(
                "donate=True is incompatible with probe=True: probed steps "
                "run through ProbeSession's stateful wrapper, which shifts "
                "positional args and would donate probe state instead of "
                "the pool")
        self.model, self.params, self.config = model, params, config
        self._donate = (config.donate if config.donate is not None
                        else (not config.probe
                              and jax.default_backend() != "cpu"))
        shape = (cfg.num_layers, config.pool_pages, config.page_size,
                 cfg.num_kv_heads * cfg.resolved_head_dim)
        kvd = jnp.dtype(cfg.kv_cache_dtype)
        self.pool_k = jnp.zeros(shape, kvd)
        self.pool_v = jnp.zeros(shape, kvd)
        self.table = PageTable(config.pool_pages, config.page_size)
        self.tree: Optional[PrefixTree] = \
            PrefixTree(self.table) if config.prefix_cache else None
        self._steps: Dict[Tuple[str, Any], Any] = {}
        self._waiting: deque = deque()
        self._active: List[Request] = []
        self._prefilling: deque = deque()     # _PrefillJob, FCFS
        self._finished: List[Request] = []
        self._next_rid = 0
        self.phase_stats: Dict[str, Dict[str, int]] = {
            p: {"steps": 0, "cycles": 0} for p in PHASES}
        self.bucket_hist: Dict[int, int] = {}
        self.chunk_stats: Dict[Tuple[int, int], Dict[str, int]] = {}
        self.evictions = 0                    # pages reclaimed from tree
        self.hol_blocked_steps = 0            # decode rounds displaced
        self.tokens_out = 0
        # host-side counters (cumulative seconds; see counters())
        self._called: set = set()             # (phase, size) called once
        self.rounds = 0
        self.host_s = 0.0
        self.sync_s = 0.0
        self.publish_s = 0.0
        self.first_calls = 0
        self.first_call_s = 0.0
        self._gc0 = gc_seconds()

    # -- step registry ---------------------------------------------------
    def _build(self, phase: str, size):
        c = self.config
        if phase == "prefill":
            fn = build_engine_prefill(self.model, size, c.page_size)
        elif phase == "cache":
            fn = build_page_scatter(size)
        elif phase == "chunkpf":
            fn = build_chunk_prefill(self.model, size[0], size[1],
                                     c.page_size)
        else:
            fn = build_paged_decode(
                self.model, size, c.max_pages, c.page_size,
                use_kernel=c.use_kernel, pages_per_step=c.pages_per_step)
        if c.probe:
            from repro.core import ProbeConfig, ProbeSession
            return ProbeSession(fn, ProbeConfig(
                targets=c.probe_targets, offload=1.0,
                max_probes=c.probe_max_probes),
                bus=self.bus, source=f"engine/{phase}x{_size_tag(size)}")
        dn = donation_argnums(phase) if self._donate else ()
        return jax.jit(fn, donate_argnums=dn)

    def _entry(self, phase: str, size):
        entry = self._steps.get((phase, size))
        if entry is None:
            entry = self._steps[(phase, size)] = self._build(phase, size)
        return entry

    def _invoke(self, phase: str, size, *args):
        """Call the ``(phase, size)`` step program. Its first call traces
        it and compiles it or loads it from the cache: that call runs
        under an ``engine.compile`` span and counts in ``first_calls``
        and ``first_call_s``."""
        entry = self._entry(phase, size)
        call = entry.step if self.config.probe else entry
        if (phase, size) in self._called:
            return call(*args)
        with timed("engine.compile", phase=phase, size=_size_tag(size)) as t:
            out = call(*args)
        self._called.add((phase, size))
        self.first_calls += 1
        self.first_call_s += t.s
        return out

    def _sync(self, phase: str, x) -> np.ndarray:
        """Block on one device-to-host read (``engine.sync``)."""
        with timed("engine.sync", phase=phase) as t:
            out = np.asarray(x)
        self.sync_s += t.s
        return out

    def _publish(self, topic: str, fn, *args, **kw):
        """One call into the bus, which runs its subscribers' code on
        this thread (``engine.publish``)."""
        with timed("engine.publish", topic=topic) as t:
            fn(*args, **kw)
        self.publish_s += t.s

    def _chunk_shapes(self) -> List[Tuple[int, int]]:
        """Every (ctx_pages, chunk_pages) continuation shape the chunked
        scheduler can reach: chunk starts are multiples of K, the final
        chunk covers the remainder (never padded past the prompt's own
        page-aligned length, so it replays the whole-prompt row plan)."""
        K = self.config.prefill_chunk_pages
        shapes = set()
        if K:
            for pp in range(K + 1, self.config.max_pages + 1):
                for cs in range(K, pp, K):
                    shapes.add((cs, min(K, pp - cs)))
        return sorted(shapes)

    def warmup(self):
        """Trace + compile every (phase, shape) step ahead of serving.

        Without donation the outputs are discarded (the pool is never
        assigned), so warmup leaves serving state untouched. With
        donation the pool buffers passed in are consumed, so the pool is
        rebound to each step's outputs; the null page picks up warmup
        writes, which no real request ever reads unmasked. Either way
        warmup only fills the compile caches, keeping wave-over-wave
        host memory flat (soak test)."""
        c, ps = self.config, self.config.page_size
        for pp in range(1, c.max_pages + 1):
            _, k, v = self._invoke(
                "prefill", pp, self.params,
                {"tokens": jnp.zeros((1, pp * ps), jnp.int32),
                 "last_idx": jnp.zeros((1,), jnp.int32)})
            out = self._invoke("cache", pp, self.pool_k, self.pool_v, k, v,
                               jnp.zeros((pp,), jnp.int32))
            if self._donate:
                self.pool_k, self.pool_v = out
        for (cs, n) in self._chunk_shapes():
            self._invoke(
                "chunkpf", (cs, n), self.params, self.pool_k, self.pool_v,
                {"tokens": jnp.zeros((1, n * ps), jnp.int32),
                 "ctx_pages": jnp.zeros((cs,), jnp.int32),
                 "last_idx": jnp.zeros((1,), jnp.int32)})
        for b in c.buckets:
            out = self._invoke(
                "decode", b, self.params, self.pool_k, self.pool_v,
                {"tokens": jnp.zeros((b, 1), jnp.int32),
                 "pos": jnp.zeros((b,), jnp.int32),
                 "pages": jnp.zeros((b, c.max_pages), jnp.int32)})
            if self._donate:
                self.pool_k, self.pool_v = out[1], out[2]

    def _step(self, phase: str, size, *args):
        """Run one step, return (outputs, model-clock cycle delta)."""
        if self.config.probe:
            entry = self._entry(phase, size)
            c0 = entry.clock()
            out = self._invoke(phase, size, *args)
            delta = entry.clock() - c0
        else:
            out = self._invoke(phase, size, *args)
            delta = 0
        st = self.phase_stats.setdefault(phase, {"steps": 0, "cycles": 0})
        st["steps"] += 1
        st["cycles"] += delta
        if self.bus is not None:
            self._publish(phase, self.bus.publish_phase, phase, cycles=delta,
                          batch=size if phase == "decode" else None)
        return out, delta

    def retraces(self) -> int:
        """Compile-cache entries beyond the one trace each step owns."""
        total = 0
        for (_, _), entry in self._steps.items():
            jf = entry.pf._jitted_stateful if self.config.probe else entry
            if jf is not None and hasattr(jf, "_cache_size"):
                total += max(0, jf._cache_size() - 1)
        return total

    # -- request lifecycle ----------------------------------------------
    def _pages_needed(self, prompt_len: int, max_new: int) -> int:
        # positions 0..prompt_len-1 (prefill) plus max_new-1 decode writes
        return max(1, math.ceil((prompt_len + max_new - 1)
                                / self.config.page_size))

    def submit(self, prompt: Sequence[int], max_new: int = 8) -> int:
        prompt = [int(t) for t in prompt]
        if not prompt or max_new < 1:
            raise ValueError("need a non-empty prompt and max_new >= 1")
        if self._pages_needed(len(prompt), max_new) > self.config.max_pages:
            raise ValueError(
                f"request needs {self._pages_needed(len(prompt), max_new)} "
                f"pages; page table holds {self.config.max_pages}")
        r = Request(rid=self._next_rid, prompt=prompt, max_new=max_new,
                    t_submit=time.perf_counter())
        self._next_rid += 1
        self._waiting.append(r)
        return r.rid

    def _page_tokens(self, r: Request) -> List[Tuple[int, ...]]:
        ps = self.config.page_size
        return [tuple(r.prompt[i * ps:(i + 1) * ps])
                for i in range(len(r.prompt) // ps)]

    def _reclaim(self, n_pages: int, n_shared: int,
                 page_tokens: List[Tuple[int, ...]]) -> int:
        """Evict prefix-cache pages until the head request's fresh-page
        need fits, per ``evict_policy``; returns the updated shared-page
        count (a "clear" drops the head's own match too)."""
        if self.tree is None or not self.tree.nodes:
            return n_shared
        if self.config.evict_policy == "clear":
            # legacy all-or-nothing: only safe once serving is idle
            if not self._active and not self._prefilling:
                self.evictions += len(self.tree.clear())
                n_shared = 0
            return n_shared
        while n_pages - n_shared > self.table.free_pages:
            shortfall = (n_pages - n_shared) - self.table.free_pages
            freed = self.tree.evict(shortfall, protect=page_tokens)
            if not freed:
                break                 # every remaining leaf is in use
            self.evictions += len(freed)
            n_shared = self.tree.lookup(page_tokens)
        return n_shared

    def _try_admit(self, r: Request) -> bool:
        n_pages = self._pages_needed(len(r.prompt), r.max_new)
        page_tokens = self._page_tokens(r)
        n_shared = self.tree.lookup(page_tokens) if self.tree else 0
        if n_pages - n_shared > self.table.free_pages:
            # prefix-cache pages are the only reclaimable slack: evict
            # when the pool alone is the blocker, else wait for drains
            n_shared = self._reclaim(n_pages, n_shared, page_tokens)
            if n_pages - n_shared > self.table.free_pages:
                return False
        shared = self.tree.match(page_tokens) if self.tree else []
        assert len(shared) == n_shared, (len(shared), n_shared)
        fresh = self.table.alloc(n_pages - len(shared))
        r.pages = shared + fresh
        r.shared_pages = len(shared)
        r.t_admit = time.perf_counter()
        self._start_prefill(r, page_tokens)
        return True

    def _start_prefill(self, r: Request,
                       page_tokens: List[Tuple[int, ...]]):
        K = self.config.prefill_chunk_pages
        pp = math.ceil(len(r.prompt) / self.config.page_size)
        if not K or pp <= K:
            with timed("engine.prefill", rid=r.rid, prompt_len=len(r.prompt),
                       pages=pp, queue_ms=1e3 * (r.t_admit - r.t_submit)):
                self._prefill(r, page_tokens)
            return
        # chunks start at multiples of K; fully prefix-shared leading
        # chunks are skipped (their pages already hold these exact KV
        # rows), but the final chunk always runs for the first token
        start = min((r.shared_pages // K) * K, ((pp - 1) // K) * K)
        self._prefilling.append(_PrefillJob(r, page_tokens, pp, start))

    def _prefill(self, r: Request, page_tokens: List[Tuple[int, ...]]):
        c = self.config
        P = len(r.prompt)
        pp = math.ceil(P / c.page_size)
        if self._active:
            # decode rounds this whole-prompt prefill displaces beyond
            # the one chunk quantum any prefill step costs
            q = max(c.prefill_chunk_pages, 1)
            self.hol_blocked_steps += max(0, math.ceil(pp / q) - 1)
        toks = np.zeros((1, pp * c.page_size), np.int32)
        toks[0, :P] = r.prompt
        (logits, k, v), d = self._step(
            "prefill", pp, self.params,
            {"tokens": jnp.asarray(toks),
             "last_idx": jnp.array([P - 1], jnp.int32)})
        r.phase_cycles["prefill"] += d
        ids = jnp.array(r.pages[:pp], jnp.int32)
        (self.pool_k, self.pool_v), d = self._step(
            "cache", pp, self.pool_k, self.pool_v, k, v, ids)
        r.phase_cycles["cache"] += d
        if self.tree is not None and page_tokens:
            self.tree.insert(page_tokens, r.pages[:len(page_tokens)])
        self._emit_first_token(r, logits)

    def _emit_first_token(self, r: Request, logits):
        tok = int(self._sync("prefill", jnp.argmax(logits, axis=-1)[0]))
        r.t_first = time.perf_counter()
        r.token_times.append(r.t_first)
        r.out_tokens.append(tok)
        self.tokens_out += 1
        r.last_tok = tok
        r.pos = len(r.prompt) - 1
        if len(r.out_tokens) >= r.max_new:
            self._complete(r)
        else:
            self._active.append(r)

    def _chunk_step(self):
        """Prefill the head job's next chunk (one scheduler quantum)."""
        c = self.config
        job = self._prefilling[0]
        r, ps = job.req, c.page_size
        P, pp, cs = len(r.prompt), job.pp, job.next_page
        n = min(c.prefill_chunk_pages, pp - cs)
        with timed("engine.chunk", rid=r.rid, prompt_len=P, ctx_pages=cs,
                   chunk_pages=n, queue_ms=1e3 * (r.t_admit - r.t_submit)):
            final = cs + n >= pp
            toks = np.zeros((1, n * ps), np.int32)
            seg = r.prompt[cs * ps:min(P, (cs + n) * ps)]
            toks[0, :len(seg)] = seg
            li = (P - 1 - cs * ps) if final else (n * ps - 1)
            batch = {"tokens": jnp.asarray(toks),
                     "last_idx": jnp.array([li], jnp.int32)}
            if cs == 0:
                (logits, k, v), d = self._step("prefill", n, self.params,
                                               batch)
            else:
                batch["ctx_pages"] = jnp.array(r.pages[:cs], jnp.int32)
                (logits, k, v), d = self._step(
                    "chunkpf", (cs, n), self.params, self.pool_k, self.pool_v,
                    batch)
            r.phase_cycles["prefill"] += d
            ids = jnp.array(r.pages[cs:cs + n], jnp.int32)
            (self.pool_k, self.pool_v), dc = self._step(
                "cache", n, self.pool_k, self.pool_v, k, v, ids)
            r.phase_cycles["cache"] += dc
            cst = self.chunk_stats.setdefault((cs, n),
                                              {"steps": 0, "cycles": 0})
            cst["steps"] += 1
            cst["cycles"] += d + dc
            job.next_page = cs + n
            # publish fully-written prompt pages incrementally so requests
            # arriving mid-prefill can already share the finished chunks
            if self.tree is not None and job.page_tokens:
                done_pages = min(cs + n, len(job.page_tokens))
                self.tree.insert(job.page_tokens[:done_pages],
                                 r.pages[:done_pages])
            if final:
                self._prefilling.popleft()
                self._emit_first_token(r, logits)

    def _complete(self, r: Request):
        for p in r.pages:
            self.table.free(p)
        r.pages = []
        r.done = True
        self._finished.append(r)
        if self.bus is not None:
            self._publish("request", self.bus.publish_request, {
                "rid": r.rid, "prompt_len": r.prompt_len,
                "tokens": len(r.out_tokens),
                "shared_pages": r.shared_pages,
                "decode_batches": list(r.decode_batches),
                "phase_cycles": dict(r.phase_cycles),
                "queue_ms": 1e3 * (r.t_admit - r.t_submit),
                "first_token_ms": 1e3 * (r.t_first - r.t_submit)})

    def _admit(self):
        lanes = self.config.buckets[-1]
        with timed("engine.admit") as t:
            n = 0
            while self._waiting and \
                    len(self._active) + len(self._prefilling) < lanes:
                if not self._try_admit(self._waiting[0]):
                    break               # FCFS: the head blocks the line
                self._waiting.popleft()
                n += 1
            t.set(admitted=n)

    def _decode_round(self):
        c = self.config
        sel = self._active[:c.buckets[-1]]
        bucket = next(b for b in c.buckets if b >= len(sel))
        with timed("engine.decode", bucket=bucket, lanes=len(sel)):
            self.bucket_hist[bucket] = self.bucket_hist.get(bucket, 0) + 1
            pages = np.zeros((bucket, c.max_pages), np.int32)
            pos = np.zeros(bucket, np.int32)
            toks = np.zeros((bucket, 1), np.int32)
            for i, r in enumerate(sel):
                pages[i, :len(r.pages)] = r.pages
                pos[i] = r.pos + 1
                toks[i, 0] = r.last_tok
            (_, self.pool_k, self.pool_v, next_tok), d = self._step(
                "decode", bucket, self.params, self.pool_k, self.pool_v,
                {"tokens": jnp.asarray(toks), "pos": jnp.asarray(pos),
                 "pages": jnp.asarray(pages)})
            next_tok = self._sync("decode", next_tok)
            now = time.perf_counter()
            with timed("engine.emit") as t:
                finished = []
                for i, r in enumerate(sel):
                    r.pos += 1
                    tok = int(next_tok[i])
                    r.token_times.append(now)
                    r.out_tokens.append(tok)
                    self.tokens_out += 1
                    r.last_tok = tok
                    r.decode_batches.append(bucket)
                    r.phase_cycles["decode"] += d
                    if len(r.out_tokens) >= r.max_new:
                        finished.append(r)
                t.set(finished=len(finished))
                for r in finished:
                    self._active.remove(r)
                    self._complete(r)

    def run(self) -> List[Request]:
        """Serve until every submitted request has finished; returns the
        requests completed by this call, in submission order. Each
        iteration is one scheduler round (``engine.round``, whose
        arguments are the queue and ``counters()`` at its start)."""
        start = len(self._finished)
        while self._waiting or self._active or self._prefilling:
            sync0, pub0 = self.sync_s, self.publish_s
            with timed("engine.round", active=len(self._active),
                       waiting=len(self._waiting), **self.counters()) as t:
                self._round()
            self.rounds += 1
            self.host_s += t.s - (self.sync_s - sync0) \
                - (self.publish_s - pub0)
            if self.bus is not None:
                self._publish("counters", self.bus.publish_counters,
                              self.counters())
        return sorted(self._finished[start:], key=lambda r: r.rid)

    def _round(self):
        self._admit()
        progressed = False
        if self._prefilling:             # one chunk quantum per round,
            self._chunk_step()           # interleaved with decode below
            progressed = True
        if self._active:
            self._decode_round()
            progressed = True
        if not progressed and self._waiting:
            # head unadmittable with an otherwise idle engine
            r = self._waiting[0]
            raise PagePoolExhausted(
                f"request {r.rid} needs "
                f"{self._pages_needed(len(r.prompt), r.max_new)} pages "
                f"with only {self.table.free_pages} free")

    def reap(self) -> List[Request]:
        """Pop every finished request. Long-lived servers call this per
        wave so engine-held state stays constant-size (the soak test's
        flat-memory assertion)."""
        out, self._finished = self._finished, []
        return out

    # -- teardown / reporting -------------------------------------------
    def drain(self):
        """Release prefix-cache page references through the evictor;
        with no requests in flight the page table must then balance —
        asserted here so drain can't mask a refcount leak."""
        if self.tree is not None:
            self.tree.evict_all()
        if not (self._waiting or self._active or self._prefilling):
            assert self.table.balanced(), (
                f"page table unbalanced after drain: "
                f"{self.table.used_pages} pages still referenced")

    def close(self):
        """Close probe sessions (restores each step's original sink)."""
        if self.config.probe:
            for entry in self._steps.values():
                entry.close()

    def counters(self) -> Dict[str, Any]:
        """Cumulative host-side counters: scheduler rounds; ``host_s``,
        the rounds' time less ``sync_s`` (blocking device-to-host reads)
        and ``publish_s`` (calls into the bus); ``first_calls`` and
        ``first_call_s``, the step programs' first calls (trace, compile
        or cache load, dispatch); ``gc_s``, the process's collector time
        since this engine was built."""
        return {"rounds": self.rounds, "host_s": self.host_s,
                "sync_s": self.sync_s, "publish_s": self.publish_s,
                "first_calls": self.first_calls,
                "first_call_s": self.first_call_s,
                "gc_s": gc_seconds() - self._gc0}

    def stats(self) -> Dict[str, Any]:
        hits = self.tree.hits if self.tree else 0
        misses = self.tree.misses if self.tree else 0
        return {
            "requests": len(self._finished),
            "phases": {p: dict(v) for p, v in self.phase_stats.items()},
            "retraces": self.retraces(),
            "pages_peak": self.table.peak_used,
            "prefix_hits": hits,
            "prefix_misses": misses,
            "prefix_hit_rate": hits / (hits + misses) if hits + misses
            else 0.0,
            "buckets": dict(self.bucket_hist),
            "steps_traced": len(self._steps),
            "evictions": self.evictions,
            "hol_blocked_steps": self.hol_blocked_steps,
            "tokens_out": self.tokens_out,
            **self.counters(),
        }

    def phase_table(self) -> str:
        from repro.core.report import engine_phase_table
        return engine_phase_table(self.phase_stats)

    def chunk_table(self) -> str:
        from repro.core.report import engine_chunk_table
        return engine_chunk_table(self.chunk_stats)

    def request_table(self, requests: List[Request]) -> str:
        from repro.core.report import engine_request_table
        return engine_request_table(requests)
