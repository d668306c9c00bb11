"""Continuous-batching engine: scheduling invariants and bit-identity.

Three layers of coverage:

- host-side bookkeeping units (page table refcounts, prefix trie);
- hypothesis scheduler properties over random request traces, run
  against deterministic fake steps (arrival order, prompt/decode
  lengths and shared prefixes drawn freely) — no starvation, page
  refcounts balance to zero at drain, and batched outputs equal the
  closed-form sequential replay of every request;
- real-model end-to-end: a mixed trace served by the engine is
  bit-identical to the unbatched reference serving path, with zero
  retraces and a populated per-phase cycle bill, through both the
  dense-gather decode and the paged-attention Pallas kernel.
"""
import json
import os
import sys
import types

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from repro.engine import (EngineConfig, InferenceEngine, NULL_PAGE,
                          PagePoolExhausted, PageTable, PrefixTree,
                          engine_compatible)


# ------------------------------------------------- page table / trie

def test_pagetable_alloc_share_free_roundtrip():
    t = PageTable(8, 16)
    assert t.free_pages == 7 and t.balanced()
    a = t.alloc(3)
    assert len(set(a)) == 3 and NULL_PAGE not in a
    assert t.used_pages == 3 and t.peak_used == 3
    t.share(a[0])
    t.free(a[0])
    assert t.used_pages == 3          # still referenced once
    for p in a:
        t.free(p)
    assert t.balanced() and t.peak_used == 3


def test_pagetable_errors():
    t = PageTable(4, 16)
    with pytest.raises(PagePoolExhausted):
        t.alloc(4)                    # only 3 non-null pages exist
    p = t.alloc(1)[0]
    t.free(p)
    with pytest.raises(ValueError):
        t.free(p)                     # double free
    with pytest.raises(ValueError):
        t.share(p)                    # share of a dead page
    with pytest.raises(ValueError):
        PageTable(1, 16)              # no room for the null page


def test_prefix_tree_match_insert_clear():
    t = PageTable(16, 4)
    tree = PrefixTree(t)
    pages = t.alloc(3)
    keys = [(1, 2, 3, 4), (5, 6, 7, 8), (9, 10, 11, 12)]
    assert tree.insert(keys, pages) == 3
    # full hit takes one reference per page for the caller
    got = tree.match(keys[:2])
    assert got == pages[:2] and tree.hits == 2
    # diverging path stops at the shared prefix
    assert tree.match([keys[0], (0, 0, 0, 0)]) == pages[:1]
    assert tree.misses == 1
    assert tree.lookup(keys) == 3     # lookup takes no references
    for p in got + pages[:1]:
        t.free(p)
    for p in pages:                   # the requests' own references
        t.free(p)
    assert not t.balanced()           # tree still holds its references
    tree.clear()
    assert t.balanced() and tree.nodes == 0


def test_prefix_tree_lru_victim_order_deterministic():
    """Eviction frees tree-only pages in least-recently-matched order;
    a fresh match moves a branch to the back of the victim line."""
    t = PageTable(16, 4)
    tree = PrefixTree(t)
    pages = t.alloc(3)
    keys = [(i, i, i, i) for i in range(3)]
    for k, p in zip(keys, pages):
        tree.insert([k], [p])
        t.free(p)                     # request gone; tree ref only
    for p in tree.match([keys[1]]):   # re-touch the middle branch
        t.free(p)
    assert tree.evict(2) == [pages[0], pages[2]]   # LRU first, k1 hot
    assert tree.evict(5) == [pages[1]]             # then the rest
    assert tree.nodes == 0 and t.balanced()
    assert tree.evicted == 3


def test_prefix_tree_evict_leaf_first_cascade():
    """A chain a->b->c evicts leaf-first (c, b, a): parents become
    evictable only once their last child is gone."""
    t = PageTable(16, 4)
    tree = PrefixTree(t)
    pages = t.alloc(3)
    keys = [(1, 1, 1, 1), (2, 2, 2, 2), (3, 3, 3, 3)]
    tree.insert(keys, pages)
    for p in pages:
        t.free(p)
    assert tree.evict(3) == pages[::-1]
    assert t.balanced()


def test_prefix_tree_evict_spares_in_use_and_protected():
    t = PageTable(16, 4)
    tree = PrefixTree(t)
    pages = t.alloc(3)
    keys = [(i, i, i, i) for i in range(3)]
    for k, p in zip(keys, pages):
        tree.insert([k], [p])
    t.free(pages[1])                  # only the middle is tree-only
    t.free(pages[2])
    # pages[0] still live (refcount 2) and keys[2] is protected
    assert tree.evict(3, protect=[keys[2]]) == [pages[1]]
    assert t.refcount[pages[0]] == 2 and t.refcount[pages[2]] == 1
    freed = tree.evict_all()          # drain drops every tree ref
    assert freed == [pages[2]]        # pages[0]'s live ref survives
    assert t.refcount[pages[0]] == 1
    t.free(pages[0])
    assert t.balanced()


# ------------------------------------- scheduler properties (fake steps)

_FAKE_VOCAB = 997
_FAKE_PS = 4


def _fake_prefill_tok(prompt):
    return (sum(prompt) * 13 + (len(prompt) - 1) * 5) % _FAKE_VOCAB


def _fake_next_tok(tok, pos):
    return (tok * 31 + pos * 7) % _FAKE_VOCAB


def _fake_replay(prompt, max_new):
    """Closed-form sequential (batch-1) serving of one request."""
    out = [_fake_prefill_tok(prompt)]
    for i in range(max_new - 1):
        out.append(_fake_next_tok(out[-1], len(prompt) + i))
    return out


class _FakeStepEngine(InferenceEngine):
    """Engine with deterministic host-side step fakes: decode output
    depends only on the lane's own (token, position), so any batching
    or padding mistake in the scheduler shows up as a token diff.

    The prefill fake stores each page's token sum in its KV block and
    the cache fake really scatters it into the pool, so the chunkpf fake
    must read its context sums back *through the page table* — a wrong
    ctx page list, a stale pool, or an eviction of an in-use page all
    surface as a first-token mismatch against the sequential replay."""

    def _build(self, phase, size):
        cfg, c = self.model.cfg, self.config

        def kv_block(n_pages, toks):
            shape = (cfg.num_layers, n_pages, c.page_size,
                     cfg.num_kv_heads * cfg.resolved_head_dim)
            k = np.zeros(shape, np.float32)
            k[0, :, 0, 0] = toks.reshape(n_pages, c.page_size).sum(1)
            return k, np.zeros(shape, np.float32)

        def one_hot(tok):
            logits = np.zeros((1, _FAKE_VOCAB), np.float32)
            logits[0, tok] = 1.0
            return logits

        if phase == "prefill":
            def prefill(params, batch):
                toks = np.asarray(batch["tokens"])
                li = int(np.asarray(batch["last_idx"])[0])
                tok = (int(toks.sum()) * 13 + li * 5) % _FAKE_VOCAB
                return (one_hot(tok),) + kv_block(size, toks)
            return prefill
        if phase == "chunkpf":
            cs, n = size

            def chunkpf(params, pk, pv, batch):
                toks = np.asarray(batch["tokens"])
                li = int(np.asarray(batch["last_idx"])[0])
                ctx = np.asarray(batch["ctx_pages"])
                ctx_sum = int(np.asarray(pk)[0, ctx, 0, 0].sum())
                tok = ((ctx_sum + int(toks.sum())) * 13
                       + (cs * c.page_size + li) * 5) % _FAKE_VOCAB
                return (one_hot(tok),) + kv_block(n, toks)
            return chunkpf
        if phase == "cache":
            def scatter(pk, pv, k, v, ids):
                pk = np.asarray(pk).copy()
                pv = np.asarray(pv).copy()
                pk[:, np.asarray(ids)] = np.asarray(k)
                pv[:, np.asarray(ids)] = np.asarray(v)
                return pk, pv
            return scatter

        def decode(params, pk, pv, batch):
            t = np.asarray(batch["tokens"])[:, 0].astype(np.int64)
            p = np.asarray(batch["pos"]).astype(np.int64)
            nt = ((t * 31 + p * 7) % _FAKE_VOCAB).astype(np.int32)
            return np.zeros((size, _FAKE_VOCAB), np.float32), pk, pv, nt
        return decode


def _fake_engine(**overrides):
    cfg = types.SimpleNamespace(
        family="llama", frontend="none", num_layers=1, num_kv_heads=1,
        resolved_head_dim=2, kv_cache_dtype="float32", moe=None)
    model = types.SimpleNamespace(cfg=cfg)
    kw = dict(page_size=_FAKE_PS, pool_pages=10, max_pages=6,
              buckets=(1, 2, 4))
    kw.update(overrides)
    return _FakeStepEngine(model, None, EngineConfig(**kw))


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                # dev-only dependency
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @st.composite
    def _traces(draw):
        """Random request traces: shared prefixes (full pages),
        free-form tails, mixed decode budgets, arbitrary arrival."""
        prefixes = [draw(st.lists(st.integers(0, 50), min_size=_FAKE_PS,
                                  max_size=_FAKE_PS)) for _ in range(2)]
        n = draw(st.integers(1, 8))
        reqs = []
        for _ in range(n):
            base = prefixes[draw(st.integers(0, 1))] \
                if draw(st.booleans()) else []
            tail = draw(st.lists(st.integers(0, 50), min_size=1,
                                 max_size=3 * _FAKE_PS))
            max_new = draw(st.integers(1, 2 * _FAKE_PS))
            prompt = (base + tail)[:6 * _FAKE_PS - max_new + 1]
            reqs.append((prompt, max_new))
        return reqs

    @settings(max_examples=40, deadline=None)
    @given(_traces())
    def test_random_trace_matches_sequential_replay(reqs):
        eng = _fake_engine()
        rids = [eng.submit(p, m) for p, m in reqs]
        done = eng.run()
        by_rid = {r.rid: r for r in done}
        assert sorted(by_rid) == sorted(rids)      # no starvation
        for rid, (prompt, max_new) in zip(rids, reqs):
            assert by_rid[rid].out_tokens == _fake_replay(prompt, max_new)
        eng.drain()
        assert eng.table.balanced()
        assert eng.table.peak_used <= eng.config.pool_pages - 1

    @settings(max_examples=20, deadline=None)
    @given(_traces(), st.booleans())
    def test_random_trace_page_accounting(reqs, prefix_cache):
        eng = _fake_engine(pool_pages=8, prefix_cache=prefix_cache)
        for p, m in reqs:
            eng.submit(p, m)
        done = eng.run()
        assert all(len(r.out_tokens) == m
                   for r, (_, m) in zip(sorted(done, key=lambda r: r.rid),
                                        reqs))
        assert all(not r.pages for r in done)      # released on finish
        eng.drain()
        assert eng.table.balanced()

    @settings(max_examples=40, deadline=None)
    @given(_traces(), st.integers(1, 3))
    def test_random_trace_chunked_matches_replay(reqs, chunk):
        """Chunked prefill (any chunk size) yields the same token
        streams as whole-prompt serving — the fake chunkpf step reads
        its context sums back through the page table, so a wrong ctx
        page list or a stale pool breaks the first token."""
        eng = _fake_engine(prefill_chunk_pages=chunk)
        rids = [eng.submit(p, m) for p, m in reqs]
        done = eng.run()
        by_rid = {r.rid: r for r in done}
        for rid, (prompt, max_new) in zip(rids, reqs):
            assert by_rid[rid].out_tokens == _fake_replay(prompt, max_new)
        eng.drain()
        assert eng.table.balanced()

    @settings(max_examples=40, deadline=None)
    @given(_traces(), st.integers(0, 2),
           st.sampled_from(["lru", "clear"]))
    def test_random_trace_eviction_under_pressure(reqs, chunk, policy):
        """Admit/evict/complete under a pool sized to force eviction:
        token streams still replay exactly (an evicted-in-use page
        would corrupt a chunk's context read or a shared prefix),
        refcounts balance at drain, and the evictor never frees a page
        a live request references."""
        eng = _fake_engine(pool_pages=8, prefill_chunk_pages=chunk,
                           evict_policy=policy)
        rids = [eng.submit(p, m) for p, m in reqs]
        done = eng.run()
        by_rid = {r.rid: r for r in done}
        assert sorted(by_rid) == sorted(rids)
        for rid, (prompt, max_new) in zip(rids, reqs):
            assert by_rid[rid].out_tokens == _fake_replay(prompt, max_new)
        st_ = eng.stats()
        assert st_["evictions"] == eng.evictions >= 0
        eng.drain()
        assert eng.table.balanced()


def test_fake_engine_prefix_sharing_counts():
    eng = _fake_engine()
    shared = list(range(_FAKE_PS))
    eng.submit(shared + [7, 8], 2)
    eng.submit(shared + [9], 2)
    eng.run()
    st_ = eng.stats()
    assert st_["prefix_hits"] == 1 and st_["prefix_misses"] == 1
    assert {r.shared_pages for r in eng.reap()} == {0, 1}
    eng.drain()
    assert eng.table.balanced()


def test_submit_validation_and_compat():
    eng = _fake_engine()
    with pytest.raises(ValueError):
        eng.submit([], 2)
    with pytest.raises(ValueError):
        eng.submit([1], 0)
    with pytest.raises(ValueError):                # needs > max_pages
        eng.submit(list(range(6 * _FAKE_PS)), _FAKE_PS)
    bad = types.SimpleNamespace(cfg=types.SimpleNamespace(
        family="ssm", frontend="none"))
    assert not engine_compatible(bad.cfg)
    with pytest.raises(ValueError):
        InferenceEngine(bad, None)


def test_fcfs_head_blocks_until_pages_free():
    """A large head-of-queue request waits for pool pressure to clear
    but is never overtaken (and eventually completes)."""
    eng = _fake_engine(pool_pages=8, max_pages=6, buckets=(1, 2))
    eng.submit(list(range(10)), 2)                 # 3 pages
    eng.submit(list(range(16)), 5)                 # 5 pages: must wait
    eng.submit([1, 2], 1)                          # 1 page: behind head
    done = eng.run()
    assert [r.rid for r in done] == [0, 1, 2]
    assert all(len(r.out_tokens) == m
               for r, m in zip(done, (2, 5, 1)))
    eng.drain()
    assert eng.table.balanced()


def test_chunked_prefill_unblocks_decode_head_of_line():
    """A long prompt admitted behind a running decode lane counts HoL
    displacement whole-prompt but not chunked — and chunking splits it
    into per-chunk steps interleaved with decode rounds."""
    def serve(**kw):
        eng = _fake_engine(buckets=(1, 2), **kw)
        eng.submit([1, 2, 3], 8)                    # decode-heavy
        eng.submit(list(range(16)), 2)              # 4-page prompt
        done = eng.run()
        st_ = eng.stats()
        eng.drain()
        return done, st_

    whole_done, whole = serve()
    chunk_done, chunk = serve(prefill_chunk_pages=1)
    assert [r.out_tokens for r in whole_done] == \
        [r.out_tokens for r in chunk_done]
    assert whole["hol_blocked_steps"] == 3          # ceil(4/1) - 1
    assert chunk["hol_blocked_steps"] == 0
    assert chunk["phases"]["chunkpf"]["steps"] == 3  # pages 1..3
    assert whole["tokens_out"] == chunk["tokens_out"] == 10


def test_chunked_prefill_shares_completed_chunks_incrementally():
    """A request arriving mid-prefill of a sibling with the same prompt
    shares every chunk the sibling has already finished (the tree is
    fed incrementally, not only at prefill completion)."""
    eng = _fake_engine(prefill_chunk_pages=1, buckets=(1,))
    prompt = list(range(20))                        # 5 full pages
    eng.submit(prompt, 1)
    done = eng.run()
    eng.submit(prompt + [3], 1)                     # same 5-page prefix
    done += eng.run()
    assert done[1].shared_pages == 5
    assert [r.out_tokens for r in done] == \
        [_fake_replay(prompt, 1), _fake_replay(prompt + [3], 1)]
    # skipped fully-shared leading chunks: only the final chunk ran
    # for the second request (pages 5 of 6 -> one chunkpf at ctx 5)
    assert eng.chunk_stats[(5, 1)]["steps"] == 1
    eng.drain()
    assert eng.table.balanced()


def test_engine_config_validation_gates():
    with pytest.raises(ValueError):                 # unknown policy
        _fake_engine(evict_policy="random")
    with pytest.raises(ValueError):                 # donation vs probe
        _fake_engine(donate=True, probe=True)
    with pytest.raises(ValueError):                 # negative chunk
        _fake_engine(prefill_chunk_pages=-1)
    # capacity MoE drops tokens by total count -> chunking refused
    cfg = types.SimpleNamespace(
        family="llama", frontend="none", num_layers=1, num_kv_heads=1,
        resolved_head_dim=2, kv_cache_dtype="float32",
        moe=types.SimpleNamespace(impl="capacity"))
    model = types.SimpleNamespace(cfg=cfg)
    with pytest.raises(ValueError):
        _FakeStepEngine(model, None,
                        EngineConfig(prefill_chunk_pages=2))
    # dropless routing is fine
    cfg.moe = types.SimpleNamespace(impl="ragged")
    _FakeStepEngine(model, None, EngineConfig(prefill_chunk_pages=2))


def test_donation_argnums_per_phase():
    from repro.engine import donation_argnums
    assert donation_argnums("cache") == (0, 1)
    assert donation_argnums("decode") == (1, 2)
    assert donation_argnums("prefill") == ()
    assert donation_argnums("chunkpf") == ()


# ------------------------------------------- real model, bit-identity

def _reference_serve(model, params, prompt, max_new):
    """Unbatched (batch-1, dense-cache) reference token stream."""
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.distributed.steps import build_decode_step, build_prefill_step
    P = len(prompt)
    pf = jax.jit(build_prefill_step(model, ShapeConfig("r", 128, 1,
                                                       "prefill")))
    dec = jax.jit(build_decode_step(model))
    lg, cache = pf(params, {"tokens": jnp.array([prompt], jnp.int32)})
    nt = jnp.argmax(lg, -1).astype(jnp.int32)
    out = [int(nt[0])]
    for i in range(max_new - 1):
        lg, cache, nt = dec(params, cache, {"tokens": nt[:, None],
                                            "pos": jnp.int32(P + i)})
        out.append(int(nt[0]))
    return out


def _mixed_trace(vocab, seed=7):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, 16).tolist()
    prompts = [prefix + rng.integers(0, vocab, 5).tolist(),
               rng.integers(0, vocab, 7).tolist(),
               prefix + rng.integers(0, vocab, 9).tolist()]
    return prompts, [5, 3, 4]


def test_engine_bit_identical_and_probed(tiny_model):
    cfg, model, params = tiny_model
    prompts, max_new = _mixed_trace(cfg.vocab_size)
    refs = [_reference_serve(model, params, p, m)
            for p, m in zip(prompts, max_new)]
    eng = InferenceEngine(model, params, EngineConfig(
        page_size=16, pool_pages=16, max_pages=2, buckets=(1, 2, 4),
        probe=True))
    for p, m in zip(prompts, max_new):
        eng.submit(p, m)
    done = eng.run()
    for r, ref in zip(done, refs):
        assert r.out_tokens == ref                 # bit-identical
    stats = eng.stats()
    assert stats["retraces"] == 0
    assert stats["prefix_hits"] >= 1               # third request reuses
    assert all(v["cycles"] > 0 for v in stats["phases"].values())
    assert all(r.phase_cycles["prefill"] > 0 for r in done)
    assert all(r.phase_cycles["decode"] > 0 for r in done)
    assert "prefill" in eng.phase_table()
    assert "shared pages" in eng.request_table(done)
    eng.drain()
    assert eng.table.balanced()
    eng.close()


def test_chunk_prefill_step_byte_identical(tiny_model):
    """Step-level: a 2-page prompt prefilled page 0 whole + page 1 via
    chunkpf equals the one-shot 2-page prefill byte for byte — logits
    at the real last token AND the page-major KV blocks."""
    import jax.numpy as jnp
    from repro.engine import (build_chunk_prefill, build_engine_prefill,
                              build_page_scatter)
    cfg, model, params = tiny_model
    ps, P = 16, 27
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg.vocab_size, (1, 2 * ps)).astype(np.int32)
    toks[0, P:] = 0
    lg_w, k_w, v_w = jax.jit(build_engine_prefill(model, 2, ps))(
        params, {"tokens": jnp.asarray(toks),
                 "last_idx": jnp.array([P - 1], jnp.int32)})
    lg0, k0, v0 = jax.jit(build_engine_prefill(model, 1, ps))(
        params, {"tokens": jnp.asarray(toks[:, :ps]),
                 "last_idx": jnp.array([ps - 1], jnp.int32)})
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    pool = jnp.zeros((cfg.num_layers, 8, ps, kv * hd),
                     jnp.dtype(cfg.kv_cache_dtype))
    pool_k, pool_v = jax.jit(build_page_scatter(1))(
        pool, pool, k0, v0, jnp.array([3], jnp.int32))
    lg_c, k_c, v_c = jax.jit(build_chunk_prefill(model, 1, 1, ps))(
        params, pool_k, pool_v,
        {"tokens": jnp.asarray(toks[:, ps:]),
         "ctx_pages": jnp.array([3], jnp.int32),
         "last_idx": jnp.array([P - 1 - ps], jnp.int32)})
    assert jnp.array_equal(lg_w, lg_c)
    assert jnp.array_equal(k_w[:, :1], k0) and jnp.array_equal(
        v_w[:, :1], v0)
    assert jnp.array_equal(k_w[:, 1:], k_c) and jnp.array_equal(
        v_w[:, 1:], v_c)


def test_xla_decode_writes_only_the_new_rows(tiny_model):
    """Step-level: the XLA decode over a random lane-dense pool, lanes at
    mixed positions and one padded lane, changes exactly the B written
    (page, slot) rows of every layer; its logits, next tokens and pool
    equal the paged-attention kernel path's on the same inputs."""
    import jax.numpy as jnp
    from repro.engine import NULL_PAGE, build_paged_decode
    cfg, model, params = tiny_model
    ps, n_pages, P = 16, 2, 12
    width = cfg.num_kv_heads * cfg.resolved_head_dim
    shape = (cfg.num_layers, P, ps, width)
    kk, kv_ = jax.random.split(jax.random.PRNGKey(9))
    kvd = jnp.dtype(cfg.kv_cache_dtype)
    pool_k = jax.random.normal(kk, shape).astype(kvd)
    pool_v = jax.random.normal(kv_, shape).astype(kvd)
    pages = np.array([[3, 7], [5, 1], [9, 10], [NULL_PAGE, NULL_PAGE]],
                     np.int32)                         # last lane padded
    pos = np.array([20, 4, 31, 0], np.int32)
    toks = np.array([[11], [22], [33], [0]], np.int32)
    batch = {"tokens": jnp.asarray(toks), "pos": jnp.asarray(pos),
             "pages": jnp.asarray(pages)}
    lg, pk, pv, nt = jax.jit(build_paged_decode(
        model, 4, n_pages, ps, use_kernel=False))(params, pool_k, pool_v,
                                                  batch)
    lg_k, pk_k, pv_k, nt_k = jax.jit(build_paged_decode(
        model, 4, n_pages, ps, use_kernel=True))(params, pool_k, pool_v,
                                                 batch)
    assert np.array_equal(np.asarray(lg), np.asarray(lg_k))
    assert np.array_equal(np.asarray(nt), np.asarray(nt_k))
    written = np.zeros(shape[:3], bool)
    written[:, pages[np.arange(4), pos // ps], pos % ps] = True
    for old, new, ref in ((pool_k, pk, pk_k), (pool_v, pv, pv_k)):
        old, new, ref = map(np.asarray, (old, new, ref))
        assert np.array_equal(new, ref)
        assert np.array_equal(new[~written], old[~written])
        assert not np.any(np.all(new[written] == old[written], axis=-1))


def test_engine_chunked_and_donated_bit_identical(tiny_model):
    """End-to-end: the engine with chunked prefill — probed, and again
    with donated pool buffers forced on — serves the mixed trace with
    the exact whole-prompt token streams and zero retraces."""
    import warnings
    cfg, model, params = tiny_model
    prompts, max_new = _mixed_trace(cfg.vocab_size)
    refs = [_reference_serve(model, params, p, m)
            for p, m in zip(prompts, max_new)]
    eng = InferenceEngine(model, params, EngineConfig(
        page_size=16, pool_pages=16, max_pages=2, buckets=(1, 2, 4),
        probe=True, prefill_chunk_pages=1))
    for p, m in zip(prompts, max_new):
        eng.submit(p, m)
    done = eng.run()
    for r, ref in zip(done, refs):
        assert r.out_tokens == ref
    st_ = eng.stats()
    assert st_["retraces"] == 0
    assert st_["phases"]["chunkpf"]["steps"] >= 1
    assert st_["phases"]["chunkpf"]["cycles"] > 0   # probed like others
    assert "chunk pages" in eng.chunk_table()
    eng.drain()
    assert eng.table.balanced()
    eng.close()

    with warnings.catch_warnings():
        # CPU backends can't honor donation; jax warns but stays correct
        warnings.simplefilter("ignore")
        eng = InferenceEngine(model, params, EngineConfig(
            page_size=16, pool_pages=16, max_pages=2, buckets=(1, 2, 4),
            prefill_chunk_pages=1, donate=True))
        eng.warmup()                   # donation rebinds the pool here
        for p, m in zip(prompts, max_new):
            eng.submit(p, m)
        done = eng.run()
    for r, ref in zip(done, refs):
        assert r.out_tokens == ref
    assert eng.stats()["retraces"] == 0
    eng.drain()
    assert eng.table.balanced()


@pytest.mark.slow
def test_engine_kernel_path_bit_identical(tiny_model):
    """Same trace through the paged-attention Pallas decode kernel."""
    cfg, model, params = tiny_model
    prompts, max_new = _mixed_trace(cfg.vocab_size)
    refs = [_reference_serve(model, params, p, m)
            for p, m in zip(prompts, max_new)]
    eng = InferenceEngine(model, params, EngineConfig(
        page_size=16, pool_pages=16, max_pages=2, buckets=(1, 4),
        use_kernel=True, pages_per_step=2))
    for p, m in zip(prompts, max_new):
        eng.submit(p, m)
    done = eng.run()
    for r, ref in zip(done, refs):
        assert r.out_tokens == ref
    assert eng.stats()["retraces"] == 0
    eng.drain()
    assert eng.table.balanced()


def test_paged_attention_kernel_matches_dense():
    """Kernel-level: Pallas paged attention equals the dense-gather
    einsum reference bit for bit, across pipelining depths."""
    import jax.numpy as jnp
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref
    B, KV, G, HD, PS, NP, POOL = 3, 2, 2, 8, 4, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, KV, G, HD), jnp.float32)
    pk = jax.random.normal(ks[1], (POOL, PS, KV, HD)).astype(jnp.bfloat16)
    pv = jax.random.normal(ks[2], (POOL, PS, KV, HD)).astype(jnp.bfloat16)
    pages = jax.random.permutation(
        ks[3], POOL)[:B * NP].reshape(B, NP).astype(jnp.int32)
    pos = jnp.array([0, 7, 15], jnp.int32)
    ref = paged_attention_ref(q, pk, pv, pages, pos)
    for pps in (1, 2, 4):
        out = paged_attention(q, pk, pv, pages, pos, pages_per_step=pps,
                              interpret=True)
        assert np.array_equal(np.asarray(out), np.asarray(ref)), pps


def test_paged_attention_search_space_registered():
    from repro.kernels.search_spaces import SPACES, paged_attention_space
    assert SPACES["paged_attention"] is paged_attention_space
    space = paged_attention_space(B=2, KV=2, G=1, HD=8, page_size=4,
                                  n_pages=4, pool_pages=16,
                                  pages_per_step=(1, 2, 4))
    assert space.candidates() == [{"pages_per_step": v} for v in (1, 2, 4)]
    fn = space.bind({"pages_per_step": 2})
    out = fn(*space.args)
    assert out.shape == (2, 2, 1, 8)
    assert not space.is_valid({"pages_per_step": 3})


def test_chunked_prefill_search_space_registered():
    """The chunk-size schedule axis is a first-class DSE space: every
    candidate traces, and all chunkings produce bit-identical logits
    and pool contents (a pure schedule change)."""
    from repro.kernels.search_spaces import (SPACES, chunked_prefill_space,
                                             sweep_shapes, sweep_space)
    assert SPACES["chunked_prefill"] is chunked_prefill_space
    space = chunked_prefill_space(prompt_pages=3, page_size=8)
    assert space.axes == {"chunk_pages": (1, 2, 3)}
    assert space.default == {"chunk_pages": 3}
    assert not space.is_valid({"chunk_pages": 4})
    outs = {}
    for cand in space.candidates():
        logits, pk, pv = jax.jit(space.bind(cand))(*space.args)
        outs[cand["chunk_pages"]] = tuple(
            np.asarray(x) for x in (logits, pk, pv))
    ref = outs[3]                      # whole-prompt baseline
    for k, got in outs.items():
        for a, b in zip(got, ref):
            assert np.array_equal(a, b), f"chunk_pages={k} diverged"
    sw = sweep_space("chunked_prefill", prompt_pages=2, page_size=8)
    assert sw.axes == {"chunk_pages": (1, 2)}
    assert sweep_shapes("chunked_prefill") == [{"prompt_pages": 2},
                                               {"prompt_pages": 4}]


@pytest.mark.slow
def test_serve_wrapper_bit_identical_to_legacy():
    """launch.serve routed through the engine returns exactly the
    legacy lock-step loop's tokens (flags preserved, batch=1 incl.)."""
    from repro.launch.serve import serve
    a = serve(batch=2, prompt_len=9, max_new=3, engine=False)
    b = serve(batch=2, prompt_len=9, max_new=3, engine=True)
    assert np.array_equal(a, b)
    c = serve(batch=1, prompt_len=5, max_new=2, engine=True, profile=True)
    d = serve(batch=1, prompt_len=5, max_new=2, engine=False)
    assert np.array_equal(c, d)


@pytest.mark.slow
def test_engine_soak_short():
    from repro.engine.soak import soak
    out = soak(waves=2, requests_per_wave=4, seed=1, verbose=False)
    assert out["served"] == 8 and out["retraces"] == 0


@pytest.mark.slow
def test_engine_soak_pressure_short():
    """Undersized pool: the soak's own asserts cover flat memory and
    balanced drain; here we check pressure actually evicted and the
    chunked scheduler survives the same trace with zero retraces."""
    from repro.engine.soak import soak
    out = soak(waves=2, requests_per_wave=6, seed=1, pressure=True,
               chunk=2, min_hit_rate=0.0, verbose=False)
    assert out["served"] == 12 and out["retraces"] == 0
    assert out["evictions"] > 0
    assert out["buffers_last"] <= out["buffers_first"] + 16


# --------------------------------------------------- golden lock

def test_engine_golden_locked():
    import regen_golden
    path = regen_golden.golden_path(regen_golden.ENGINE_CASE)
    assert os.path.exists(path), \
        "missing tests/golden/engine_serve.json — run tools/regen_golden.py"
    with open(path) as f:
        golden = json.load(f)
    if golden["jax"] != jax.__version__:
        pytest.skip(f"golden for jax {golden['jax']}, running "
                    f"{jax.__version__}")
    got = json.loads(regen_golden.encode(regen_golden.run_engine_case()))
    assert got == golden, (
        "engine serving record drifted — inspect with `python "
        "tools/regen_golden.py --diff --case engine_serve`")
    assert golden["stats"]["retraces"] == 0
    assert golden["stats"]["balanced_after_drain"] is True
