"""Trace-once cycle simulator (ISSUE 9 tentpole): `price()` must be
integer-identical to BOTH live clocks — the kernel-probed grid replay
(sim mode) and the DSEEngine ProbeSession measurement (flat mode) — and
the artifacts must round-trip canonically and re-price under the
calibration / mesh contexts current at pricing time."""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.core import costmodel as cm
from repro.core import tracesim as ts
from repro.core.dse import DSEEngine
from repro.core.instrument import decode_record
from repro.core.pragma import ProbeConfig, probe
from repro.kernels.search_spaces import (flash_attention_space,
                                         paged_attention_space,
                                         ssd_scan_space)

CASES = {
    "flash_attention": (
        lambda: flash_attention_space(S=128, D=32, blocks_q=(32, 64),
                                      blocks_k=(32, 64), pipelines=(1, 2)),
        [{"block_q": 32, "block_k": 32, "pipeline": 1},
         {"block_q": 64, "block_k": 32, "pipeline": 2}],
    ),
    "ssd_scan": (
        lambda: ssd_scan_space(L=128, chunks=(32, 64), pipelines=(1, 2)),
        [{"chunk": 32, "pipeline": 2}, {"chunk": 64, "pipeline": 1}],
    ),
    "paged_attention": (
        lambda: paged_attention_space(),
        [{"pages_per_step": 2}, {"pages_per_step": 8}],
    ),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def captured(request):
    """One walked capture per golden kernel, shared across tests."""
    build, configs = CASES[request.param]
    space = build()
    trace = ts.capture(space, configs, walk=True,
                       space_fingerprint=ts.space_fingerprint(space))
    return space, configs, trace


def live_grid_replay_cycles(space, config) -> int:
    """The live kernel-probed decode span (the clock sim mode models)."""
    pc = ProbeConfig(targets=("",), max_probes=16, buffer_depth=2,
                     cycle_source="model", kernel_probes=("*",),
                     inline="off_all")
    pf = probe(space.bind(config), pc)
    _, rec = pf(*space.args)
    return int(decode_record(jax.device_get(rec))["cycle"])


# --------------------------------------------------- integer exactness

def test_sim_price_equals_live_grid_replay(captured):
    space, configs, trace = captured
    for cfg in configs:
        sim = ts.price(trace, cfg, mode="sim")
        live = live_grid_replay_cycles(space, cfg)
        assert sim == live, (space.kernel_id, cfg)
        entry = trace.entries[ts.config_key(cfg)]
        assert entry.exact and entry.walked


def test_flat_price_equals_engine_measurement(captured):
    space, configs, trace = captured
    engine = DSEEngine(space, budget=None)
    for cfg in configs:
        flat = ts.price(trace, cfg, mode="flat")
        measured = engine._measure(cfg, 2)
        assert flat == int(measured) == measured, (space.kernel_id, cfg)


def test_calibrated_reprice_matches_measure(captured):
    """Installing a kernel calibration re-prices the SAME artifact to
    the engine's calibrated model clock — no re-capture."""
    space, configs, trace = captured
    cfg = configs[0]
    uncal = ts.price(trace, cfg, mode="flat")
    cm.clear_kernel_calibration()
    try:
        entry = trace.entries[ts.config_key(cfg)]
        for site in entry.sites:
            cm.set_kernel_calibration(site.kernel, 0.5)
        recal = ts.price(trace, cfg, mode="flat")
        assert recal < uncal
        assert recal == DSEEngine(space, budget=None)._measure(cfg, 2)
        # sim mode walks measured branch structure: calibration-free
        assert ts.price(trace, cfg, mode="sim") == ts.price(
            trace, cfg, mode="sim")
    finally:
        cm.clear_kernel_calibration()
    assert ts.price(trace, cfg, mode="flat") == uncal


# ------------------------------------------------------- serialization

def test_trace_json_roundtrip_canonical(captured):
    space, configs, trace = captured
    s1 = ts.to_json(trace)
    back = ts.from_json(s1)
    assert ts.to_json(back) == s1, "round-trip must be byte-identical"
    # canonical: parse -> dump(sorted) is a fixed point
    assert json.dumps(json.loads(s1), sort_keys=True,
                      separators=(",", ":")) == s1
    for cfg in configs:
        assert ts.price(back, cfg, mode="sim") == \
            ts.price(trace, cfg, mode="sim")
        assert ts.price(back, cfg, mode="flat") == \
            ts.price(trace, cfg, mode="flat")


def test_trace_store_merge_and_staleness_key(tmp_path, captured):
    space, configs, trace = captured
    store = ts.TraceStore(str(tmp_path))
    half = ts.KernelTrace(kernel_id=trace.kernel_id, shape=trace.shape,
                          space_fingerprint=trace.space_fingerprint)
    k0, k1 = (ts.config_key(c) for c in configs[:2])
    half.entries[k0] = trace.entries[k0]
    store.merge(half)
    other = ts.KernelTrace(kernel_id=trace.kernel_id, shape=trace.shape,
                           space_fingerprint=trace.space_fingerprint)
    other.entries[k1] = trace.entries[k1]
    merged = store.merge(other)
    assert set(merged.entries) >= {k0, k1}, "merge must keep both writers"
    loaded = store.load(trace.kernel_id, trace.shape,
                        trace.space_fingerprint)
    assert loaded is not None and set(loaded.entries) >= {k0, k1}
    # a kernel edit changes the space fingerprint -> different artifact
    assert store.load(trace.kernel_id, trace.shape, "deadbeef") is None


# --------------------------------------------------- collective context

def test_collective_sites_reprice_with_mesh_context():
    from repro.core.meshprobe import extend_axis_env

    def fn(x):
        return jax.lax.psum(x * 2.0, "dev")

    with extend_axis_env({"dev": 8}):
        closed = jax.make_jaxpr(fn)(jnp.ones((4096,), jnp.float32))
    entry = ts.capture_closed(closed)
    assert len(entry.collectives) == 1
    (eqn,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "psum"]
    base = entry.base_cycles
    # priced against whatever context is CURRENT at price() time, with
    # the same arithmetic as the live eqn cost
    assert ts.price(entry, mode="flat") == base + cm.eqn_cost(eqn).cycles
    with cm.collective_axis_sizes({"dev": 8}):
        p8 = ts.price(entry, mode="flat")
        assert p8 == base + cm.eqn_cost(eqn).cycles
    with cm.collective_axis_sizes({"dev": 2}):
        p2 = ts.price(entry, mode="flat")
        assert p2 == base + cm.eqn_cost(eqn).cycles
    assert p8 > p2, "bigger ring, more wire cycles"


# -------------------------------------------------------- cheap checks

def test_price_requires_config_for_trace(captured):
    space, configs, trace = captured
    with pytest.raises(ValueError):
        ts.price(trace)
    with pytest.raises(KeyError):
        ts.price(trace, {"not": "captured"})
    with pytest.raises(ValueError):
        ts.price(trace, configs[0], mode="oracle")


def test_unwalked_capture_prices_flat_in_sim_mode():
    build, configs = CASES["ssd_scan"]
    space = build()
    entry = ts.capture_entry(space, configs[0], walk=False)
    assert not entry.walked
    assert ts.price(entry, mode="sim") == ts.price(entry, mode="flat")


def test_entry_resources_match_live_analysis():
    build, configs = CASES["flash_attention"]
    space = build()
    cfg = configs[0]
    entry = ts.capture_entry(space, cfg, walk=False)
    closed = jax.make_jaxpr(space.bind(cfg))(*space.args)
    live = cm.jaxpr_kernel_resources(closed.jaxpr)
    got = ts.entry_resources(entry)
    assert (got.vmem_bytes, got.hbm_bytes, got.flops, got.grid_steps) == \
        (live.vmem_bytes, live.hbm_bytes, live.flops, live.grid_steps)
    assert got.static_cycles == live.static_cycles
