"""Ring wire-byte model: HLO text parsing edge cases (variadic tuples,
token operands, iota replica groups) and the traced-jaxpr view that the
mesh probe joins against the scope hierarchy."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.collectives import (jaxpr_collectives,
                                      parse_collective_bytes,
                                      parse_replica_group_size,
                                      ring_wire_bytes)


def test_ring_wire_bytes_formulas():
    assert ring_wire_bytes("all-gather", 800, 8) == 800 * 7 / 8
    assert ring_wire_bytes("reduce-scatter", 100, 4) == 300
    assert ring_wire_bytes("all-reduce", 400, 4) == 2 * 400 * 3 / 4
    assert ring_wire_bytes("all-to-all", 160, 2) == 80
    assert ring_wire_bytes("collective-permute", 64, 1) == 64
    # G == 1 moves nothing for group collectives
    assert ring_wire_bytes("all-reduce", 400, 1) == 0
    assert ring_wire_bytes("all-gather", 400, 1) == 0
    with pytest.raises(ValueError):
        ring_wire_bytes("all-of-the-above", 1, 2)


def test_replica_group_parsing_edge_cases():
    # explicit groups: G = size of the FIRST group
    assert parse_replica_group_size("replica_groups={{0,1,2,3},{4,5,6,7}}") == 4
    assert parse_replica_group_size("replica_groups={{0},{1}}") == 1
    # empty group braces -> all devices, size unknown -> 1 (no traffic)
    assert parse_replica_group_size("replica_groups={{}}") == 1
    # iota form: [n_groups, group_size]<=[total]
    assert parse_replica_group_size("replica_groups=[2,4]<=[8]") == 4
    assert parse_replica_group_size("replica_groups=[8,1]<=[8]") == 1
    # absent attribute (collective-permute)
    assert parse_replica_group_size("source_target_pairs={{0,1}}") == 1


def test_parse_hlo_variadic_tuple_and_token():
    hlo = "\n".join([
        # variadic all-reduce over a tuple INCLUDING a token operand
        "  ar = (f32[4,8]{1,0}, bf16[16]{0}, token[]) all-reduce(a, b, t), "
        "replica_groups={{0,1,2,3}}, to_apply=add",
        # async pair: -start counted once, -done skipped
        "  ag = f32[32,8]{1,0} all-gather-start(x), replica_groups=[2,4]<=[8]"
        ", dimensions={0}",
        "  agd = f32[32,8]{1,0} all-gather-done(ag)",
        # permute has no replica_groups
        "  cp = u32[2]{0} collective-permute(y), "
        "source_target_pairs={{0,1},{1,0}}",
        # a non-collective line must not match
        "  d = f32[8,8]{1,0} dot(p, q), lhs_contracting_dims={1}",
    ])
    out = parse_collective_bytes(hlo)
    ar = out["all-reduce"]
    # token[] contributes 0 bytes; f32[4,8] + bf16[16] = 128 + 32
    assert ar["count"] == 1 and ar["result_bytes"] == 160
    assert ar["wire_bytes"] == pytest.approx(2 * 160 * 3 / 4)
    ag = out["all-gather"]
    assert ag["count"] == 1 and ag["result_bytes"] == 32 * 8 * 4
    assert ag["wire_bytes"] == pytest.approx(32 * 8 * 4 * 3 / 4)
    cp = out["collective-permute"]
    assert cp["count"] == 1 and cp["wire_bytes"] == 8
    assert "dot" not in out and len(out) == 3


def test_jaxpr_collectives_joins_scopes_and_groups():
    from repro.core.meshprobe import extend_axis_env

    def fn(x):
        with jax.named_scope("sync"):
            s = jax.lax.psum(x, "a")            # over axis a (size 2)
        with jax.named_scope("gather"):
            g = jax.lax.all_gather(x, "b")      # over axis b (size 4)
        return jnp.sum(s) + jnp.sum(g)

    sizes = {"a": 2, "b": 4}
    with extend_axis_env(sizes):
        closed = jax.make_jaxpr(fn)(jnp.ones((8,), jnp.float32))
    sites = {s.primitive: s for s in
             jaxpr_collectives(closed.jaxpr, sizes)}
    psum = sites["psum"]
    assert psum.kind == "all-reduce" and psum.group_size == 2
    assert psum.result_bytes == 32
    assert psum.wire_bytes == pytest.approx(2 * 32 * 1 / 2)
    ag = sites["all_gather"]
    assert ag.kind == "all-gather" and ag.group_size == 4
    assert ag.result_bytes == 4 * 32            # gathered along axis b
    assert ag.wire_bytes == pytest.approx(4 * 32 * 3 / 4)


def test_costmodel_collective_term_responds_to_mesh_size():
    """With axis sizes in context the collective term uses ring wire
    bytes (mesh-size sensitive); without, the legacy operand-bytes
    fallback keeps old numbers (baseline compatibility)."""
    from repro.core import costmodel as cm
    from repro.core.meshprobe import extend_axis_env

    def fn(x):
        return jax.lax.psum(x, "dev")

    with extend_axis_env({"dev": 8}):
        closed = jax.make_jaxpr(fn)(jnp.ones((4096,), jnp.float32))
    (eqn,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "psum"]
    legacy = cm.eqn_cost(eqn)
    assert legacy.comm_bytes == 4096 * 4        # operand bytes fallback
    with cm.collective_axis_sizes({"dev": 8}):
        c8 = cm.eqn_cost(eqn)
    with cm.collective_axis_sizes({"dev": 2}):
        c2 = cm.eqn_cost(eqn)
    assert c8.comm_bytes == int(2 * 4096 * 4 * 7 / 8 + 0.5)
    assert c2.comm_bytes == int(2 * 4096 * 4 * 1 / 2)
    assert c8.cycles > c2.cycles                # bigger ring, more cycles
    with cm.collective_axis_sizes(None):
        assert cm.eqn_cost(eqn).comm_bytes == legacy.comm_bytes
