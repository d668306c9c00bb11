"""The main path's Pallas kernels and the engine's step programs
compile for a TPU v5e at real widths.

Interpret mode runs a kernel body as plain JAX ops, so it cannot see
what the chip's compiler refuses: blocks off the (8, 128) tiling, dot
shapes Mosaic does not lower, more VMEM than a kernel may use. These
tests compile each kernel for a described (not attached) v5e chip:
tinyllama-1.1b decode and prefill attention widths, and mamba2-370m's
SSD widths. The engine's decode and page-scatter programs compile at
granite-3-2b and minicpm-2b widths, and their optimised HLO must not
relay the KV pool. The topology is described inside a fixture, never
at import, so every xdist worker collects the same tests.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.engine.step import build_page_scatter, build_paged_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or the library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executable can be written to the persistent
    # cache but never read back: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("pages_per_step", [1, 2])
def test_paged_attention_compiles_at_tinyllama_decode(one_chip,
                                                      pages_per_step):
    B, kv, g, hd, page, n_pages = 4, 4, 8, 64, 16, 10
    pool = B * n_pages + 2

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = _compile(
        lambda q, k, v, p, pos: paged_attention(
            q, k, v, p, pos, pages_per_step=pages_per_step),
        s((B, kv, g, hd), jnp.bfloat16),
        s((pool, page, kv, hd), jnp.bfloat16),
        s((pool, page, kv, hd), jnp.bfloat16),
        s((B, n_pages), jnp.int32), s((B,), jnp.int32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("with_probe", [False, True])
def test_flash_attention_compiles_at_tinyllama_prefill(one_chip,
                                                       with_probe):
    S, H, Hkv, D = 2048, 32, 4, 64

    def s(heads):
        return jax.ShapeDtypeStruct((1, heads, S, D), jnp.bfloat16,
                                    sharding=one_chip)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, with_probe=with_probe),
        s(H), s(Hkv), s(Hkv))
    _assert_kernel(compiled)


@pytest.mark.parametrize("pipeline", [1, 2])
def test_ssd_scan_compiles_at_mamba2_370m(one_chip, pipeline):
    # d_inner 2048 / head_dim 64 = 32 heads, d_state 128, one group
    B, H, G, L, P, N = 1, 32, 1, 2048, 64, 128

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda x, a, b, c: ssd_scan(x, a, b, c, chunk=256,
                                    pipeline=pipeline),
        s((B, H, L, P)), s((B, H, L)), s((B, G, L, N)), s((B, G, L, N)))
    _assert_kernel(compiled)


# -------------------------------------------------- engine step programs

_RELAYS = ("copy", "copy-start", "transpose", "dynamic-slice",
           "dynamic-update-slice")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.-]+) = (.*?) ([a-z][\w-]*)\("
                    r"(%[\w.-]+)?")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")


def _relays_at_least(hlo: str, limit: int):
    """Every copy, transpose, dynamic-slice or dynamic-update-slice in an
    optimised HLO module, fused or not, whose result holds ``limit``
    bytes or more. A transpose that permutes nothing and keeps its
    operand's layout (one a gather fusion leaves behind) moves no data
    and is not counted."""
    layouts, found = {}, []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, result, op, operand = m.groups()
        arrays = _ARRAY.findall(result)
        if len(arrays) == 1:
            layouts[name] = arrays[0][2]
        if op not in _RELAYS:
            continue
        if op == "transpose" and layouts.get(operand) == arrays[0][2]:
            dims = re.search(r"dimensions=\{([\d,]*)\}", line).group(1)
            if dims == ",".join(map(str, range(len(dims.split(","))))):
                continue
        for dtype, dims, _ in arrays:
            n = math.prod(int(d) for d in dims.split(",") if d)
            bits = 8 if dtype == "pred" else int(re.sub(r"\D", "", dtype))
            if n * bits // 8 >= limit:
                found.append(f"{op} {name} {dtype}[{dims}]")
    return found


_CELLS = {
    # config, its published overrides, decode bucket, pages per lane,
    # pool pages: the benchmark's chat and longdoc engines
    "granite-3-2b": (dict(param_dtype="bfloat16", tie_embeddings=True),
                     32, 80, 2561),
    "minicpm-2b": (dict(param_dtype="bfloat16", padded_heads=0),
                   8, 140, 561),
}


def _engine_shapes(one_chip, arch):
    from repro.models.model import Model
    over, bucket, n_pages, pool_pages = _CELLS[arch]
    model = Model(get_config(arch).replace(**over))
    cfg = model.cfg

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype),
                                    model.abstract_params())
    width = cfg.num_kv_heads * cfg.resolved_head_dim
    pool = s((cfg.num_layers, pool_pages, 16, width),
             jnp.dtype(cfg.kv_cache_dtype))
    layer_slice = pool_pages * 16 * width * pool.dtype.itemsize
    return model, params, pool, s, bucket, n_pages, layer_slice


@pytest.mark.parametrize("arch", sorted(_CELLS))
def test_decode_moves_no_pool_slice(one_chip, arch):
    """The XLA paged decode reads the pool in place and writes only the
    new rows: no op relays, slices or rewrites a layer's pool slice."""
    model, params, pool, s, bucket, n_pages, limit = _engine_shapes(
        one_chip, arch)
    batch = {"tokens": s((bucket, 1), jnp.int32),
             "pos": s((bucket,), jnp.int32),
             "pages": s((bucket, n_pages), jnp.int32)}
    compiled = jax.jit(
        build_paged_decode(model, bucket, n_pages, 16, use_kernel=False),
        donate_argnums=(1, 2)).lower(params, pool, pool, batch).compile()
    assert _relays_at_least(compiled.as_text(), limit) == []


def test_page_scatter_moves_no_pool_slice(one_chip):
    model, _, pool, s, _, _, limit = _engine_shapes(one_chip,
                                                    "granite-3-2b")
    n = 16
    blk = s(pool.shape[:1] + (n,) + pool.shape[2:], pool.dtype)
    compiled = jax.jit(build_page_scatter(n), donate_argnums=(0, 1)).lower(
        pool, pool, blk, blk, s((n,), jnp.int32)).compile()
    assert _relays_at_least(compiled.as_text(), limit) == []
