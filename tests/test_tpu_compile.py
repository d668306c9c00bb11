"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode runs a kernel body as plain JAX ops, so it cannot see
what the chip's compiler refuses: blocks off the (8, 128) tiling, dot
shapes Mosaic does not lower, more VMEM than a kernel may use. These
tests compile each kernel for a described (not attached) v5e chip:
tinyllama-1.1b decode and prefill attention widths, and mamba2-370m's
SSD widths. The topology is described inside a fixture, never at
import, so every xdist worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
