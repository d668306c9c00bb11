"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py),
all in interpret mode (the kernel body executes as traced JAX ops)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ssdk


def _rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))


@pytest.mark.parametrize("B,H,Hkv,S,D", [
    (1, 4, 4, 128, 32),      # MHA
    (2, 8, 2, 256, 64),      # GQA 4:1
    (1, 4, 1, 128, 64),      # MQA
    (2, 2, 2, 64, 16),       # tiny
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, Hkv, S, D, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(B * H + S), 3)
    q = jax.random.normal(k1, (B, H, S, D)).astype(dtype)
    k = jax.random.normal(k2, (B, Hkv, S, D)).astype(dtype)
    v = jax.random.normal(k3, (B, Hkv, S, D)).astype(dtype)
    o = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert _rel(o.astype(jnp.float32), o_ref.astype(jnp.float32)) < tol


@pytest.mark.parametrize("bq,bk,pp", [
    (64, 64, 2),       # pipelined kv groups
    (64, 64, 4),
    (128, 64, 1),      # unequal blocks: diagonal spans >1 kv block per q
    (64, 32, 4),       #   block (regression: finalize/skip used the q
    (256, 64, 4),      #   block's FIRST row instead of its last)
    (32, 64, 2),
])
def test_flash_attention_blocks_pipeline(bq, bk, pp):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(k1, (2, 4, 256, 32))
    k = jax.random.normal(k2, (2, 4, 256, 32))
    v = jax.random.normal(k3, (2, 4, 256, 32))
    for causal in (True, False):
        o = fa.flash_attention(q, k, v, causal=causal, block_q=bq,
                               block_k=bk, pipeline=pp, interpret=True)
        o_ref = ref.flash_attention_ref(q, k, v, causal=causal)
        assert _rel(o, o_ref) < 2e-5, (bq, bk, pp, causal)


def test_ssd_scan_pipeline():
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    B, H, G, L, P, N = 1, 4, 2, 128, 16, 32
    x = jax.random.normal(ks[0], (B, H, L, P)) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (B, H, L))) * 0.3
    b = jax.random.normal(ks[2], (B, G, L, N)) * 0.5
    c = jax.random.normal(ks[3], (B, G, L, N)) * 0.5
    y_ref, _ = ref.ssd_ref(x, a, b, c)
    for chunk, pp in [(64, 2), (64, 4), (128, 4), (32, 2)]:
        y = ssdk.ssd_scan(x, a, b, c, chunk=chunk, pipeline=pp,
                          interpret=True)
        assert _rel(y, y_ref) < 2e-5, (chunk, pp)


def test_flash_attention_noncausal():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (1, 2, 128, 32))
    k = jax.random.normal(k2, (1, 2, 128, 32))
    v = jax.random.normal(k3, (1, 2, 128, 32))
    o = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=False)
    assert _rel(o, o_ref) < 2e-5


def test_flash_probe_decoupled():
    """The RealProbe in-kernel counters must not change the datapath."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (2, 4, 256, 32))
    k = jax.random.normal(k2, (2, 4, 256, 32))
    v = jax.random.normal(k3, (2, 4, 256, 32))
    o0 = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    o1, probe = ops.flash_attention(q, k, v, causal=True, block_q=64,
                                    block_k=64, with_probe=True)
    assert jnp.array_equal(o0, o1)
    nq = 256 // 64
    probe = np.asarray(probe)
    # visited = all kv blocks; computed = causal prefix only
    assert (probe[..., 0] == nq).all()
    assert (probe[0, 0, :, 1] == np.arange(nq) + 1).all()


@pytest.mark.parametrize("B,H,G,L,P,N,chunk", [
    (1, 4, 1, 128, 16, 32, 32),
    (2, 4, 2, 64, 8, 16, 16),
    (1, 2, 2, 96, 16, 64, 32),
])
def test_ssd_scan_sweep(B, H, G, L, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(L + P), 4)
    x = jax.random.normal(ks[0], (B, H, L, P)) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (B, H, L))) * 0.3
    b = jax.random.normal(ks[2], (B, G, L, N)) * 0.5
    c = jax.random.normal(ks[3], (B, G, L, N)) * 0.5
    y = ssdk.ssd_scan(x, a, b, c, chunk=chunk, interpret=True)
    y_ref, _ = ref.ssd_ref(x, a, b, c)
    assert _rel(y, y_ref) < 2e-5


def test_ssd_model_adapter_matches_xla_path():
    from repro.models.ssm import ssd_chunked_xla
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    B, L, H, P, G, N = 2, 64, 4, 8, 2, 16
    x = jax.random.normal(ks[0], (B, L, H, P)) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (B, L, H))) * 0.3
    b = jax.random.normal(ks[2], (B, L, G, N)) * 0.5
    c = jax.random.normal(ks[3], (B, L, G, N)) * 0.5
    y_xla, _ = ssd_chunked_xla(x, a, b, c, chunk=16, h_per_g=H // G,
                               return_final_state=True)
    y_pl = ops.ssd_scan(x, a, b, c, chunk=16)
    assert _rel(y_pl, y_xla) < 2e-5


def test_flash_gqa_adapter_matches_model_path():
    from repro.models.attention import causal_flash_xla
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, H, HD = 2, 128, 4, 32
    q = jax.random.normal(ks[0], (B, S, H, HD))
    k = jax.random.normal(ks[1], (B, S, H, HD))
    v = jax.random.normal(ks[2], (B, S, H, HD))
    o_xla = causal_flash_xla(q, k, v, 64, 64)
    o_pl = ops.flash_attention(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3),
                               causal=True).transpose(0, 2, 1, 3)
    assert _rel(o_pl, o_xla) < 5e-3   # model path uses bf16 dots


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_default_only_on_cpu(monkeypatch, backend, want):
    """Interpret mode on the CPU, compiled kernels on the TPU, and an
    error anywhere else: a run meant for the chip never lands in the
    interpreter unnoticed."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret_default()
    else:
        assert ops._interpret_default() is want


def _chip_smoke():
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _kv_head_shifted(kernel):
    """Reads kv head h + 1 where head h belongs."""
    return lambda q, pk, pv, pages, pos: kernel(
        q, jnp.roll(pk, 1, axis=2), jnp.roll(pv, 1, axis=2), pages, pos)


def _pages_shifted(kernel):
    """Walks each page-table row one page late."""
    return lambda q, pk, pv, pages, pos: kernel(
        q, pk, pv, jnp.roll(pages, 1, axis=1), pos)


def _mask_page_late(kernel):
    """Masks one page past the current position."""
    return lambda q, pk, pv, pages, pos: kernel(q, pk, pv, pages, pos + 16)


@pytest.mark.parametrize("fault", [None, _kv_head_shifted, _pages_shifted,
                                   _mask_page_late])
def test_chip_smoke_attention_bound_catches_faults(fault):
    """The chip smoke run's kernel check at tinyllama's decode widths:
    the kernel sits inside its bf16 rounding bound, and each planted
    fault lands far outside it."""
    from repro.configs.registry import get_config
    from repro.kernels.paged_attention import paged_attention
    smoke = _chip_smoke()
    kernel = lambda *a: paged_attention(*a, interpret=True)  # noqa: E731
    attend = kernel if fault is None else fault(kernel)
    gap, limit = smoke.attention_gap(attend, get_config("tinyllama-1.1b"),
                                     n_pages=10)
    if fault is None:
        assert gap <= limit
    else:
        assert gap > 4 * limit, (gap, limit)
