"""Pallas kernels vs pure-jnp oracles (interpret=True shape/dtype sweeps).

``interpret=True`` runs each kernel in the Pallas interpreter on the
CPU; ``tests/test_chip_compile.py`` checks that the same kernels compile
for the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the [test] extra")
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fft_stage import ops as fft_ops
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref

pytestmark = pytest.mark.slow


def t(rng, shape, dt=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dt)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

SWEEP = [
    # B, H, Hkv, S,   D,  causal, window, softcap, dtype
    (1, 2, 2, 128, 64, True, None, None, jnp.float32),
    (2, 4, 2, 256, 64, True, None, None, jnp.float32),
    (1, 4, 1, 128, 128, False, None, None, jnp.float32),
    (1, 2, 2, 256, 64, True, 64, None, jnp.float32),
    (1, 2, 2, 128, 64, True, None, 30.0, jnp.float32),
    (1, 2, 1, 192, 64, True, None, None, jnp.float32),   # ragged S vs block
    (1, 2, 2, 128, 64, True, None, None, jnp.bfloat16),
]


@pytest.mark.parametrize("B,H,Hkv,S,D,causal,window,softcap,dt", SWEEP)
def test_flash_forward(rng, B, H, Hkv, S, D, causal, window, softcap, dt):
    q, k, v = t(rng, (B, H, S, D), dt), t(rng, (B, Hkv, S, D), dt), \
        t(rng, (B, Hkv, S, D), dt)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        softcap=softcap, interpret=True)
    o_ref = attention_ref(q, k, v, causal=causal, window=window,
                          softcap=softcap)
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    assert float(jnp.abs(o.astype(jnp.float32)
                         - o_ref.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("B,H,Hkv,S,D,causal,window,softcap,dt", SWEEP[:5])
def test_flash_backward(rng, B, H, Hkv, S, D, causal, window, softcap, dt):
    q, k, v = t(rng, (B, H, S, D), dt), t(rng, (B, Hkv, S, D), dt), \
        t(rng, (B, Hkv, S, D), dt)

    def loss_k(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       window=window, softcap=softcap,
                                       interpret=True) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=causal, window=window,
                                     softcap=softcap) ** 2)

    g1 = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert rel < 5e-4


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_SWEEP = [
    (1, 64, 2, 16, 1, 16, 16),
    (2, 128, 4, 32, 2, 32, 32),
    (1, 256, 2, 16, 1, 64, 64),
    (1, 128, 4, 16, 1, 16, 128),    # chunk == S
]


@pytest.mark.parametrize("B,S,H,Pd,G,N,chunk", SSD_SWEEP)
def test_ssd_kernel(rng, B, S, H, Pd, G, N, chunk):
    x = t(rng, (B, S, H, Pd))
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, S, H)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    b = t(rng, (B, S, G, N))
    c = t(rng, (B, S, G, N))
    y, stf = ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=True)
    y_ref, st_ref = ssd_ref(x, dt, a, b, c)
    assert float(jnp.abs(y - y_ref).max()
                 / (jnp.abs(y_ref).max() + 1e-9)) < 1e-4
    assert float(jnp.abs(stf - st_ref).max()
                 / (jnp.abs(st_ref).max() + 1e-9)) < 1e-4


def test_ssd_chunk_invariance(rng):
    """Chunk length is an implementation detail: results must agree."""
    B, S, H, Pd, G, N = 1, 128, 2, 16, 1, 32
    x = t(rng, (B, S, H, Pd))
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, S, H)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    b = t(rng, (B, S, G, N))
    c = t(rng, (B, S, G, N))
    y16, _ = ssd_scan(x, dt, a, b, c, chunk=16, interpret=True)
    y64, _ = ssd_scan(x, dt, a, b, c, chunk=64, interpret=True)
    assert float(jnp.abs(y16 - y64).max()) < 1e-4


def test_mamba_chunked_jnp_matches_ref(rng):
    """The model's chunked-jnp SSD path equals the sequential oracle."""
    from repro.models.mamba import MambaConfig, _ssd_chunked
    B, S, H, Pd, G, N = 2, 96, 4, 16, 1, 24
    cfg = MambaConfig(d_model=H * Pd // 2, d_state=N, head_dim=Pd,
                      chunk=32)
    x = t(rng, (B, S, H, Pd))
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, S, H)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    b = t(rng, (B, S, G, N))
    c = t(rng, (B, S, G, N))
    y, st = _ssd_chunked(x, dt, a, jnp.repeat(b, H, 2), jnp.repeat(c, H, 2),
                         cfg)
    y_ref, st_ref = ssd_ref(x, dt, a, b, c)
    assert float(jnp.abs(y - y_ref.astype(jnp.float32)).max()
                 / (jnp.abs(y_ref).max() + 1e-9)) < 1e-4


# ---------------------------------------------------------------------------
# local FFT kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch,n", [(1, 64), (4, 256), (8, 1024),
                                     (3, 4096)])
def test_fft_stage_kernel(rng, batch, n):
    x = (rng.standard_normal((batch, n))
         + 1j * rng.standard_normal((batch, n))).astype(np.complex64)
    y = fft_ops.fft(jnp.asarray(x), interpret=True)
    ref = np.fft.fft(x)
    assert np.abs(np.asarray(y) - ref).max() / np.abs(ref).max() < 1e-5
    xi = fft_ops.ifft(jnp.asarray(ref), interpret=True)
    assert np.abs(np.asarray(xi) - x).max() < 1e-4


@settings(max_examples=8, deadline=None)
@given(st.integers(4, 11))
def test_fft_stage_property(logn):
    n = 1 << logn
    rng = np.random.default_rng(logn)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)
         ).astype(np.complex64)
    y = fft_ops.fft(jnp.asarray(x), interpret=True)
    ref = np.fft.fft(x)
    assert np.abs(np.asarray(y) - ref).max() / np.abs(ref).max() < 1e-5
