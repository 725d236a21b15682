"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU) with
shape/dtype sweeps — deliverable (c)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, paged_attention, ssd_scan
from repro.kernels import ref as kref

pytestmark = pytest.mark.kernels   # jit-compile heavy: reordered after
#                                    the fast subset (tests/conftest.py)


@pytest.mark.parametrize("S,Hq,Hkv,D,causal,window,bq,bkv", [
    (128, 8, 2, 64, True, 0, 64, 64),
    (160, 8, 8, 32, True, 0, 64, 32),
    (96, 4, 1, 64, True, 48, 32, 32),
    (96, 4, 4, 32, False, 0, 32, 32),
    (100, 4, 2, 16, True, 0, 32, 32),       # ragged -> padding
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel(S, Hq, Hkv, D, causal, window, bq, bkv, dtype, rng):
    q = jnp.asarray(rng.standard_normal((2, S, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((2, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((2, S, Hkv, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_kv=bkv)
    ref = kref.flash_attention_ref(q, k, v, causal=causal, window=window)
    atol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("B,Hq,Hkv,D,page,npages,npool", [
    (3, 8, 2, 32, 16, 5, 32),
    (2, 4, 4, 64, 8, 7, 16),
    (4, 8, 1, 16, 32, 3, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel(B, Hq, Hkv, D, page, npages, npool, dtype, rng):
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((npool, Hkv, page, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((npool, Hkv, page, D)), dtype)
    bt = jnp.asarray(rng.integers(0, npool, (B, npages)), jnp.int32)
    cl = jnp.asarray(rng.integers(1, npages * page, (B,)), jnp.int32)
    out = paged_attention(q, kp, vp, bt, cl)
    ref = kref.paged_attention_ref(q, kp, vp, bt, cl)
    atol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("S,H,P,G,N,chunk", [
    (96, 4, 32, 2, 16, 32),
    (100, 2, 16, 1, 8, 32),      # ragged
    (64, 8, 64, 2, 32, 16),
])
def test_ssd_kernel(S, H, P, G, N, chunk, rng):
    x = jnp.asarray(rng.standard_normal((2, S, H, P)), jnp.float32)
    la = -jnp.abs(jnp.asarray(rng.standard_normal((2, S, H)),
                              jnp.float32)) * 0.1
    Bm = jnp.asarray(rng.standard_normal((2, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((2, S, G, N)), jnp.float32)
    y, st = ssd_scan(x, la, Bm, Cm, chunk=chunk)
    yr, str_ = kref.ssd_scan_ref(x, la, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), atol=5e-4)
