"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.attention import naive_attention
from repro.models.ssm import ssd_chunked


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    return naive_attention(q, k, v, causal=causal, window=window)


def paged_attention_ref(q, k_pool, v_pool, block_tables, ctx_lens):
    """Gather pages into contiguous caches, then run masked attention."""
    B, Hq, D = q.shape
    n_pool, Hkv, page, _ = k_pool.shape
    n_pages = block_tables.shape[1]

    def gather(pool):     # (B, n_pages, Hkv, page, D) -> (B, S, Hkv, D)
        return pool[block_tables].swapaxes(2, 3).reshape(
            B, n_pages * page, Hkv, -1)

    kc, vc = gather(k_pool), gather(v_pool)
    out = []
    for b in range(B):                            # oracle: clarity over speed
        valid = jnp.arange(n_pages * page) < ctx_lens[b]
        G = Hq // Hkv
        qg = q[b].reshape(Hkv, G, D)
        s = jnp.einsum("kgd,tkd->kgt", qg.astype(jnp.float32),
                       kc[b].astype(jnp.float32)) * D ** -0.5
        s = jnp.where(valid[None, None], s, -1e30)
        w = jnp.exp(s - s.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        o = jnp.einsum("kgt,tkd->kgd", w, vc[b].astype(jnp.float32))
        out.append(o.reshape(Hq, -1))
    return jnp.stack(out).astype(q.dtype)


def ssd_scan_ref(x, la, Bm, Cm, *, chunk=128):
    """Oracle = the model-layer chunked SSD (itself validated against a
    token-by-token recurrence in tests)."""
    return ssd_chunked(x, la, Bm, Cm, chunk)
