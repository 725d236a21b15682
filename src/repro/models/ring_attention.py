"""Ring flash attention: sequence-parallel exact attention.

The structural answer to §Perf iteration D1: with the sequence sharded
over a mesh axis, each device keeps its Q shard resident and the K/V
shards ROTATE around the ring via ``collective_permute`` — flash
(m, l, acc) statistics merge the partials, so attention is exact while
per-device memory stays O(S/n) and the wire traffic is the KV payload
once around the ring (vs. an all-gather of the whole sequence per layer).

Use inside ``shard_map`` with the sequence axis sharded over
``axis_name``; ``ring_attention_sharded`` wraps that for callers holding
global arrays.  Causality is enforced from global positions (device i
owns sequence chunk i), so entire future chunks contribute nothing and
early-exit devices simply add zero mass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import NEG_INF, _group_heads


def ring_flash_attention(q, k, v, axis_name: str, *, causal: bool = True):
    """Local shards: q (B, S_loc, Hq, D); k/v (B, S_loc, Hkv, D[v]).

    Returns the local output shard (B, S_loc, Hq, Dv).  Must run inside
    ``shard_map`` with the sequence dim sharded over ``axis_name``.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S_loc, Hq, Dk = q.shape
    Hkv = k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    qg = _group_heads(q, Hkv)                       # (B, S, Hkv, G, D)
    scale = Dk ** -0.5
    q_pos = idx * S_loc + jnp.arange(S_loc)

    # accumulators must carry the same varying-mesh-axes type as the data
    # they merge with (q may vary over more axes than the ring's)
    vary_axes = tuple(jax.typeof(q).vma)

    def _mk(x):
        return jax.lax.pcast(x, vary_axes, to="varying") if vary_axes else x

    acc0 = _mk(jnp.zeros((B, S_loc, Hkv, G, Dv), jnp.float32))
    m0 = _mk(jnp.full((B, S_loc, Hkv, G), NEG_INF, jnp.float32))
    l0 = _mk(jnp.zeros((B, S_loc, Hkv, G), jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]     # ring order

    def body(carry, t):
        acc, m, l, k_t, v_t = carry
        src = (idx - t) % n                         # owner of this KV shard
        kv_pos = src * S_loc + jnp.arange(S_loc)
        s = jnp.einsum("bskgd,btkd->bskgt", qg, k_t,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = kv_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bskgt,btkd->bskgd", p.astype(v_t.dtype), v_t,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        return (acc, m_new, l, k_t, v_t), None

    (acc, _, l, _, _), _ = jax.lax.scan(
        body, (acc0, m0, l0, k, v), jnp.arange(n, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S_loc, Hq, Dv).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "data", *,
                           causal: bool = True):
    """Global-array wrapper: shards the sequence dim over ``axis_name``
    and runs the ring inside shard_map."""
    spec = P(None, axis_name, None, None)
    fn = functools.partial(ring_flash_attention, axis_name=axis_name,
                           causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)
