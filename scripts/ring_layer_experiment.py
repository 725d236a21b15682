"""§Perf D2 quantification: FULL transformer layer (projections + FFN),
replicated-sequence head-TP layout vs ring-attention sequence-parallel
layout, at prefill_32k scale on the 16×16 mesh.

HLO-measured collectives are corrected for the scan-once undercount
(ring ppermutes execute (n-1)× per layer); analytic formulas printed
alongside.  Run:

    python scripts/ring_layer_experiment.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.dryrun import collective_bytes
from repro.models.attention import flash_attention
from repro.models.ring_attention import ring_flash_attention

B, S, H, D, DM, DFF = 32, 32768, 32, 128, 4096, 11008
MESH = jax.make_mesh((16, 16), ("data", "model"))
N = 16


def layer_tp(x, wq, wk, wv, wo, w1, w2):
    """Standard layout: x replicated over model, heads/ffn TP."""
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", x, wk)
    v = jnp.einsum("bsd,dhk->bshk", x, wv)
    o = flash_attention(q, k, v, causal=True)
    x = x + jnp.einsum("bshk,hkd->bsd", o, wo)
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w1))
    return x + jnp.einsum("bsf,fd->bsd", h, w2)


def layer_ring(x, wq, wk, wv, wo, w1, w2):
    """Sequence-parallel layout: x seq-sharded; weights replicated
    (projections are local per seq shard); attention via the ring."""
    def inner(x, wq, wk, wv, wo, w1, w2):
        q = jnp.einsum("bsd,dhk->bshk", x, wq)
        k = jnp.einsum("bsd,dhk->bshk", x, wk)
        v = jnp.einsum("bsd,dhk->bshk", x, wv)
        o = ring_flash_attention(q, k, v, axis_name="model", causal=True)
        x = x + jnp.einsum("bshk,hkd->bsd", o, wo)
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w1))
        return x + jnp.einsum("bsf,fd->bsd", h, w2)

    xs = P("data", "model", None)
    ws = P(*([None] * 3))
    w2s = P(None, None)
    return jax.shard_map(inner, mesh=MESH,
                         in_specs=(xs, ws, ws, ws, ws, w2s, w2s),
                         out_specs=xs)(x, wq, wk, wv, wo, w1, w2)


def measure(fn, shardings):
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in
            [(B, S, DM), (DM, H, D), (DM, H, D), (DM, H, D), (H, D, DM),
             (DM, DFF), (DFF, DM)]]
    with MESH:
        c = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
    cb = collective_bytes(c.as_text())
    mem = c.memory_analysis()
    return cb, mem


def main():
    xr = NamedSharding(MESH, P("data", None, None))
    wh = NamedSharding(MESH, P(None, "model", None))
    wo_ = NamedSharding(MESH, P("model", None, None))
    w1 = NamedSharding(MESH, P(None, "model"))
    w2 = NamedSharding(MESH, P("model", None))
    cb, mem = measure(layer_tp, (xr, wh, wh, wh, wo_, w1, w2))
    print(f"head-TP layer : coll/dev {cb['total_bytes'] / 2**20:8.1f} MiB "
          f"(top-level, complete) temp {mem.temp_size_in_bytes / 2**30:.2f} "
          f"GiB  counts={cb['counts']}")

    xs = NamedSharding(MESH, P("data", "model", None))
    wr = NamedSharding(MESH, P(None, None, None))
    w2r = NamedSharding(MESH, P(None, None))
    cb2, mem2 = measure(layer_ring, (xs, wr, wr, wr, wr, w2r, w2r))
    ring_hlo = cb2["total_bytes"]
    # ppermute sits inside the ring scan body -> executes (N-1)x more
    perm_bytes = cb2["bytes"].get("collective-permute", 0)
    corrected = ring_hlo + perm_bytes * (N - 1)
    print(f"ring SP layer : coll/dev {ring_hlo / 2**20:8.1f} MiB (HLO, "
          f"scan-once) -> {corrected / 2**20:8.1f} MiB corrected "
          f"temp {mem2.temp_size_in_bytes / 2**30:.2f} GiB "
          f"counts={cb2['counts']}")
    # analytic references
    ar = 2 * 2 * (B * S // 16 * DM * 2) * 15 / 16
    ring_an = 2 * (B // 16) * (S // 16) * H * D * 2 * (N - 1)
    print(f"analytic      : head-TP ARs ≈ {ar / 2**20:.1f} MiB/dev/layer, "
          f"ring KV rotation ≈ {ring_an / 2**20:.1f} MiB/dev/layer")


if __name__ == "__main__":
    main()
