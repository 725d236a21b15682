"""Compile a configuration's fused step for a described TPU v5e, no chip
attached, and print its memory analysis at the configuration's KV budget.

    JAX_PLATFORMS=cpu python bench/aot.py deepseek7b

Compiles the largest step shape the configuration's cells reach (rows and
table rows from ``harness.buckets``).  Run it before a configuration's
first chip call: what the TPU compiler refuses here costs no chip time.
A compile that passes is not a chip run; in particular it does not count
what loading the program reserves beside its arguments.
"""
import sys

from config import setup

jax = setup(cache=False)
import jax.numpy as jnp  # noqa: E402


def main(name: str):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from config import family, load_config
    from harness import buckets, next_pow2
    from repro.kernels import ops
    from repro.serving.engine import _paged_decode_step
    from repro.serving.kv_cache import make_pools

    ops._interpret = lambda: False        # tracing sees the CPU backend
    c = load_config(name)
    fam = family(c)
    cfg, e = fam.program_config(c), c["engine"]
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    params = on(jax.eval_shape(lambda: fam.program_params(c, 0)))
    rows, tabs = max(buckets(e))
    width = next_pow2(-(-e["max_len"] // e["page_size"]))
    i32 = jnp.int32
    ragged = on((jax.ShapeDtypeStruct((rows,), i32),
                 jax.ShapeDtypeStruct((rows,), i32),
                 jax.ShapeDtypeStruct((tabs, width), i32),
                 jax.ShapeDtypeStruct((rows,), i32)))
    budget = e["kv_budget_tokens"]
    pages = -(-budget // e["page_size"]) + 1
    pools = on(jax.eval_shape(lambda: make_pools(
        cfg.n_layers, pages, e["page_size"], cfg.n_kv_heads,
        cfg.resolved_head_dim(), jnp.dtype(cfg.dtype))))
    m = _paged_decode_step.lower(params, *ragged, *pools, None, None,
                                 cfg, e["page_size"]).compile()
    m = m.memory_analysis()
    gib = 2.0 ** 30
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    print(f"{name}: kv budget {budget} tokens, step {rows} rows x "
          f"{tabs} table rows x {width} pages: arguments "
          f"{m.argument_size_in_bytes / gib:.3f} GiB, outputs "
          f"{m.output_size_in_bytes / gib:.3f} GiB, temporaries "
          f"{m.temp_size_in_bytes / gib:.3f} GiB, total "
          f"{total / gib:.3f} GiB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
