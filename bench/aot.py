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

import numpy as np

from config import setup

jax = setup(cache=False)


class _Captured(Exception):
    """Raised by the stand-in step once it holds the step's arguments."""


def step_arguments(c: dict, rows: int, tabs: int, sharding) -> tuple:
    """The arguments the engine hands its fused step for one padding step
    of ``rows`` rows over ``tabs`` table rows (``harness.padding_step``),
    as shapes placed by ``sharding``, statics as they are: the engine is
    built and stepped under ``jax.eval_shape`` with a stand-in step that
    records what it is given, so the weights, the cache state, whatever
    its layout, and the engine's own page count come from the program,
    and nothing is allocated."""
    from config import family
    from harness import Session, padding_step
    from repro.serving import engine as mod
    sess, real, got = Session(c), mod._paged_decode_step, []

    def stand_in(*args):
        got.append(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding)
            if isinstance(a, (jax.Array, np.ndarray)) else a, args))
        raise _Captured

    def one_step():
        params = family(c).program_params(c, 0)
        padding_step(sess.engine_for(params, 0), rows, tabs)

    mod._paged_decode_step = stand_in
    try:
        jax.eval_shape(one_step)
    except _Captured:
        pass
    finally:
        mod._paged_decode_step = real
    return got[0]


def main(name: str):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from config import load_config
    from harness import buckets, next_pow2
    from repro.kernels import ops
    from repro.serving.engine import _paged_decode_step

    ops._interpret = lambda: False        # tracing sees the CPU backend
    c = load_config(name)
    e = c["engine"]
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    rows, tabs = max(buckets(e))
    width = next_pow2(-(-e["max_len"] // e["page_size"]))
    budget = e["kv_budget_tokens"]
    m = _paged_decode_step.lower(
        *step_arguments(c, rows, tabs, chip)).compile()
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
