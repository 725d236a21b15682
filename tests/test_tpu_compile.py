"""Ahead-of-time compiles for a described TPU v5e chip (DESIGN.md §16).

Interpret mode checks a kernel's indexing but not Mosaic's tiling rules,
and a CPU run cannot tell whether a whole step fits the chip's HBM.  These
tests hand shapes (never arrays) on a described ``v5e:2x2`` device to the
TPU compiler, at Granite-3.0-2B's published widths, and check that every
paged-attention variant and the fused serving step lower to a Mosaic
kernel (``tpu_custom_call``).  Nothing runs, so nothing here is a timing.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and only the worker that runs this
file loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_splitk_pallas)

pytestmark = pytest.mark.kernels

CFG = get_config("granite-3-2b")
PAGE = 16
ROWS = 512                       # one iteration's ragged query rows
TABLE = (32, 2048 // PAGE)       # table rows x pages of max_len=2048
POOL_PAGES = 32768 // PAGE + 1   # a 32k-token KV budget + the scratch page
HBM_BYTES = 16 * 2**30           # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _pool_shapes(sharding, quant):
    from repro.serving.kv_cache import make_pools
    pools = jax.eval_shape(lambda: make_pools(
        CFG.n_layers, POOL_PAGES, PAGE, CFG.n_kv_heads,
        CFG.resolved_head_dim(), jnp.bfloat16, quantized=quant))
    pools = _on(sharding, pools)
    return pools if quant else (*pools, None, None)


def _ragged_shapes(sharding):
    i32 = jnp.int32
    return (jax.ShapeDtypeStruct((ROWS,), i32, sharding=sharding),
            jax.ShapeDtypeStruct((ROWS,), i32, sharding=sharding),
            jax.ShapeDtypeStruct(TABLE, i32, sharding=sharding),
            jax.ShapeDtypeStruct((ROWS,), i32, sharding=sharding))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kernel", [paged_attention_pallas,
                                    paged_attention_splitk_pallas],
                         ids=["serial", "splitk"])
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, quant):
    H, Hkv, D = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim()
    q = jax.ShapeDtypeStruct((ROWS, H, D), jnp.bfloat16, sharding=one_chip)
    # one layer's pools
    kp, vp, ks, vs = (None if a is None else
                      jax.ShapeDtypeStruct(a.shape[1:], a.dtype,
                                           sharding=one_chip)
                      for a in _pool_shapes(one_chip, quant))
    _, ctx, tables, rows = _ragged_shapes(one_chip)

    def fn(q, kp, vp, tables, ctx, rows, ks, vs):
        return kernel(q, kp, vp, tables, ctx, row_map=rows, k_scale=ks,
                      v_scale=vs, interpret=False)

    compiled = jax.jit(fn).lower(q, kp, vp, tables, ctx, rows, ks,
                                 vs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_decode_step_compiles_for_v5e(one_chip, quant, monkeypatch):
    """The whole 40-layer fused step at full width.  Tracing sees the CPU
    backend, so the kernel is steered out of interpret mode here."""
    from repro.kernels import ops
    from repro.models import init_params
    from repro.serving.engine import _paged_decode_step
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    params = _on(one_chip, jax.eval_shape(
        lambda: init_params(jax.random.key(0), CFG)))
    compiled = _paged_decode_step.lower(
        params, *_ragged_shapes(one_chip), *_pool_shapes(one_chip, quant),
        CFG, PAGE).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB does not fit a v5e"
