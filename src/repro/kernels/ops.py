"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels lower through Mosaic.  On the CPU backend
(the test suite, ``JAX_PLATFORMS=cpu``) they run with ``interpret=True``:
the kernel body executes with real block/grid semantics, so the BlockSpec
indexing is checked but not Mosaic's tiling rules.  Any other backend
raises instead of silently interpreting.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on tpu (Mosaic) or cpu "
                           f"(interpreted), not on {backend!r}")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_kv=128):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_kv=block_kv,
                                  interpret=_interpret())


@jax.jit
def paged_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                    row_map=None, k_scale=None, v_scale=None):
    """Each row walks its live pages only (DESIGN.md §16), at every
    table width."""
    return paged_attention_pallas(q, k_pool, v_pool, block_tables, ctx_lens,
                                  row_map=row_map, k_scale=k_scale,
                                  v_scale=v_scale, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, la, Bm, Cm, *, chunk=128):
    return ssd_scan_pallas(x, la, Bm, Cm, chunk=chunk,
                           interpret=_interpret())
