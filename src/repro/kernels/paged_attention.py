"""Pallas TPU paged attention — the serving engine's hot-spot (DESIGN.md
§16).

TPU adaptation of vLLM's PagedAttention: the per-request block table is
*scalar-prefetched* so the kv-pool BlockSpec index maps can chase the
indirection while the previous tile is still streaming HBM→VMEM.  Pool
blocks are (page_size × head_dim) VMEM tiles of one kv head; flash-style
(m, l, acc) statistics for the G query heads of a group live in VMEM
scratch.

Three generalizations over the original one-page-at-a-time kernel:

- **Ragged mixed launch** — ``row_map`` maps each query row to a row of a
  *compact* block table, so one launch serves prefill-chunk rows (many
  rows, one request, staggered ``ctx_lens``) and decode rows (one row per
  request) together.  ``row_map=None`` keeps the legacy one-row-per-table
  contract.
- **Split-K flash decoding** (``paged_attention_splitk_pallas``) — long
  contexts are partitioned across a split grid axis (``pages_per_split``
  pages each); every split emits partial (acc, m, l) and a jnp combine
  merges them.  The serial kernel chains *all* pages of a request through
  one (m, l, acc) register state; split-K cuts that sequential dependency
  to ``pages_per_split`` steps and lets the splits occupy parallel cores.
- **int8 KV pages** — with ``k_scale``/``v_scale`` (per-(slot, head) bf16
  scales matching the ``quantize_kv`` contract) the kernel dequantizes
  int8 page tiles in-VMEM, halving the KV HBM stream.

Inputs:
    q            (B, Hq, D)        one token per query row
    k_pool/v_pool(P, Hkv, page, D) global paged KV pools (fp or int8),
                                   head-major: Mosaic needs a block's last
                                   two dims to be (page, D) tiles, so the
                                   squeezed head axis cannot sit between
    block_tables (T, n_pages)      int32 pool-page ids per table row
    ctx_lens     (B,)              int32 valid context length per query row
    row_map      (B,) or None      int32 table row per query row
    k/v_scale    (P, Hkv, page)    bf16 dequant scales (int8 pools only)

Fully masked rows (``ctx_lens[b] == 0``) return exact zeros: masked
scores contribute ``p = 0`` (an explicit mask multiply — NEG_INF is
finite, so ``exp(s - m)`` alone would give 1 when every score is masked)
and the final ``l``-clamp turns 0/0 into 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _validate(q, k_pool, block_tables, row_map, k_scale, v_scale):
    B, Hq, _ = q.shape
    Hkv = k_pool.shape[1]
    if Hq % Hkv != 0:
        raise ValueError(
            f"paged attention: Hq={Hq} query heads do not group evenly "
            f"over Hkv={Hkv} kv heads (Hq % Hkv != 0 silently mis-sliced "
            f"before this check existed)")
    if block_tables.ndim != 2 or block_tables.shape[1] == 0:
        raise ValueError(
            f"paged attention: block_tables must be (rows, n_pages>=1), "
            f"got {block_tables.shape} — a zero-length page axis leaves "
            f"the output unwritten (garbage)")
    if row_map is None and block_tables.shape[0] != B:
        raise ValueError(
            f"paged attention: {B} query rows but {block_tables.shape[0]} "
            f"block-table rows; pass row_map for ragged launches")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("paged attention: k_scale and v_scale must be "
                         "passed together (int8 pools) or not at all")


def _flash_step(q_ref, k_ref, v_ref, ks_ref, vs_ref, ctx, page_start,
                acc_ref, m_ref, l_ref, *, sm_scale):
    """One page's online-softmax update of the (m, l, acc) scratch."""
    q = q_ref[...].astype(jnp.float32)            # (G, D)
    k = k_ref[...].astype(jnp.float32)            # (page, D)
    v = v_ref[...].astype(jnp.float32)            # (page, Dv)
    if ks_ref is not None:                        # int8 pages: dequant in VMEM
        k = k * ks_ref[...].astype(jnp.float32)   # (page, 1) scales
        v = v * vs_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    tokpos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = tokpos < ctx
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # explicit mask multiply: when EVERY score is masked m_new == NEG_INF
    # (finite), so exp(s - m_new) alone would be exp(0) == 1 and a ctx=0
    # row would average garbage V instead of returning zeros
    p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _paged_kernel(*refs, page, n_pages, sm_scale, quant, stats):
    tables_ref, rows_ref, ctx_ref = refs[:3]
    del tables_ref, rows_ref                      # consumed by index maps
    q_ref, k_ref, v_ref = refs[3:6]
    i = 6
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref = refs[6:8]
        i = 8
    o_ref = refs[i]
    i += 1
    if stats:
        mo_ref, lo_ref = refs[i:i + 2]
        i += 2
    acc_ref, m_ref, l_ref = refs[i:i + 3]

    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _flash_step(q_ref, k_ref, v_ref, ks_ref, vs_ref, ctx_ref[b], j * page,
                acc_ref, m_ref, l_ref, sm_scale=sm_scale)

    @pl.when(j == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        if stats:
            mo_ref[...] = m_ref[...]
            lo_ref[...] = l_ref[...]


def _splitk_kernel(*refs, page, pages_per_split, sm_scale, quant):
    tables_ref, rows_ref, ctx_ref = refs[:3]
    del tables_ref, rows_ref                      # consumed by index maps
    q_ref, k_ref, v_ref = refs[3:6]
    i = 6
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref = refs[6:8]
        i = 8
    acc_out, m_out, l_out = refs[i:i + 3]
    acc_ref, m_ref, l_ref = refs[i + 3:i + 6]

    b = pl.program_id(0)
    s_id = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    page_global = s_id * pages_per_split + j
    _flash_step(q_ref, k_ref, v_ref, ks_ref, vs_ref, ctx_ref[b],
                page_global * page, acc_ref, m_ref, l_ref,
                sm_scale=sm_scale)

    @pl.when(j == pages_per_split - 1)
    def _flush():                                 # partial stats, no division
        acc_out[...] = acc_ref[...]
        m_out[...] = m_ref[...]
        l_out[...] = l_ref[...]


def _prep(q, k_pool, v_pool, block_tables, ctx_lens, row_map, k_scale,
          v_scale):
    """Shared shape plumbing of both launch variants."""
    _validate(q, k_pool, block_tables, row_map, k_scale, v_scale)
    B, Hq, D = q.shape
    Hkv = k_pool.shape[1]
    G = Hq // Hkv
    if row_map is None:
        row_map = jnp.arange(B, dtype=jnp.int32)
    scalars = (jnp.asarray(block_tables, jnp.int32),
               jnp.asarray(row_map, jnp.int32),
               jnp.asarray(ctx_lens, jnp.int32))
    inputs = [q.reshape(B, Hkv, G, D), k_pool, v_pool]
    if k_scale is not None:
        inputs += [k_scale[..., None], v_scale[..., None]]
    return B, Hq, D, Hkv, G, scalars, inputs


def paged_attention_pallas(q, k_pool, v_pool, block_tables, ctx_lens, *,
                           row_map=None, k_scale=None, v_scale=None,
                           return_stats=False, interpret=False):
    """Serial page-innermost variant.  Returns (B, Hq, Dv); with
    ``return_stats`` also the per-row softmax statistics (m, l), each
    (B, Hq) float32 — the cross-variant comparison hook (m is *bitwise*
    comparable with the split-K combine: max is exact)."""
    B, Hq, D, Hkv, G, scalars, inputs = _prep(
        q, k_pool, v_pool, block_tables, ctx_lens, row_map, k_scale,
        v_scale)
    page = k_pool.shape[2]
    Dv = v_pool.shape[-1]
    n_pages = block_tables.shape[1]
    quant = k_scale is not None

    kernel = functools.partial(_paged_kernel, page=page, n_pages=n_pages,
                               sm_scale=D ** -0.5, quant=quant,
                               stats=return_stats)

    def q_index(b, h, j, tables, rows, ctx):
        return (b, h, 0, 0)

    def kv_index(b, h, j, tables, rows, ctx):
        return (tables[rows[b], j], h, 0, 0)

    in_specs = [
        pl.BlockSpec((None, None, G, D), q_index),
        pl.BlockSpec((None, None, page, D), kv_index),
        pl.BlockSpec((None, None, page, Dv), kv_index),
    ]
    if quant:
        in_specs += [pl.BlockSpec((None, None, page, 1), kv_index)] * 2
    o_spec = pl.BlockSpec((None, None, G, Dv), q_index)
    o_shape = jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype)
    if return_stats:
        s_spec = pl.BlockSpec((None, None, G, 1), q_index)
        s_shape = jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32)
        out_specs, out_shape = (o_spec, s_spec, s_spec), \
            (o_shape, s_shape, s_shape)
    else:
        out_specs, out_shape = o_spec, o_shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # block_tables, row_map, ctx_lens
        grid=(B, Hkv, n_pages),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    # named: the kernel's instruction in a profile is
    # ``%paged_attention_serial.N``, whatever jit wraps it
    outs = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret,
                          name="paged_attention_serial")(*scalars, *inputs)
    if return_stats:
        out, m, l = outs
        return (out.reshape(B, Hq, Dv), m.reshape(B, Hq),
                l.reshape(B, Hq))
    return outs.reshape(B, Hq, Dv)


def paged_attention_splitk_pallas(q, k_pool, v_pool, block_tables,
                                  ctx_lens, *, pages_per_split=4,
                                  row_map=None, k_scale=None, v_scale=None,
                                  return_stats=False, interpret=False):
    """Flash-decoding split-K variant (DESIGN.md §16): the page axis is
    partitioned into ``ceil(n_pages / pages_per_split)`` splits; each
    split accumulates private (m, l, acc) partials over its pages and the
    final combine rescales by ``exp(m_s - max_s m_s)`` outside the
    kernel.  Identical math to the serial kernel up to summation order
    (m is bitwise identical — max is exact)."""
    if pages_per_split <= 0:
        raise ValueError(f"pages_per_split must be >= 1, got "
                         f"{pages_per_split}")
    B, Hq, D, Hkv, G, scalars, inputs = _prep(
        q, k_pool, v_pool, block_tables, ctx_lens, row_map, k_scale,
        v_scale)
    page = k_pool.shape[2]
    Dv = v_pool.shape[-1]
    n_pages = block_tables.shape[1]
    quant = k_scale is not None
    n_splits = -(-n_pages // pages_per_split)
    padded = n_splits * pages_per_split
    if padded != n_pages:                  # pad with page 0 — masked by ctx
        tables = jnp.pad(scalars[0], ((0, 0), (0, padded - n_pages)))
        scalars = (tables,) + scalars[1:]

    kernel = functools.partial(_splitk_kernel, page=page,
                               pages_per_split=pages_per_split,
                               sm_scale=D ** -0.5, quant=quant)

    def q_index(b, h, s, j, tables, rows, ctx):
        return (b, h, 0, 0)

    def kv_index(b, h, s, j, tables, rows, ctx):
        return (tables[rows[b], s * pages_per_split + j], h, 0, 0)

    def part_index(b, h, s, j, tables, rows, ctx):
        return (b, h, s, 0, 0)

    in_specs = [
        pl.BlockSpec((None, None, G, D), q_index),
        pl.BlockSpec((None, None, page, D), kv_index),
        pl.BlockSpec((None, None, page, Dv), kv_index),
    ]
    if quant:
        in_specs += [pl.BlockSpec((None, None, page, 1), kv_index)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, n_splits, pages_per_split),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((None, None, None, G, Dv), part_index),
            pl.BlockSpec((None, None, None, G, 1), part_index),
            pl.BlockSpec((None, None, None, G, 1), part_index),
        ),
        scratch_shapes=[
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    acc_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((B, Hkv, n_splits, G, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, n_splits, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, n_splits, G, 1), jnp.float32),
        ),
        interpret=interpret,
        name="paged_attention_splitk",   # ``%paged_attention_splitk.N``
    )(*scalars, *inputs)
    # combine: m = max_s m_s (exact); partials rescale by exp(m_s - m).
    # Splits fully beyond ctx carry (m=NEG_INF, l=0, acc=0) and vanish;
    # a fully masked row keeps l=0 and the clamp returns zeros.
    m = jnp.max(m_p, axis=2, keepdims=True)          # (B, Hkv, 1, G, 1)
    alpha = jnp.exp(m_p - m)
    l = jnp.sum(l_p * alpha, axis=2)                 # (B, Hkv, G, 1)
    acc = jnp.sum(acc_p * alpha, axis=2)             # (B, Hkv, G, Dv)
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    if return_stats:
        return (out.reshape(B, Hq, Dv), m[:, :, 0].reshape(B, Hq),
                l.reshape(B, Hq))
    return out.reshape(B, Hq, Dv)
