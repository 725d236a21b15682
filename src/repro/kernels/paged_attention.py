"""Pallas TPU paged attention — the serving engine's hot-spot (DESIGN.md
§16).

TPU adaptation of vLLM's PagedAttention for a ragged mixed launch: one
grid step per query row, which walks only the row's *live* pages —
``ceil(ctx / page)`` of them — in blocks of ``pages_per_block`` pages and
all kv heads at once.  Pages past the row's context are never copied and
never computed, so the work of a row follows its context, not the table
width.

- **Copies.**  The pools stay in HBM (``memory_space=pl.ANY``).  One page
  for all heads, ``pool[page_id]``, is one contiguous ``(Hkv, page, D)``
  slab in the head-major layout, so a block is ``pages_per_block`` async
  copies into a VMEM buffer of shape ``(2, pages_per_block, Hkv, page,
  D)``.  The copies of the next block — the next row's first block after
  a row's last — start before the current block is computed (double
  buffering across rows too; the grid is sequential).
- **Compute.**  Per kv head, the block's scores are ``(G, D) x (D,
  pages_per_block * page)``, folded into float32 flash statistics
  ``(m, l, acc)``.  Tokens past ``ctx`` are masked in the scores and in V,
  so whatever a dead slot holds never reaches the output.
- **Ragged launch.**  ``row_map`` maps each query row to a row of a
  *compact* block table, so one launch serves prefill-chunk rows (many
  rows, one request, staggered ``ctx_lens``) and decode rows together.
  ``row_map=None`` maps row b to table row b.
- **int8 KV pages.**  With ``k_scale``/``v_scale`` (per-(slot, head)
  bf16 scales, the ``quantize_kv`` contract) the scales are copied per
  page beside K and V and the pages are dequantized in VMEM.

Inputs:
    q            (B, Hq, D)         one token per query row
    k_pool/v_pool(P, Hkv, page, Dp) global paged KV pools (fp or int8);
                                    Dp >= D, zeros past D (the engine's
                                    pools are ``lane_dense``)
    block_tables (T, n_pages)       int32 pool-page ids per table row
    ctx_lens     (B,)               int32 valid context length per query row
    row_map      (B,) or None       int32 table row per query row
    k/v_scale    (P, Hkv, S)        bf16 dequant scales, slot i in lane i,
                                    S >= page (int8 pools only)

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
LANES = 128
# VMEM for one of the four double-buffered K/V block slots.  VMEM tiles
# are (sublanes, 128 lanes), so a page costs its padded footprint.
BLOCK_VMEM_BYTES = 2 * 2**20


def lane_dense(n: int) -> int:
    """``n`` rounded up to whole 128-lane rows.  Mosaic copies only whole
    128-lane tiles out of HBM, so the pools keep their minor axis
    (head_dim; the page's slots in the scale pools) padded to this
    width."""
    return -(-n // LANES) * LANES


def _validate(q, k_pool, block_tables, row_map, k_scale, v_scale):
    B, Hq, D = q.shape
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
    if k_pool.shape[-1] < D:
        raise ValueError(f"paged attention: pools hold {k_pool.shape[-1]} "
                         f"lanes per token, q has head_dim {D}")


def _tile_bytes(rows, cols, dtype):
    """VMEM bytes of a (rows, cols) array: whole tiles of (8 * 32-bit
    packing) sublanes by 128 lanes."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // item)
    return -(-rows // sub) * sub * lane_dense(cols) * item


def pages_per_block_for(k_pool, k_scale, width):
    """Pages a block holds: as many as fit ``BLOCK_VMEM_BYTES`` of one
    K slot (the page's ``(Hkv, page, D)`` tiles, and its scale tiles for
    int8 pools), as a power of two, at most the table width."""
    _, Hkv, page, D = k_pool.shape
    per_page = Hkv * _tile_bytes(page, D, k_pool.dtype)
    if k_scale is not None:
        per_page += _tile_bytes(Hkv, k_scale.shape[-1], k_scale.dtype)
    n = max(1, min(BLOCK_VMEM_BYTES // per_page, width))
    return 1 << (n.bit_length() - 1)


def _scale_columns(s_ref, page):
    """One block's int8 scales ``(ppb, Hkv, lanes)`` (slot i of page p in
    lane i) as ``(T, ppb * Hkv)`` float32: entry (t, p * Hkv + h) is
    head h's scale of slot ``t % page`` in page p.  A one-hot matmul —
    exact, every sum has one term — moves the slots from lanes to
    sublanes, where a token's scale has to sit to scale its row."""
    ppb, Hkv, n = s_ref.shape
    T = ppb * page
    a = s_ref[...].astype(jnp.float32).reshape(ppb * Hkv, n)
    slot = jax.lax.broadcasted_iota(jnp.int32, (T, n), 0) % page
    onehot = slot == jax.lax.broadcasted_iota(jnp.int32, (T, n), 1)
    return jax.lax.dot_general(
        onehot.astype(jnp.bfloat16), a.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _head_column(cols, h, Hkv, page):
    """Head h's per-token scales ``(T, 1)`` out of ``_scale_columns``."""
    T, n = cols.shape
    want = (jax.lax.broadcasted_iota(jnp.int32, (T, n), 0) // page) * Hkv + h
    pick = jax.lax.broadcasted_iota(jnp.int32, (T, n), 1) == want
    return jnp.sum(jnp.where(pick, cols, 0.0), axis=1, keepdims=True)


def _live_kernel(tables_ref, rows_ref, ctx_ref, q_ref, k_hbm, v_hbm,
                 *refs, page, ppb, width, sm_scale, quant, cdt):
    if quant:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, slot_ref,
         m_ref, l_ref, acc_ref) = refs
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                 (vs_hbm, vs_buf))
    else:
        (o_ref, k_buf, v_buf, sem, slot_ref, m_ref, l_ref, acc_ref) = refs
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf))
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    _, _, Hkv, _, D = k_buf.shape
    T = ppb * page                                  # tokens per block

    def live_pages(r):
        return jnp.minimum(pl.cdiv(ctx_ref[r], page), width)

    def n_blocks(r):                 # >= 1: a ctx=0 row still writes zeros
        return jnp.maximum(pl.cdiv(live_pages(r), ppb), 1)

    def block_copies(r, blk, slot, verb):
        """Start (or wait for) the copies of row r's block blk into
        ``slot``: one per live page, K, V (and their scales) each."""
        first = blk * ppb
        n = jnp.clip(live_pages(r) - first, 0, ppb)

        def one(j, carry):
            pid = tables_ref[rows_ref[r], first + j]
            for src, buf in pairs:
                cp = pltpu.make_async_copy(src.at[pid], buf.at[slot, j],
                                           sem.at[slot])
                getattr(cp, verb)()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        block_copies(0, 0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    ctx = jnp.minimum(ctx_ref[b], width * page)   # no token past the table
    nb = n_blocks(b)
    slot0 = slot_ref[0]

    def block(i, carry):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < nb)
        def _next_block():
            block_copies(b, i + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(i + 1 == nb, b + 1 < n_rows))
        def _next_row():
            block_copies(b + 1, 0, 1 - slot, "start")

        block_copies(b, i, slot, "wait")
        tok0 = i * T
        mask = (tok0 + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)) < ctx
        vmask = (tok0 + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)) < ctx
        if quant:
            k_cols = _scale_columns(ks_buf.at[slot], page)
            v_cols = _scale_columns(vs_buf.at[slot], page)

        def head(h, c):
            q = q_ref[h].astype(cdt)                      # (G, D)
            if quant:                                     # dequant in VMEM
                k = (k_buf[slot, :, h].astype(jnp.float32).reshape(T, D)
                     * _head_column(k_cols, h, Hkv, page))
                v = (v_buf[slot, :, h].astype(jnp.float32).reshape(T, D)
                     * _head_column(v_cols, h, Hkv, page))
            else:
                k = k_buf[slot, :, h].reshape(T, D)
                v = v_buf[slot, :, h].reshape(T, D)
            # a dead slot may hold anything (NaN included): 0 * NaN is
            # NaN, so V is zeroed, not just weighted by p = 0
            v = jnp.where(vmask, v, 0).astype(cdt)
            s = jax.lax.dot_general(
                q, k.astype(cdt), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(mask, s, NEG_INF)               # (G, T)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # explicit mask multiply: when EVERY score is masked m_new ==
            # NEG_INF (finite), so exp(s - m_new) alone would be 1
            p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(cdt), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
            return c

        jax.lax.fori_loop(0, Hkv, head, 0)
        return carry

    jax.lax.fori_loop(0, nb, block, 0)
    slot_ref[0] = (slot0 + nb) % 2
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, block_tables, ctx_lens, *,
                           row_map=None, k_scale=None, v_scale=None,
                           pages_per_block=None, interpret=False):
    """Returns (B, Hq, D).  The pools may carry more lanes per token than
    q's head_dim D (``lane_dense``; zeros there), and int8 scale pools
    more lanes than a page has slots.  ``pages_per_block=None`` derives
    the block from the pools' shapes and dtypes
    (``pages_per_block_for``); tests force small blocks.  The matmuls
    take q's and the pool's common dtype (bf16 for bf16 or int8 pools
    under bf16 activations), accumulating in float32."""
    _validate(q, k_pool, block_tables, row_map, k_scale, v_scale)
    B, Hq, D = q.shape
    _, Hkv, page, Dp = k_pool.shape
    G = Hq // Hkv
    width = block_tables.shape[1]
    quant = k_scale is not None
    if pages_per_block is None:
        pages_per_block = pages_per_block_for(k_pool, k_scale, width)
    if pages_per_block <= 0:
        raise ValueError(f"pages_per_block must be >= 1, got "
                         f"{pages_per_block}")
    if row_map is None:
        row_map = jnp.arange(B, dtype=jnp.int32)
    scalars = (jnp.asarray(block_tables, jnp.int32),
               jnp.asarray(row_map, jnp.int32),
               jnp.asarray(ctx_lens, jnp.int32))
    q = jnp.pad(q, ((0, 0), (0, 0), (0, Dp - D))).reshape(B, Hkv, G, Dp)
    inputs = [q, k_pool, v_pool]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    row_spec = pl.BlockSpec((None, Hkv, G, Dp), lambda b, *_: (b, 0, 0, 0))
    in_specs = [row_spec, any_spec, any_spec]
    scratch = [pltpu.VMEM((2, pages_per_block, Hkv, page, Dp),
                          k_pool.dtype)] * 2
    if quant:
        inputs += [k_scale, v_scale]
        in_specs += [any_spec, any_spec]
        scratch += [pltpu.VMEM((2, pages_per_block) + k_scale.shape[1:],
                               k_scale.dtype)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),         # the next block's slot
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, Dp), jnp.float32)]
    kernel = functools.partial(
        _live_kernel, page=page, ppb=pages_per_block, width=width,
        sm_scale=D ** -0.5, quant=quant,
        cdt=jnp.promote_types(q.dtype, k_pool.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # block_tables, row_map, ctx_lens
        grid=(B,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    # named: the kernel's instruction in a profile is
    # ``%paged_attention_live.N``, whatever jit wraps it
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dp), q.dtype),
        # rows run in order: each prefetches the next row's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_attention_live",
    )(*scalars, *inputs)
    return out.reshape(B, Hq, Dp)[..., :D]
