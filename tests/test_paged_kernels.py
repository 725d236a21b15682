"""Live-page paged-attention kernel (DESIGN.md §16).

Interpret-mode parity vs the pure-jnp oracle in ``kernels/ref.py`` across
ragged context shapes (at/off page and block boundaries, single-token,
GQA groups) and forced block sizes; dead pages and dead slots are never
read (NaN there changes nothing); the output does not depend on the
table width; int8 pools within quant noise of fp; the all-masked ctx=0
l-clamp path; how a block's page count follows from the pool's shape;
the explicit ValueErrors; and the engine-level static-shape pin: zero
``_paged_decode_step`` retraces across page boundaries after warmup.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_FACTORIES
from repro.core import Request, make_scheduler
from repro.kernels import ref as kref
from repro.kernels.paged_attention import (lane_dense, paged_attention_pallas,
                                           pages_per_block_for)
from repro.models import init_params
from repro.models.attention import dequantize_kv, quantize_kv
from repro.serving.engine import ServingEngine

pytestmark = pytest.mark.kernels


def make_case(seed, B, Hq, Hkv, D, page, npages, npool, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((npool, Hkv, page, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((npool, Hkv, page, D)), dtype)
    bt = jnp.asarray(rng.integers(0, npool, (B, npages)), jnp.int32)
    return q, kp, vp, bt


def ragged_ctxs(page, npages):
    """One context per edge case: single token, exactly one page, one
    past a boundary, the full table, one short of a boundary."""
    return jnp.asarray([1, page, page + 1, page * npages,
                        page * (npages - 1) - 1], jnp.int32)


def attend(q, kp, vp, bt, cl, **kw):
    return np.asarray(paged_attention_pallas(q, kp, vp, bt, cl,
                                             interpret=True, **kw))


CASES = [
    # B is fixed at 5 = len(ragged_ctxs): (Hq, Hkv, D, page, npages, npool)
    (4, 4, 16, 8, 5, 12),       # MHA
    (8, 2, 16, 8, 5, 12),       # GQA G=4
    (6, 2, 32, 4, 7, 16),       # GQA G=3, odd page count
]


@pytest.mark.parametrize("pages_per_block", [None, 1, 2, 4])
@pytest.mark.parametrize("Hq,Hkv,D,page,npages,npool", CASES)
def test_parity_ragged_ctx(Hq, Hkv, D, page, npages, npool,
                           pages_per_block):
    q, kp, vp, bt = make_case(1, 5, Hq, Hkv, D, page, npages, npool)
    cl = ragged_ctxs(page, npages)
    ref = kref.paged_attention_ref(q, kp, vp, bt, cl)
    out = attend(q, kp, vp, bt, cl, pages_per_block=pages_per_block)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("pages_per_block", [2, 4])
def test_ragged_ctx_block_edges(pages_per_block):
    """Rows that end mid-block, one short of, at and one past a block
    edge, and rows spanning several blocks."""
    page, npages = 4, 12
    blk = pages_per_block * page
    cl = jnp.asarray([blk - 1, blk, blk + 1, blk + page // 2, 2 * blk,
                      2 * blk + 3, page * npages], jnp.int32)
    q, kp, vp, bt = make_case(9, len(cl), 8, 2, 16, page, npages, 40)
    ref = kref.paged_attention_ref(q, kp, vp, bt, cl)
    out = attend(q, kp, vp, bt, cl, pages_per_block=pages_per_block)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-5)


def _nan_outside_live(kp, vp, bt, cl, page):
    """Pools with NaN in every page no row reads and in every slot past
    a row's context in its last page (the tables' pages are disjoint)."""
    live = np.zeros(kp.shape[:1] + kp.shape[2:3], bool)     # (pool, page)
    for row, ctx in zip(np.asarray(bt), np.asarray(cl)):
        for j in range(-(-int(ctx) // page)):
            live[row[j], :min(page, int(ctx) - j * page)] = True
    keep = jnp.asarray(live)[:, None, :, None]
    return (jnp.where(keep, kp, jnp.nan), jnp.where(keep, vp, jnp.nan),
            jnp.where(keep, kp, 0.0), jnp.where(keep, vp, 0.0))


@pytest.mark.parametrize("pages_per_block", [1, 2, 4])
def test_dead_pages_never_read(pages_per_block):
    """Pages past a row's context are never copied and dead slots never
    reach the output: NaN there leaves the output finite and equal to the
    oracle on the same pools with those entries zeroed.  (A kernel that
    only weighted dead V by p = 0 returns NaN: 0 * NaN is NaN.)"""
    page, npages, B = 4, 6, 6
    q, kp, vp, _ = make_case(10, B, 8, 2, 16, page, npages, B * npages)
    bt = jnp.asarray(np.random.default_rng(10).permutation(B * npages)
                     .reshape(B, npages), jnp.int32)
    cl = jnp.asarray([0, 1, 5, 8, 13, 24], jnp.int32)
    kn, vn, kz, vz = _nan_outside_live(kp, vp, bt, cl, page)
    out = attend(q, kn, vn, bt, cl, pages_per_block=pages_per_block)
    assert np.isfinite(out).all()
    ref = kref.paged_attention_ref(q, kz, vz, bt, cl)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("pages_per_block", [1, 2])
def test_output_independent_of_table_width(pages_per_block):
    """The same rows through a table of width W and of 4W — the extra
    columns pointing at NaN pages — give bitwise-equal outputs: a row's
    work follows its context, not the table."""
    page, npages, B = 4, 5, 5
    q, kp, vp, bt = make_case(11, B, 8, 2, 16, page, npages, 12)
    cl = ragged_ctxs(page, npages)
    kp = jnp.concatenate([kp, jnp.full_like(kp[:1], jnp.nan)])
    vp = jnp.concatenate([vp, jnp.full_like(vp[:1], jnp.nan)])
    wide = jnp.concatenate(
        [bt, jnp.full((B, 3 * npages), kp.shape[0] - 1, jnp.int32)], axis=1)
    narrow = attend(q, kp, vp, bt, cl, pages_per_block=pages_per_block)
    np.testing.assert_array_equal(
        narrow, attend(q, kp, vp, wide, cl, pages_per_block=pages_per_block))
    assert np.isfinite(narrow).all()


def test_lane_dense_pools_match_reference():
    """The engine's pools: head_dim zero-padded to whole 128-lane rows
    (``lane_dense``).  q keeps its head_dim; the output does too."""
    q, kp, vp, bt = make_case(12, 5, 8, 2, 16, 8, 5, 12)
    cl = ragged_ctxs(8, 5)
    pad = ((0, 0), (0, 0), (0, 0), (0, lane_dense(16) - 16))
    out = attend(q, jnp.pad(kp, pad), jnp.pad(vp, pad), bt, cl)
    assert out.shape == q.shape
    ref = kref.paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-5)


def test_ctx_zero_rows_return_exact_zeros():
    """All-masked rows keep l = 0 and the l-clamp returns exact zeros —
    NEG_INF is finite, so without the explicit mask multiply exp(s - m)
    would be 1 everywhere and a ctx=0 row would average garbage V."""
    q, kp, vp, bt = make_case(3, 5, 4, 4, 16, 8, 4, 8)
    cl = jnp.asarray([0, 3, 0, 8, 0], jnp.int32)
    for pages_per_block in (1, 2):
        out = attend(q, kp, vp, bt, cl, pages_per_block=pages_per_block)
        assert (out[[0, 2, 4]] == 0).all()
        assert np.abs(out[[1, 3]]).max() > 0


def test_row_map_matches_per_request_launches():
    """The ragged mixed launch: rows sharing a table row via row_map get
    the same result as separate per-row launches, whatever the block."""
    q, kp, vp, bt = make_case(4, 5, 8, 2, 16, 8, 5, 12)
    bt = bt[:2]
    rm = jnp.asarray([0, 0, 0, 1, 1], jnp.int32)
    cl = jnp.asarray([3, 17, 1, 40, 33], jnp.int32)
    for pages_per_block in (1, 2):
        out = attend(q, kp, vp, bt, cl, row_map=rm,
                     pages_per_block=pages_per_block)
        for i in range(5):
            one = attend(q[i:i + 1], kp, vp,
                         bt[int(rm[i]):int(rm[i]) + 1], cl[i:i + 1],
                         pages_per_block=pages_per_block)
            np.testing.assert_allclose(out[i], one[0], atol=1e-6)


def test_int8_pools_match_dequantized_reference():
    """In-VMEM dequant is exact: the kernel on int8 pools + scales equals
    the oracle on the dequantized pools to fp tolerance, and stays within
    quant noise of the unquantized oracle.  ``lane_dense``: the engine's
    pools, head_dim and the scales' slots padded to 128 lanes."""
    q, kp, vp, bt = make_case(5, 5, 8, 2, 16, 8, 5, 12)
    cl = ragged_ctxs(8, 5)
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    kd = dequantize_kv(kq, ks, jnp.float32)
    vd = dequantize_kv(vq, vs, jnp.float32)
    ref_q = kref.paged_attention_ref(q, kd, vd, bt, cl)
    ref_fp = kref.paged_attention_ref(q, kp, vp, bt, cl)
    heads = ((0, 0),) * 3 + ((0, lane_dense(16) - 16),)
    slots = ((0, 0),) * 2 + ((0, lane_dense(8) - 8),)
    layouts = {"compact": (kq, vq, ks, vs, None),
               "block2": (kq, vq, ks, vs, 2),
               "lane_dense": (jnp.pad(kq, heads), jnp.pad(vq, heads),
                              jnp.pad(ks, slots), jnp.pad(vs, slots), None)}
    for kq, vq, ks, vs, ppb in layouts.values():
        out = attend(q, kq, vq, bt, cl, k_scale=ks, v_scale=vs,
                     pages_per_block=ppb)
        np.testing.assert_allclose(out, np.asarray(ref_q), atol=1e-5)
        err = np.abs(out - np.asarray(ref_fp)).max()
        assert err < 0.15 * np.asarray(ref_fp).std()


@pytest.mark.parametrize("Hkv,D,dtype,quant,width,expect", [
    (8, 128, jnp.bfloat16, False, 128, 64),    # Granite-3.0-2B, 32 KB a page
    (32, 128, jnp.bfloat16, False, 128, 16),   # DeepSeek-LLM-7B, 128 KB
    (8, 128, jnp.int8, True, 128, 32),         # Granite, int8 + scales
    (8, 128, jnp.bfloat16, False, 8, 8),       # capped at the table width
    (8, 128, jnp.bfloat16, False, 5, 4),       # ... as a power of two
])
def test_pages_per_block_follows_pool_shape(Hkv, D, dtype, quant, width,
                                            expect):
    """The block's page count comes from the pool's shape and dtype alone:
    pages of 16 slots against a fixed VMEM budget per K slot."""
    kp = jax.ShapeDtypeStruct((1025, Hkv, 16, D), dtype)
    ks = (jax.ShapeDtypeStruct((1025, Hkv, 128), jnp.bfloat16)
          if quant else None)
    assert pages_per_block_for(kp, ks, width) == expect


def test_head_divisibility_raises():
    q, kp, vp, bt = make_case(6, 2, 4, 4, 16, 8, 3, 6)
    q5 = jnp.concatenate([q, q[:, :1]], axis=1)          # Hq=5, Hkv=4
    cl = jnp.asarray([3, 9], jnp.int32)
    with pytest.raises(ValueError, match="group evenly"):
        attend(q5, kp, vp, bt, cl)


def test_zero_width_block_table_raises():
    q, kp, vp, bt = make_case(7, 2, 4, 4, 16, 8, 3, 6)
    cl = jnp.asarray([3, 9], jnp.int32)
    with pytest.raises(ValueError, match="n_pages"):
        attend(q, kp, vp, bt[:, :0], cl)


def test_scale_pair_required_together():
    q, kp, vp, bt = make_case(8, 2, 4, 4, 16, 8, 3, 6)
    cl = jnp.asarray([3, 9], jnp.int32)
    ks = jnp.ones(kp.shape[:-1], jnp.bfloat16)
    with pytest.raises(ValueError, match="together"):
        attend(q, kp, vp, bt, cl, k_scale=ks)


def test_bad_block_or_narrow_pools_raise():
    q, kp, vp, bt = make_case(8, 2, 4, 4, 16, 8, 3, 6)
    cl = jnp.asarray([3, 9], jnp.int32)
    with pytest.raises(ValueError, match="pages_per_block"):
        attend(q, kp, vp, bt, cl, pages_per_block=0)
    with pytest.raises(ValueError, match="lanes per token"):
        attend(q, kp[..., :8], vp[..., :8], bt, cl)


# -- engine-level pins ------------------------------------------------------

def test_decode_width_no_retrace_across_page_boundaries():
    """Satellite regression pin: the fused launch buckets row counts and
    table width to powers of two, so decoding across page boundaries
    never retraces the jitted step (the old dynamic
    ``max(len(pool.owned[rid]))`` width retraced on every crossing)."""
    from repro.serving import engine as engine_mod
    cfg = SMOKE_FACTORIES["llama2-7b"]()
    eng = ServingEngine(cfg, make_scheduler("fcfs"), max_slots=2,
                        max_len=96, kv_budget_tokens=4000, backend="paged",
                        page_size=16, chunked=True,
                        prefill_chunk_tokens=16)
    reqs = [Request(rid=i, client="c", arrival=0.0, prompt_len=8,
                    output_len=60, keywords=("chat",)) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(6):                    # warmup: prefill + first decodes
        eng.step()
    n_traces = engine_mod._paged_decode_step._cache_size()
    pos0 = [r._pos for r in eng.running]
    for _ in range(40):                   # crosses pages 16, 32, 48, 64
        eng.step()
    assert [r._pos for r in eng.running] == [p + 40 for p in pos0]
    assert any((p + 40) // 16 > p // 16 for p in pos0)
    assert engine_mod._paged_decode_step._cache_size() == n_traces


def test_int8_engine_greedy_tokens_match_fp():
    """int8 KV pages end to end (mirrors
    ``test_quantized_decode_close_to_bf16``): same params, greedy decode,
    the quantized pool produces identical token sequences."""
    cfg = SMOKE_FACTORIES["llama2-7b"]()
    params = init_params(jax.random.key(7), cfg)
    rng = np.random.default_rng(11)
    toks = {}
    for kv_quant in (False, True):
        reqs = [Request(rid=i, client=f"client{i % 2}", arrival=0.01 * i,
                        prompt_len=int(rng.integers(8, 20)),
                        output_len=int(rng.integers(4, 7)),
                        keywords=("chat",)) for i in range(4)]
        rng = np.random.default_rng(11)   # same lengths for both arms
        # same explicit budget for both arms so admission/batching are
        # identical and the only difference is the pool dtype
        eng = ServingEngine(cfg, make_scheduler("fcfs"), params=params,
                            max_slots=4, max_len=64, backend="paged",
                            chunked=True, kv_quant=kv_quant,
                            kv_budget_tokens=512)
        done = eng.run(reqs)
        assert len(done) == 4
        toks[kv_quant] = {r.rid: r._next_token for r in done}
    assert toks[True] == toks[False]


def test_kv_quant_requires_paged_chunked():
    cfg = SMOKE_FACTORIES["llama2-7b"]()
    with pytest.raises(AssertionError, match="kv_quant"):
        ServingEngine(cfg, make_scheduler("fcfs"), backend="slots",
                      kv_quant=True)


def test_kv_quant_doubles_default_budget():
    cfg = SMOKE_FACTORIES["llama2-7b"]()
    fp = ServingEngine(cfg, make_scheduler("fcfs"), max_slots=4,
                       max_len=64, backend="paged", chunked=True)
    q = ServingEngine(cfg, make_scheduler("fcfs"), max_slots=4,
                      max_len=64, backend="paged", chunked=True,
                      kv_quant=True)
    assert q.kv_budget == 2 * fp.kv_budget


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """No hidden fallback: the Pallas interpreter runs only on the CPU
    backend; a backend that is neither cpu nor tpu raises."""
    from repro.kernels import ops
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret
