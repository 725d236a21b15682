"""Split-K / ragged / int8 paged-attention kernel layer (DESIGN.md §16).

Interpret-mode parity vs the pure-jnp oracle in ``kernels/ref.py`` across
ragged context shapes (at/off page boundaries, single-token, GQA groups),
split-K vs serial softmax statistics (m is bitwise comparable — max is
exact), int8-pool decode pinned within quant noise of fp, the all-masked
ctx=0 l-clamp path, the explicit ValueErrors, and the engine-level
static-shape pin: zero ``_paged_decode_step`` retraces across page
boundaries after warmup.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_FACTORIES
from repro.core import Request, make_scheduler
from repro.kernels import ref as kref
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_splitk_pallas)
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


CASES = [
    # B is fixed at 5 = len(ragged_ctxs): (Hq, Hkv, D, page, npages, npool)
    (4, 4, 16, 8, 5, 12),       # MHA
    (8, 2, 16, 8, 5, 12),       # GQA G=4
    (6, 2, 32, 4, 7, 16),       # GQA G=3, odd page count
]


@pytest.mark.parametrize("Hq,Hkv,D,page,npages,npool", CASES)
def test_serial_parity_ragged_ctx(Hq, Hkv, D, page, npages, npool):
    q, kp, vp, bt = make_case(0, 5, Hq, Hkv, D, page, npages, npool)
    cl = ragged_ctxs(page, npages)
    ref = kref.paged_attention_ref(q, kp, vp, bt, cl)
    out = paged_attention_pallas(q, kp, vp, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("pages_per_split", [1, 2, 4])
@pytest.mark.parametrize("Hq,Hkv,D,page,npages,npool", CASES)
def test_splitk_parity_ragged_ctx(Hq, Hkv, D, page, npages, npool,
                                  pages_per_split):
    q, kp, vp, bt = make_case(1, 5, Hq, Hkv, D, page, npages, npool)
    cl = ragged_ctxs(page, npages)
    ref = kref.paged_attention_ref(q, kp, vp, bt, cl)
    out = paged_attention_splitk_pallas(q, kp, vp, bt, cl,
                                        pages_per_split=pages_per_split,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_splitk_stats_bitwise_m_vs_serial():
    """The combine's row max equals the serial kernel's running max
    BITWISE (max is associative and exact); l agrees to rounding."""
    q, kp, vp, bt = make_case(2, 5, 8, 2, 16, 8, 6, 12)
    cl = ragged_ctxs(8, 6)
    o_s, m_s, l_s = paged_attention_pallas(q, kp, vp, bt, cl,
                                           return_stats=True,
                                           interpret=True)
    for pps in (2, 3):
        o_k, m_k, l_k = paged_attention_splitk_pallas(
            q, kp, vp, bt, cl, pages_per_split=pps, return_stats=True,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_k))
        np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_k),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_k),
                                   atol=1e-5)


def test_ctx_zero_rows_return_exact_zeros():
    """All-masked rows keep l = 0 and the l-clamp returns exact zeros —
    NEG_INF is finite, so without the explicit mask multiply exp(s - m)
    would be 1 everywhere and a ctx=0 row would average garbage V."""
    q, kp, vp, bt = make_case(3, 5, 4, 4, 16, 8, 4, 8)
    cl = jnp.asarray([0, 3, 0, 8, 0], jnp.int32)
    for fn, kw in ((paged_attention_pallas, {}),
                   (paged_attention_splitk_pallas, {"pages_per_split": 2})):
        out = np.asarray(fn(q, kp, vp, bt, cl, interpret=True, **kw))
        assert (out[[0, 2, 4]] == 0).all()
        assert np.abs(out[[1, 3]]).max() > 0


def test_row_map_matches_per_request_launches():
    """The ragged mixed launch: rows sharing a table row via row_map get
    the same result as separate per-row launches."""
    q, kp, vp, bt = make_case(4, 5, 8, 2, 16, 8, 5, 12)
    bt = bt[:2]
    rm = jnp.asarray([0, 0, 0, 1, 1], jnp.int32)
    cl = jnp.asarray([3, 17, 1, 40, 33], jnp.int32)
    out = paged_attention_pallas(q, kp, vp, bt, cl, row_map=rm,
                                 interpret=True)
    for i in range(5):
        one = paged_attention_pallas(q[i:i + 1], kp, vp,
                                     bt[int(rm[i]):int(rm[i]) + 1],
                                     cl[i:i + 1], interpret=True)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(one[0]),
                                   atol=1e-6)


def test_int8_pools_match_dequantized_reference():
    """In-VMEM dequant is exact: the kernel on int8 pools + scales equals
    the oracle on the dequantized pools to fp tolerance, and stays within
    quant noise of the unquantized oracle."""
    q, kp, vp, bt = make_case(5, 5, 8, 2, 16, 8, 5, 12)
    cl = ragged_ctxs(8, 5)
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    kd = dequantize_kv(kq, ks, jnp.float32)
    vd = dequantize_kv(vq, vs, jnp.float32)
    ref_q = kref.paged_attention_ref(q, kd, vd, bt, cl)
    ref_fp = kref.paged_attention_ref(q, kp, vp, bt, cl)
    for fn, kw in ((paged_attention_pallas, {}),
                   (paged_attention_splitk_pallas, {"pages_per_split": 2})):
        out = fn(q, kq, vq, bt, cl, k_scale=ks, v_scale=vs,
                 interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_q),
                                   atol=1e-5)
        err = np.abs(np.asarray(out) - np.asarray(ref_fp)).max()
        assert err < 0.15 * np.asarray(ref_fp).std()


def test_head_divisibility_raises():
    q, kp, vp, bt = make_case(6, 2, 4, 4, 16, 8, 3, 6)
    q5 = jnp.concatenate([q, q[:, :1]], axis=1)          # Hq=5, Hkv=4
    cl = jnp.asarray([3, 9], jnp.int32)
    with pytest.raises(ValueError, match="group evenly"):
        paged_attention_pallas(q5, kp, vp, bt, cl, interpret=True)
    with pytest.raises(ValueError, match="group evenly"):
        paged_attention_splitk_pallas(q5, kp, vp, bt, cl, interpret=True)


def test_zero_width_block_table_raises():
    q, kp, vp, bt = make_case(7, 2, 4, 4, 16, 8, 3, 6)
    cl = jnp.asarray([3, 9], jnp.int32)
    with pytest.raises(ValueError, match="n_pages"):
        paged_attention_pallas(q, kp, vp, bt[:, :0], cl, interpret=True)
    with pytest.raises(ValueError, match="n_pages"):
        paged_attention_splitk_pallas(q, kp, vp, bt[:, :0], cl,
                                      interpret=True)


def test_scale_pair_required_together():
    q, kp, vp, bt = make_case(8, 2, 4, 4, 16, 8, 3, 6)
    cl = jnp.asarray([3, 9], jnp.int32)
    ks = jnp.ones(kp.shape[:-1], jnp.bfloat16)
    with pytest.raises(ValueError, match="together"):
        paged_attention_pallas(q, kp, vp, bt, cl, k_scale=ks,
                               interpret=True)


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
