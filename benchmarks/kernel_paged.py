"""Paged-attention kernel microbench (DESIGN.md §16): split-K flash
decoding + int8 KV pages vs the serial page-loop kernel.

Wall-clock on this CPU container measures interpret-mode overhead, not
kernel quality, so the gates are deterministic:

- **parity** — interpret-mode kernels vs the pure-jnp oracle
  (``kernels/ref.py``) on fixed rng(0) shapes, split-K vs serial softmax
  stats (m bitwise — max is exact), int8 pools vs the dequantized
  oracle;
- **modeled kernel roofline** — long-context single-request decode, the
  shape split-K exists for.  The serial kernel chains every page of a
  request through one (m, l, acc) register state, so its critical path
  is ``n_pages`` sequential page steps on ``B*Hkv`` parallel programs;
  split-K cuts the chain to ``pages_per_split`` (+ one combine) and
  multiplies the programs by the split count, and int8 pages halve the
  KV bytes per page step.  Modeled time = max(sequential-chain time,
  aggregate HBM time); MFU = attention FLOPs / (t x peak).

    PYTHONPATH=src python benchmarks/kernel_paged.py [--smoke]
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels import ref as kref
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_splitk_pallas)
from repro.models.attention import dequantize_kv, quantize_kv
from repro.serving.costmodel import A100_80G

# modeled execution resources (A100, the paper's testbed): parallel
# program slots (SMs), and the HBM round-trip latency one page step of
# the sequential (m, l, acc) dependency chain cannot hide
N_PAR = 108
T_LAT = 1e-6
HW = A100_80G
BW_EFF = HW.hbm_bw * HW.bw_eff


def modeled_decode(B, Hq, Hkv, hd, page, ctx, *, pages_per_split=None,
                   int8=False):
    """Modeled kernel time + MFU for one paged-attention layer."""
    n_pages = -(-ctx // page)
    # per-(b, h) program, per page step: K+V tile (+ bf16 scales on int8)
    page_bytes = (page * hd * (1 if int8 else 2) * 2
                  + (page * 2 * 2 if int8 else 0))
    t_page = max(page_bytes / (BW_EFF / N_PAR), T_LAT)
    if pages_per_split:
        n_splits = -(-n_pages // pages_per_split)
        programs = B * Hkv * n_splits
        depth = pages_per_split
        t_combine = T_LAT            # the jnp combine over split partials
    else:
        programs = B * Hkv
        depth = n_pages
        t_combine = 0.0
    waves = -(-programs // N_PAR)
    t_chain = waves * depth * t_page + t_combine
    total_bytes = B * Hkv * n_pages * page_bytes
    t = max(t_chain, total_bytes / BW_EFF)
    flops = 4 * B * Hq * ctx * hd
    return t, flops / (t * HW.peak_flops)


def parity(quick: bool):
    """Max |err| of every kernel variant vs the oracle on fixed shapes."""
    shapes = [(5, 8, 2, 16, 8, 5, 12)]
    if not quick:
        shapes.append((4, 4, 4, 32, 4, 9, 16))
    errs = {"serial": 0.0, "splitk": 0.0, "int8": 0.0, "int8_splitk": 0.0}
    m_bitwise = True
    l_err = 0.0
    t0 = time.monotonic()
    for B, Hq, Hkv, D, page, npages, npool in shapes:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((npool, Hkv, page, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((npool, Hkv, page, D)),
                         jnp.float32)
        bt = jnp.asarray(rng.integers(0, npool, (B, npages)), jnp.int32)
        cl = jnp.asarray(
            [1, page, page + 1, page * npages,
             page * (npages - 1) - 1][:B], jnp.int32)
        ref = np.asarray(kref.paged_attention_ref(q, kp, vp, bt, cl))

        o_s, m_s, l_s = paged_attention_pallas(q, kp, vp, bt, cl,
                                               return_stats=True,
                                               interpret=True)
        errs["serial"] = max(errs["serial"],
                             float(np.abs(np.asarray(o_s) - ref).max()))
        o_k, m_k, l_k = paged_attention_splitk_pallas(
            q, kp, vp, bt, cl, pages_per_split=2, return_stats=True,
            interpret=True)
        errs["splitk"] = max(errs["splitk"],
                             float(np.abs(np.asarray(o_k) - ref).max()))
        m_bitwise = m_bitwise and bool(
            (np.asarray(m_s) == np.asarray(m_k)).all())
        l_err = max(l_err, float(np.abs(np.asarray(l_s)
                                        - np.asarray(l_k)).max()))

        kq, ks = quantize_kv(kp)
        vq, vs = quantize_kv(vp)
        ref_q = np.asarray(kref.paged_attention_ref(
            q, dequantize_kv(kq, ks, jnp.float32),
            dequantize_kv(vq, vs, jnp.float32), bt, cl))
        o_q = paged_attention_pallas(q, kq, vq, bt, cl, k_scale=ks,
                                     v_scale=vs, interpret=True)
        errs["int8"] = max(errs["int8"],
                           float(np.abs(np.asarray(o_q) - ref_q).max()))
        o_qk = paged_attention_splitk_pallas(
            q, kq, vq, bt, cl, pages_per_split=2, k_scale=ks, v_scale=vs,
            interpret=True)
        errs["int8_splitk"] = max(
            errs["int8_splitk"],
            float(np.abs(np.asarray(o_qk) - ref_q).max()))
    wall = time.monotonic() - t0
    return errs, m_bitwise, l_err, wall


def run(quick: bool = False):
    out = []
    errs, m_bitwise, l_err, wall = parity(quick)
    parity_ok = all(e < 1e-5 for e in errs.values()) and m_bitwise \
        and l_err < 1e-4
    out.append(f"kernel_paged/parity,{wall * 1e6:.0f},"
               f"serial={errs['serial']:.2e} splitk={errs['splitk']:.2e} "
               f"int8={errs['int8']:.2e} "
               f"int8_splitk={errs['int8_splitk']:.2e} "
               f"m_bitwise={m_bitwise} l_err={l_err:.2e} ok={parity_ok}")

    # long-context single-request decode (the flash-decoding shape): one
    # llama2-7b attention layer, ctx far past the split-K threshold
    cfg = get_config("llama2-7b")
    B, Hq, Hkv = 1, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim()
    page, ctx, pps = 32, 8192, 4
    t_ser, mfu_ser = modeled_decode(B, Hq, Hkv, hd, page, ctx)
    t_spk, mfu_spk = modeled_decode(B, Hq, Hkv, hd, page, ctx,
                                    pages_per_split=pps)
    t_i8, mfu_i8 = modeled_decode(B, Hq, Hkv, hd, page, ctx,
                                  pages_per_split=pps, int8=True)
    out.append(f"kernel_paged/model_serial,0,"
               f"ctx={ctx} t_us={t_ser * 1e6:.1f} mfu={mfu_ser:.5f}")
    out.append(f"kernel_paged/model_splitk,0,"
               f"ctx={ctx} pages_per_split={pps} t_us={t_spk * 1e6:.1f} "
               f"mfu={mfu_spk:.5f} speedup={t_ser / t_spk:.2f}x")
    out.append(f"kernel_paged/model_splitk_int8,0,"
               f"ctx={ctx} pages_per_split={pps} t_us={t_i8 * 1e6:.1f} "
               f"mfu={mfu_i8:.5f} speedup={t_ser / t_i8:.2f}x")

    ok = parity_ok and mfu_spk > mfu_ser and mfu_i8 >= mfu_spk
    out.append(f"kernel_paged/summary,0,"
               f"mfu_serial={mfu_ser:.5f} mfu_splitk={mfu_spk:.5f} "
               f"mfu_int8={mfu_i8:.5f} parity_ok={parity_ok} ok={ok}")
    return out


def main():
    import argparse

    try:                                   # python -m benchmarks.run
        from benchmarks.common import write_bench_json
    except ImportError:                    # python benchmarks/kernel_paged.py
        from common import write_bench_json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fewer parity shapes for CI")
    args = ap.parse_args()
    lines = run(quick=args.smoke)
    for line in lines:
        print(line, flush=True)
    write_bench_json("kernel_paged", lines, {"smoke": args.smoke})
    ok = lines[-1].rsplit("ok=", 1)[-1] == "True"
    if not ok:
        raise SystemExit(
            "kernel_paged failed its gates: every kernel variant must "
            "match the oracle, and modeled long-context decode MFU must "
            "improve serial -> split-K -> split-K+int8")


if __name__ == "__main__":
    main()
