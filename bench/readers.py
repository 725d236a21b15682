"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

Each reader gets one traced run (``cell.per_layer`` builds it) and
returns a number, or None where the run gives it nothing to read.
"""
from __future__ import annotations

import math

import tracefile
from harness import SCHED_SPANS
from work import attn_bytes, attn_flops, least_time, step_flops

STEP_MODULE = "_paged_decode_step"
# the paged kernel's device operations.  In a TPU trace the Pallas kernel
# is a ``tpu_custom_call`` instruction named after its jitted wrapper
# (``%paged_attention.N``; the kernel functions' own names do not
# appear), and the split-K combine is the fusions that read its outputs
# (``%pallas_call.N`` operands)
KERNEL_NAMES = ("tpu_custom_call", "%paged_attention", "%pallas_call")


def end_to_end(run, name):
    """An end-to-end reading of the traced run, where it has samples."""
    v = run.e2e[name]
    return None if math.isnan(v) else v


def sched_ms_per_iter(run):
    """Host milliseconds per iteration inside the batch core's admission,
    KV reconciliation, prefill plan and lifecycle, over the window."""
    its = run.window_iters
    if not its:
        return None
    total = sum(it.spans.get(s, 0.0) for it in its for s in SCHED_SPANS)
    return 1e3 * total / len(its)


def device_idle_share(run):
    """Percent of the traced slice in which no operation ran on the
    device, averaged over the devices in the trace."""
    if not run.trace.ops or run.t1 <= run.t0:
        return None
    span = run.t1 - run.t0
    idle = [1.0 - tracefile.busy_ns(ops, run.t0, run.t1) / span
            for ops in run.trace.ops]
    return 100.0 * sum(idle) / len(idle)


def step_mfu(run):
    """Useful model operations of the traced steps over the steps' device
    time times the chip's bf16 peak, in percent."""
    its = run.traced_iters
    dev = tracefile.module_ns(run.trace.modules, STEP_MODULE, run.t0,
                              run.t1) * 1e-9
    if not its or dev <= 0:
        return None
    flops = sum(step_flops(run.shape, it.ctxs, it.n_logits) for it in its)
    return 100.0 * flops / (dev * run.peaks["bf16_flops_per_s"])


def paged_attn_roofline(run):
    """The least time the traced steps' attention needs at the chip's
    peaks (live pages read once, q and out, 4*Hq*D*(ctx+1) operations per
    row) over the paged kernel's device time, in percent."""
    its = run.traced_iters
    if not its or not run.trace.ops:
        return None
    dev = tracefile.kernel_ns(run.trace.ops[0], KERNEL_NAMES, run.t0,
                              run.t1) * 1e-9
    if dev <= 0:
        return None
    need = sum(least_time(attn_flops(run.shape, it.ctxs),
                          attn_bytes(run.shape, it.ctxs, it.owners),
                          run.peaks["bf16_flops_per_s"],
                          run.peaks["hbm_bytes_per_s"])[0] for it in its)
    return 100.0 * need / dev
