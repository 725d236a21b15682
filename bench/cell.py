"""One run of one cell, from set-up to the result line's contents.

``run_cell`` does not look at the platform: ``run.py`` refuses to start
without the chip, and the CPU rehearsal in ``bench/tests`` calls this
function directly on a tiny configuration.
"""
from __future__ import annotations

import importlib.util
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

from config import BENCH, ROOT, cell_metrics, family, load_config
from harness import (SPANS, CompileCount, Session, end_to_end, free, serve,
                     sample, check)
from stats import percentile
from traffic import generate, load_mix

TRACE_FROM = 0.6     # the traced slice: from this share of the window
#                      to its end


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(devices, n: int) -> dict:
    stats = [d.memory_stats() or {} for d in devices[:n]]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": n,
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def per_layer(workload, c, peaks, out, e2e, trace_dir, device) -> tuple:
    """The cell's per-layer metrics from the traced slice, the device's
    busy and window seconds, and the breakdown."""
    import tracefile
    tr = tracefile.load(str(trace_dir), set(SPANS))
    starts = [s for _, s, _ in tr.spans] + [o[1] for d in tr.ops for o in d]
    ends = [e for _, _, e in tr.spans] + [o[2] for d in tr.ops for o in d]
    t0, t1 = min(starts), max(ends)
    a, b = out.trace_span
    run = SimpleNamespace(
        c=c, work=family(c).work(c, c["engine"]["page_size"]), peaks=peaks,
        trace=tr, t0=t0, t1=t1, e2e=e2e,
        window_iters=[it for it in out.iters if it.t1 <= out.seconds],
        traced_iters=[it for it in out.iters if it.t0 >= a and it.t1 <= b])
    metrics = {}
    for m in cell_metrics(workload, trace=True):
        v = load_reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = [tracefile.busy_ns(ops, t0, t1) * 1e-9 for ops in tr.ops]
    device["busy_s"] = float(np.mean(busy)) if busy else 0.0
    device["window_s"] = (t1 - t0) * 1e-9
    ops = tr.ops[0] if tr.ops else []
    breakdown = {"device_ops": tracefile.top_ops(ops, t0, t1),
                 "idle_gaps": tracefile.top_gaps(ops, tr.spans, t0, t1)}
    return metrics, breakdown


def verdict(limits: dict, numbers: dict) -> tuple:
    """``correct`` and the numbers compared, each beside its limit."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = bool(numbers["_tokens"]) and all(
        v["limit"] is not None and v["value"] <= v["limit"]
        for v in checks.values())
    return correct, checks


def slowest(iters, seconds: float) -> str:
    """The window's longest iteration and its host spans, for the log."""
    done = [it for it in iters if it.t1 <= seconds]
    if not done:
        return "no iteration ended inside the window"
    it = max(done, key=lambda it: it.t1 - it.t0)
    spans = ", ".join(f"{k} {v:.3f}" for k, v in sorted(it.spans.items()))
    return (f"slowest iteration {it.t1 - it.t0:.3f} s at {it.t0:.3f} s "
            f"({len(it.ctxs)} rows; host spans s: {spans})")


def run_cell(workload: str, cell: dict, seed: int, seconds: float,
             trace: bool, t_start: float, devices, peaks: dict,
             control: bool = False, sess: Session = None) -> dict:
    """One run of the cell.  With ``control`` the result's ``correct`` and
    ``checks`` are the fp8 control's: its first choices at the same
    positions, in the served tokens' place, judged by the same limits;
    the program's own are under ``program``.  ``sess`` lets a tool serve
    several seeds in one process."""
    import jax
    c = load_config(cell["config"])
    mix = load_mix(cell["traffic"])
    sess = sess or Session(c)
    params = family(c).program_params(c, seed)
    jax.block_until_ready(params)
    eng = sess.engine_for(params, seed)
    del params
    n_shapes = sess.warm_up(eng)
    specs = generate(mix, seed, seconds, c["vocab_size"])
    compiles = CompileCount()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s; {n_shapes} step shapes warmed; "
        f"{len(specs)} requests due in {seconds} s")
    trace_dir = None
    if trace:
        trace_dir = ROOT / "bench_out" / "traces" / f"{workload}.{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = serve(eng, specs, seconds, compiles,
                trace_dir=str(trace_dir) if trace else None,
                trace_from=TRACE_FROM)
    e2e = end_to_end(out)
    log(f"window: {e2e['_n']['iterations']} iterations, "
        f"{e2e['_n']['tokens']} tokens; compiles inside the window: "
        f"{out.compiles_in_window} (fused-step shapes traced: "
        f"{out.step_traces_in_window}); drain {out.drain_s:.3f} s")
    log(f"submission lateness: p50 {percentile(out.late, 50):.6f} s, "
        f"max {max(out.late, default=0.0):.6f} s over {len(out.late)}")
    log(slowest(out.iters, seconds))
    log(f"garbage collections inside the window: {len(out.gc_in_window)} "
        f"(full: {sum(g == 2 for g, _ in out.gc_in_window)}), longest "
        f"{max((t for _, t in out.gc_in_window), default=0.0):.6f} s")
    log(f"samples: {e2e['_n']}")
    device = device_info(devices, cell["chips"])
    from repro.core.request import DROPPED, THROTTLED
    failed = sum(r.state in (DROPPED, THROTTLED) for r in out.reqs)
    picked = sample(out, seed)
    metrics, breakdown = {}, None
    if trace:
        metrics, breakdown = per_layer(workload, c, peaks, out, e2e,
                                       trace_dir, device)
    else:
        for m in cell_metrics(workload, trace=False):
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            if not np.isfinite(v):
                raise RuntimeError(f"{m['name']}: the window gave nothing "
                                   f"to read ({e2e['_n']})")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"after the window: a full garbage collection took "
        f"{free(eng):.6f} s")
    del eng
    t = time.perf_counter()
    numbers = check(c, seed, out, picked, control)
    log(f"reference: {numbers['_requests']} requests, {numbers['_tokens']} "
        f"served tokens, {time.perf_counter() - t:.3f} s")
    correct, checks = verdict(c["correct"], numbers)
    result = {"correct": correct, "attempted": len(specs), "failed": failed,
              "metrics": metrics, "device": device}
    if control:
        result["program"] = {"correct": correct, "checks": checks}
        correct, checks = verdict(c["correct"], {
            **numbers, "max_gap": numbers["max_gap_fp8"]})
        result["correct"] = correct
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
