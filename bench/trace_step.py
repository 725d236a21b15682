"""Serve one cell with the program's step tracer on: what each part of an
engine iteration costs on the host, and where requests wait.

    python bench/trace_step.py --workload granite2b.tenants --seconds 51 \
        --seeds 11 12 13 --profile-seed 14

Needs the chip.  In one process, over the cell's configuration and mix:

1. cost: for each of ``--seeds``, one window with the tracer off (the
   engine as ``bench/run.py`` builds it, without the drain) and one with
   the tracer on, a ``FlightRecorder`` attached and the profiler off,
   then its drain; one JSON line per window with its ``tokens_per_s``
   and ``tbt_p95_ms`` and, for the traced one, the light requests' queue
   waits against their TTFT (``light_waits``); then one line with the
   host nanoseconds of a span, of a step's record and of a step's
   counting, measured alone.
2. profile (``--profile-seed``): one window with the tracer on and a
   ``FlightRecorder`` attached, the profiler recording the slice that
   ``bench/run.py --trace 1`` records, then its drain.  One JSON line:
   the cell's per-layer metrics as ``--trace 1`` reads them; the step
   tracer's readings (``steptrace``) over the traced iterations and over
   the window; the idle gaps named by the program's spans and split
   among them; how much of each ``mixed_step`` the ``step.*`` spans
   cover; the light requests' queue waits against their TTFT; and the
   device ops that the paged kernel's names match (``readers.KERNEL_NAMES``
   and the family's own, from ``work``).  The drained
   records (``steps.json``), the recorder's trace (``flight.json``) and
   an excerpt of the profile around its longest idle gap
   (``excerpt.json``, ``tracefile.from_json``'s form) are written beside
   the profile under ``bench_out/traces/<workload>.<seed>/``.

A tool for reading the step tracer, not part of a cell's run.
"""
import argparse
import json
import shutil
import sys
import time

from config import (ROOT, family, gate, load_cell, load_config, peaks_table,
                    setup)

# light requests due this close to the window's end wait through the
# drain, not the window: ``light_waits`` also reads the share without them
EDGE_S = 10.0


def window(sess, c, mix, seed, seconds, compiles, traced, observer=None,
           **serve_kw):
    """One window of the mix on a fresh engine, built as ``bench/run.py``
    builds it; with ``traced`` the step tracer is switched on and
    ``observer`` attached to its core before the first step.  Returns
    (engine, served)."""
    import jax
    from harness import serve
    from traffic import generate
    from repro.serving.telemetry import StepTracer
    params = family(c).program_params(c, seed)
    jax.block_until_ready(params)
    eng = sess.engine_for(params, seed)
    del params
    # warm-up steps go through the engine's own step path: the tracer
    # comes on after them, so that they add to no record
    sess.warm_up(eng)
    if traced:
        eng.tracer = StepTracer(on=True)
    if observer is not None:
        eng.core.observer = observer
        observer.bind_core(eng.core)
    specs = generate(mix, seed, seconds, c["vocab_size"])
    return eng, serve(eng, specs, seconds, compiles, **serve_kw)


def light_waits(out, events, seconds, edge=EDGE_S) -> dict:
    """The light requests' TTFT and queue waits (arrival to admission on
    the recorder's wall clock), for all those due in the window and for
    those due more than ``edge`` seconds before its end: their count,
    TTFT p95, queue-wait p95, and the queue waits' share of their summed
    TTFT.  A request without a first token counts to the drain's end."""
    import steptrace as st
    from stats import percentile
    light = [(r.rid, s["due"]) for r, s in zip(out.reqs, out.specs)
             if s["ttft"] and s["due"] < seconds]
    waits = st.queue_waits(events, [rid for rid, _ in light])
    ttft = {rid: (out.stamps[rid][0] if out.stamps[rid]
                  else seconds + out.drain_s) - due for rid, due in light}
    res = {}
    for key, cut in (("all", seconds), ("early", seconds - edge)):
        got = [rid for rid, due in light if due < cut and rid in waits]
        res[key] = {
            "n": len(got),
            "ttft_p95_s": percentile([ttft[r] for r in got], 95)
            if got else None,
            "queue_wait_p95_s": percentile([waits[r][0] for r in got], 95)
            if got else None,
            "wait_share": (sum(waits[r][0] for r in got)
                           / sum(ttft[r] for r in got)) if got else None}
    res["by_rid"] = {rid: [due, ttft[rid], *waits.get(rid, (None, None))]
                     for rid, due in light}
    return res


def kv_pages(records) -> dict:
    """The most KV pages written and held by reservations at the end of
    any of ``records``' steps."""
    levels = [r["counts"] for r in records if "kv_pages_used" in r["counts"]]
    return {k: max((c[k] for c in levels), default=None)
            for k in ("kv_pages_used", "kv_pages_reserved")}


def cost(sess, c, mix, seeds, seconds, compiles):
    """The cost phase: yields one result dict per window."""
    from harness import end_to_end, free
    from repro.serving.telemetry import FlightRecorder
    for seed in seeds:
        for traced in (False, True):
            rec = FlightRecorder() if traced else None
            eng, out = window(sess, c, mix, seed, seconds, compiles, traced,
                              observer=rec,
                              **({} if traced else {"drain_cap": 0.0}))
            e2e = end_to_end(out)
            line = {"phase": "cost", "seed": seed, "tracer": traced,
                    "tokens_per_s": e2e["tokens_per_s"],
                    "tbt_p95_ms": e2e["tbt_p95_ms"],
                    "service_jain": e2e["service_jain"],
                    "iterations": e2e["_n"]["iterations"],
                    "compiles_in_window": out.compiles_in_window}
            if traced:
                records = eng.tracer.drain()
                line["records"] = len(records)
                line["kv_pages"] = kv_pages(records)
                line["light_waits"] = light_waits(out, rec.events, seconds)
            free(eng)
            del eng
            yield line


def host_ns(n: int = 20000) -> dict:
    """Host ns of one span with the tracer off and on (no profiler
    running), of an empty step's record, and of one step's counter
    calls, each timed alone."""
    from repro.serving.telemetry import StepTracer
    out = {}
    for on in (False, True):
        tr = StepTracer(on=on)
        t = time.perf_counter_ns()
        for _ in range(n):
            with tr.span("step.pack"):
                pass
        out[f"span_{'on' if on else 'off'}"] = (time.perf_counter_ns()
                                               - t) / n
    tr = StepTracer(on=True)
    t = time.perf_counter_ns()
    for i in range(n):
        with tr.step(i):
            pass
    out["step_on"] = (time.perf_counter_ns() - t) / n
    counts = dict(rows_real=1, rows_launched=1, logits_rows_used=1,
                  logits_rows_fetched=1)
    t = time.perf_counter_ns()
    for i in range(n):
        tr.count(**counts)
        tr.level(waiting=i, kv_pages_used=i, kv_pages_reserved=i)
    out["counts_on"] = (time.perf_counter_ns() - t) / n
    return out


def excerpt(ops, modules, spans, gap, before=2e6, after=1e8) -> dict:
    """The ops, modules and spans around ``gap``, shifted to start at 0,
    in ``tracefile.from_json``'s form."""
    a, b = gap[0] - before, gap[1] + after

    def keep(xs):
        return [[x[0], x[1] - a, x[2] - a, *x[3:]] for x in xs
                if x[2] > a and x[1] < b]
    return {"ops": [keep(ops)], "modules": keep(modules),
            "spans": keep(spans), "window": [0, b - a]}


def profile(workload, sess, c, mix, seed, seconds, compiles, peaks):
    """The profile phase: one result dict; files beside the profile."""
    import readers
    import steptrace as st
    import tracefile
    from cell import TRACE_FROM, per_layer
    from harness import SPANS, end_to_end, free
    from repro.serving.telemetry import FlightRecorder, save_trace
    rec = FlightRecorder()
    trace_dir = ROOT / "bench_out" / "traces" / f"{workload}.{seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    eng, out = window(sess, c, mix, seed, seconds, compiles, True,
                      observer=rec, trace_dir=str(trace_dir),
                      trace_from=TRACE_FROM)
    records = eng.tracer.drain()
    free(eng)
    del eng
    e2e = end_to_end(out)
    device = {}
    metrics, breakdown = per_layer(workload, c, peaks, out, e2e, trace_dir,
                                   device)
    # one record per engine.step() call of the loop, in order
    assert len(records) == len(out.iters), (len(records), len(out.iters))
    a, b = out.trace_span
    traced = [r for r, it in zip(records, out.iters)
              if it.t0 >= a and it.t1 <= b]
    in_window = [r for r, it in zip(records, out.iters)
                 if it.t1 <= out.seconds]
    readings = {
        name: {"traced": fn(traced), "window": fn(in_window)}
        for name, fn in (("sched_span_ms_per_iter", st.sched_span_ms),
                         ("fetch_ms_per_iter", st.fetch_ms),
                         ("logits_used_share", st.logits_used_share),
                         ("pad_row_share", st.pad_row_share))}
    by_rows: dict = {}
    for r in traced:
        n = r["counts"].get("rows_launched", 0)
        agg = by_rows.setdefault(n, {"n": 0})
        agg["n"] += 1
        for k, v in r["span_ns"].items():
            agg[k] = agg.get(k, 0.0) + v * 1e-6
    for agg in by_rows.values():
        for k in agg:
            if k != "n":
                agg[k] /= agg["n"]
    tr = tracefile.load(str(trace_dir), set(SPANS) | set(st.PROGRAM_SPANS))
    program, spans = st.split_program(tr.spans)
    starts = [s for _, s, _ in tr.spans] + [o[1] for d in tr.ops for o in d]
    ends = [e for _, _, e in tr.spans] + [o[2] for d in tr.ops for o in d]
    t0, t1 = min(starts), max(ends)
    ops = tr.ops[0] if tr.ops else []
    gaps = st.top_gaps(ops, program, spans, t0, t1)
    cover = [st.covered(program, s) for s in spans
             if s[0] == "mixed_step" and s[1] >= t0 and s[2] <= t1]
    names = readers.KERNEL_NAMES + family(c).work(
        c, c["engine"]["page_size"]).kernel_names
    kernel = sorted({o[0].split(" = ")[0] for o in tracefile.work_ops(ops)
                     if tracefile.matches(o, names)
                     and o[2] > t0 and o[1] < t1})
    line = {
        "phase": "profile", "seed": seed, "metrics": metrics,
        "device": device, "breakdown": breakdown,
        "steptrace": readings, "by_rows_ms": by_rows,
        "light_ttft_p95_s": e2e["ttft_p95_s"],
        "light_waits": light_waits(out, rec.events, seconds),
        "kv_pages": kv_pages(in_window),
        "program_gaps": gaps, "mixed_step_cover": cover,
        "kernel_ops": kernel,
        "traced_iterations": len(traced), "records": len(records)}
    with open(trace_dir / "steps.json", "w") as f:
        json.dump(records, f)
    save_trace(rec.trace(), str(trace_dir / "flight.json"))
    idle = tracefile.idle_gaps(ops, t0, t1)
    if idle:
        longest = max(idle, key=lambda g: g[1] - g[0])
        with open(trace_dir / "excerpt.json", "w") as f:
            json.dump(excerpt(ops, tr.modules, tr.spans, longest), f)
    return line


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--profile-seed", type=int)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    jax = setup()
    devices = jax.devices()
    table = peaks_table()
    refusal = gate(devices, cell["chips"], table)
    if refusal:
        sys.exit(refusal)
    from harness import CompileCount, Session
    from traffic import load_mix
    c = load_config(cell["config"])
    mix = load_mix(cell["traffic"])
    sess = Session(c)
    compiles = CompileCount()
    for line in cost(sess, c, mix, args.seeds, args.seconds, compiles):
        print(json.dumps(line, default=float), flush=True)
    print(json.dumps({"phase": "host_ns", **host_ns()}), flush=True)
    if args.profile_seed is not None:
        print(json.dumps(profile(args.workload, sess, c, mix,
                                 args.profile_seed, args.seconds, compiles,
                                 table[devices[0].device_kind]),
                         default=float), flush=True)


if __name__ == "__main__":
    main()
