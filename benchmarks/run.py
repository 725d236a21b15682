"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows, and writes each module's
results to a machine-readable ``BENCH_<name>.json`` (uploaded as a CI
artifact — the queryable perf trajectory; ``BENCH_OUT`` overrides the
output directory).

    PYTHONPATH=src python -m benchmarks.run            # full
    PYTHONPATH=src python -m benchmarks.run --quick    # smaller loads
    PYTHONPATH=src python -m benchmarks.run --only jains roofline
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from benchmarks.common import write_bench_json

BENCHES = [
    ("cost_curves", "Fig 2/16: token count fails as a cost proxy"),
    ("mope_accuracy", "Fig 4/7: MoPE vs single proxy, router curve"),
    ("scenarios", "Figs 9/10/17/18: synthetic fairness scenarios"),
    ("ablation", "Table 1: scheduler x predictor service differences"),
    ("jains", "Fig 13: Jain-on-HF across serving setups"),
    ("alpha_sweep", "Fig 15: alpha/beta fairness-throughput trade"),
    ("trace_serving", "Fig 11/12: ShareGPT-like trace on the real engine"),
    ("ttft_stallfree", "Sec 2/7: stall-free chunked prefill vs whole-prompt"
                       " TTFT on the real engine"),
    ("prefix_cache", "DESIGN.md §9: shared-prefix radix KV cache + "
                     "prefix-affinity routing on a multiturn trace"),
    ("overload", "DESIGN.md §10: preemption under output-length "
                 "misprediction; fair vs LIFO victim selection"),
    ("locality_fairness", "DESIGN.md §11: DLPM vs Equinox vs VTC duel + "
                          "d2lpm routing on the multiturn trace"),
    ("slo_attainment", "DESIGN.md §12: SLO-auto per-iteration prefill "
                       "budgets vs static chunking, TTFT/TBT attainment"),
    ("overload_admission", "DESIGN.md §13: overload-aware admission — "
                           "throttled vs unthrottled under 3x overload"),
    ("telemetry_overhead", "DESIGN.md §14: flight-recorder cost — "
                           "recorder-on vs off on a saturated trace"),
    ("sim_scale", "DESIGN.md §15: event-driven macro-stepping — "
                  "steady-decode speedup + provider-scale wall time"),
    ("cluster_scaling", "Beyond-paper: 1-8 replica fair cluster serving"),
    ("rpm_baseline", "Sec 1: static RPM quotas waste off-peak capacity"),
    ("roofline", "Deliverable (g): three-term roofline per arch x shape"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()

    # the harness opts into flight-recorder traces (TRACE_<name>.json
    # next to BENCH_<name>.json, DESIGN.md §14); direct mod.run() calls
    # — unit tests, the determinism pin — stay trace-free by default
    os.environ.setdefault("REPRO_TRACE", "1")

    print("name,us_per_call,derived")
    failures = 0
    for mod_name, desc in BENCHES:
        if args.only and mod_name not in args.only:
            continue
        print(f"# {mod_name}: {desc}", flush=True)
        t0 = time.monotonic()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            lines = []
            for line in mod.run(quick=args.quick):
                lines.append(line)
                print(line, flush=True)
            write_bench_json(mod_name, lines,
                             {"wall_s": time.monotonic() - t0,
                              "quick": args.quick})
        except Exception:  # noqa: BLE001 — benchmark isolation
            failures += 1
            print(f"# FAILED {mod_name}", flush=True)
            traceback.print_exc()
        print(f"# {mod_name} done in {time.monotonic() - t0:.1f}s",
              flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
