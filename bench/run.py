"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload granite2b.tenants --seed 7 \
        --seconds 51 --trace 0

Loads the cell's configuration and traffic mix by the names in
``BENCHMARK.json``, makes the weights and the requests from ``--seed``,
warms up every shape of the fused step the cell can reach, serves the
traffic for ``--seconds`` on the wall clock, and compares the served
tokens of a sample of requests with the float32 reference.  The last line
of standard output is one JSON object; the numbers compared, each with
its limit, are the last lines of standard error.  ``--trace 1`` records a
profiler trace of a steady slice of the window (under
``bench_out/traces/``) and reports the cell's per-layer metrics in
place of its end-to-end ones.

Needs a TPU: without one, or with fewer chips than the cell asks for, or
on a chip that ``bench/peaks.json`` does not list, it exits non-zero and
prints no result.  JAX's compilation cache is kept in ``.jax_cache`` at
the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from config import gate, load_cell, peaks_table, setup  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    cell = load_cell(args.workload)
    jax = setup()
    devices = jax.devices()
    table = peaks_table()
    refusal = gate(devices, cell["chips"], table)
    if refusal:
        print(refusal, file=sys.stderr)
        return 2
    from cell import run_cell
    result = run_cell(args.workload, cell, args.seed, args.seconds,
                      bool(args.trace), T_START, devices,
                      table[devices[0].device_kind])
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
