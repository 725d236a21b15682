"""Readings that set a configuration's correctness limit, in one process.

    python bench/control.py deepseek7b.chat --seconds 51 \
        --seeds 101 102 103 --faults token_altered half_left_out

Each seed is one run of the cell (``cell.run_cell``, the path and sizes
of ``bench/run.py``) in control mode: the program's ``max_gap`` and
verdict, and the fp8 control's, its first choices at the same positions
put in the served tokens' place and judged by the same limits.  Then each
fault of ``bench/faults.py`` is planted under one run at the first seed.
One JSON line per run.  Needs the chip; the cell's own runs do not run
it.
"""
import argparse
import json
import sys
import time

from config import gate, load_cell, load_config, peaks_table, setup


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args()
    cell = load_cell(args.workload)
    jax = setup()
    devices, table = jax.devices(), peaks_table()
    refusal = gate(devices, cell["chips"], table)
    if refusal:
        sys.exit(refusal)
    from cell import run_cell
    from faults import planted
    from harness import Session

    sess = Session(load_config(cell["config"]))
    peaks = table[devices[0].device_kind]

    def one(seed, control):
        r = run_cell(args.workload, cell, seed, args.seconds, False,
                     time.perf_counter(), devices, peaks, control, sess)
        return {"seed": seed, "correct": r["correct"],
                "checks": r["checks"], "program": r.get("program"),
                "metrics": {k: v["value"] for k, v in r["metrics"].items()}}

    for seed in args.seeds:
        print(json.dumps({"mode": "control", **one(seed, True)}),
              flush=True)
    for name in args.faults:
        with planted(name):
            print(json.dumps({"mode": name, **one(args.seeds[0], False)}),
                  flush=True)


if __name__ == "__main__":
    main()
