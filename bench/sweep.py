"""Offered-load sweep of one configuration and mix, in one process.

    python bench/sweep.py granite2b chat --group chat --rates 0.05 0.1 0.2 \
        --seconds 51 --seed 3

Serves the mix once per rate, with the rate of ``--group`` set to each
value in turn (requests per second), on a fresh engine over the same
weights, and prints one JSON line per rate: the end-to-end readings, how
many of the requests due had their first token when the window closed,
and how many were never admitted.  The knee is the highest rate
whose backlog does not grow through the window.  Needs the chip; it is a
tool for choosing a cell's rate, not part of a cell's run.
"""
import argparse
import copy
import json
import sys
import time

from config import family, gate, load_config, peaks_table, setup


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("mix")
    ap.add_argument("--group", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    refusal = gate(setup().devices(), 1, peaks_table())
    if refusal:
        sys.exit(refusal)
    from harness import CompileCount, Session, end_to_end, free, serve
    from traffic import generate, load_mix

    c = load_config(args.config)
    sess = Session(c)
    compiles = CompileCount()
    base = load_mix(args.mix)
    for rate in args.rates:
        mix = copy.deepcopy(base)
        for g in mix["groups"]:
            if g["name"] == args.group:
                g["rate_per_s"] = rate
        params = family(c).program_params(c, args.seed)
        eng = sess.engine_for(params, args.seed)
        del params
        sess.warm_up(eng)
        specs = generate(mix, args.seed, args.seconds, c["vocab_size"])
        t = time.perf_counter()
        out = serve(eng, specs, args.seconds, compiles, drain_cap=0.0)
        e2e = end_to_end(out)
        firsts = sum(bool(out.stamps[r.rid]) and out.stamps[r.rid][0]
                     <= args.seconds for r in out.reqs)
        line = {"rate": rate, "due": len(specs), "first_tokens": firsts,
                "never_admitted": sum(r.admit_time is None
                                      for r in out.reqs),
                "compiles_in_window": out.compiles_in_window,
                "wall_s": time.perf_counter() - t,
                **{k: v for k, v in e2e.items() if k != "_n"},
                **e2e["_n"]}
        print(json.dumps(line, default=float), flush=True)
        free(eng)
        del eng


if __name__ == "__main__":
    main()
