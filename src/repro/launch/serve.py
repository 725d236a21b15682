"""Serving launcher: the Equinox stack end to end on a real model.

Runs the continuous-batching engine under any scheduler against a
synthetic or trace workload, reporting the paper's metrics.  By default it
serves the full published config (on a TPU: the fused paged path where the
architecture supports chunked prefill).  ``--smoke`` swaps in the reduced
config and shrinks every request, for a CPU run.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --scheduler equinox --workload balanced --duration 5 [--smoke]
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax
import numpy as np

from repro.configs import SMOKE_FACTORIES, get_config
from repro.core import jain, make_scheduler
from repro.models import supports_chunked_prefill
from repro.predictor import MoPE, Oracle, SingleProxy
from repro.serving.costmodel import A100_80G, CostModel
from repro.serving.engine import ServingEngine
from repro.workloads import SCENARIOS, corpus, lmsys_like

# --smoke shrinks every request's token counts so the reduced model
# serves quickly on the CPU
SMOKE_TOKEN_SCALE = 0.05
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and
    left alone.  Otherwise the cache goes to ``<repo>/.jax_cache``: a
    fixed path, so a later process finds what an earlier one compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_predictor(name, cm, seed=0):
    if name == "oracle":
        return Oracle(cm)
    train_corpus = corpus(8000, seed=seed)
    if name == "single":
        return SingleProxy(cm, train_corpus, epochs=20)
    return MoPE(cm, train_corpus, epochs=20)


def build_engine(cfg, sched, cm, *, backend=None, **kw) -> ServingEngine:
    """The launcher's engine: the fused paged backend wherever the
    architecture supports chunked prefill, the slots backend otherwise."""
    if backend is None:
        backend = "paged" if supports_chunked_prefill(cfg) else "slots"
    return ServingEngine(cfg, sched, cost_model=cm, backend=backend, **kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--scheduler", default="equinox",
                    choices=["fcfs", "rpm", "vtc", "equinox"])
    ap.add_argument("--predictor", default="mope",
                    choices=["mope", "single", "oracle"])
    ap.add_argument("--workload", default="balanced")
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--backend", default=None, choices=["slots", "paged"],
                    help="default: paged where chunked prefill is "
                         "supported, else slots")
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced model and requests, for a CPU run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    full = get_config(args.arch)
    cfg = SMOKE_FACTORIES[args.arch]() if args.smoke else full
    cm = CostModel(full, A100_80G)
    pred = (build_predictor(args.predictor, cm, args.seed)
            if args.scheduler in ("vtc", "equinox") else None)
    sched = make_scheduler(args.scheduler, predictor=pred)
    if args.workload in SCENARIOS:
        reqs = SCENARIOS[args.workload](duration=args.duration,
                                        seed=args.seed)
    else:
        reqs = lmsys_like(duration=args.duration, seed=args.seed)
    if args.smoke:
        for r in reqs:
            r.prompt_len = max(4, int(r.prompt_len * SMOKE_TOKEN_SCALE))
            r.output_len = max(2, int(r.output_len * SMOKE_TOKEN_SCALE))

    eng = build_engine(cfg, sched, cm, backend=args.backend,
                       max_slots=args.max_slots, max_len=512,
                       seed=args.seed)
    done = eng.run(reqs)
    ttfts = np.array([r.ttft() for r in done if r.ttft() is not None])
    lats = np.array([r.e2e_latency() for r in done])
    tput = sum(r.prompt_len + r.generated for r in done) / max(eng.t_model,
                                                               1e-9)
    print(f"scheduler={args.scheduler} predictor={args.predictor} "
          f"workload={args.workload} model={cfg.name} "
          f"backend={eng.backend}")
    print(f"finished {len(done)}/{len(reqs)} requests, "
          f"{eng.iterations} engine iterations")
    print(f"modeled throughput: {tput:.0f} tok/s")
    if len(ttfts):
        print(f"TTFT p50/p90: {np.percentile(ttfts, 50):.3f}/"
              f"{np.percentile(ttfts, 90):.3f} s (modeled)")
        print(f"mean e2e latency: {lats.mean():.3f} s (modeled)")
    print(f"service per client: "
          f"{ {k: round(v, 1) for k, v in sched.service.items()} }")
    print(f"jain(service): {jain(list(sched.service.values())):.3f}")


if __name__ == "__main__":
    main()
