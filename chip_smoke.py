"""Chip smoke test: the fused paged serving path at Granite-3.0-2B width.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --replicas 4   # a four-chip host: the replica path

With no arguments, one process on one chip serves through the launcher's
own engine (``repro.launch.serve.build_engine``): ``ServingEngine`` on the
paged backend with chunked prefill, under the Equinox scheduler with a
trained MoPE predictor, on Granite-3.0-2B at its published widths with
weights made from ``--seed``.

1. bf16 KV pages: 24 requests from 3 clients, prompts of 128-1536 tokens
   and outputs of 16-128 tokens, served to completion.
2. int8 KV pages: a shorter phase on the same params.

Every request must finish with its whole output, in-vocabulary tokens and
finite logits.  Three requests' first-token logits are compared with a
plain ``models.prefill`` of the same prompt: the relative L2 error must
stay under 2e-2 (f32 dots inside the kernel run at the chip's default
precision, so the two paths are not bit-equal).

``--replicas N`` runs only the replica phase: N engine replicas, each
pinned to its own chip, behind ``Cluster`` with shared fairness state and
``least_kv`` routing, against one replica serving the same requests.

The timings printed are bring-up readings taken on the host clock around
iterations that end with logits on the host; times on the engine's
modeled clock say "modeled".  None is a benchmark number.  The last line
of stdout is one JSON object naming the device.  Without a TPU the script
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import statistics
import sys
import time

import numpy as np

CONFIG = "granite-3-2b"
# A prefill chunk plus one decode row per slot stays within 512 rows, the
# row bucket tests/test_tpu_compile.py compiles (512 + 16 would pad to
# 1024).  The step does not donate the pools, and loading it reserves
# about its outputs, temporaries and one more pool's size: with 4.7 GiB of
# params a 32768-token pool did not fit a 16 GiB v5e, 16384 tokens do.
ENGINE = dict(max_slots=16, max_len=2048, page_size=16,
              kv_budget_tokens=16384, prefill_chunk_tokens=512 - 16,
              keep_first_logits=True)
# the replica phase is about placement and routing, not width: a shorter
# table and fewer slots keep its per-device compiles and steps cheap
REPLICA_ENGINE = dict(ENGINE, max_slots=8, max_len=512,
                      kv_budget_tokens=8192, prefill_chunk_tokens=128 - 8)
N_REFERENCE = 3                 # requests checked against models.prefill
REL_L2_LIMIT = 2e-2


def check(ok: bool, msg: str):
    if not ok:
        raise RuntimeError(msg)


def make_requests(n, seed, prompt, output, n_clients=3):
    from repro.core import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, client=f"client{i % n_clients}",
                    arrival=0.001 * i,
                    prompt_len=int(rng.integers(*prompt, endpoint=True)),
                    output_len=int(rng.integers(*output, endpoint=True)),
                    keywords=("chat",)) for i in range(n)]


class Watch:
    """Observes one engine: times every iteration (an iteration that
    traced a new shape bucket counts as compile time) and checks every
    logits row that reaches the host."""

    def __init__(self, eng, vocab: int):
        from repro.serving.engine import _paged_decode_step
        self.compile_s, self.steady_s, self.bad_rows = [], [], []
        run_mixed, step = eng._run_mixed_paged, eng.step

        def run_mixed_checked(plan, decoding):
            first, rows = run_mixed(plan, decoding)
            for rid, row in [*first.items(), *rows.items()]:
                tok = int(np.argmax(row))
                if (row.shape != (vocab,) or not np.isfinite(row).all()
                        or not 0 <= tok < vocab):
                    self.bad_rows.append(rid)
            return first, rows

        def timed_step():
            n_traced = _paged_decode_step._cache_size()
            t0 = time.perf_counter()
            n = step()
            dt = time.perf_counter() - t0
            grew = _paged_decode_step._cache_size() > n_traced
            (self.compile_s if grew else self.steady_s).append(dt)
            return n

        eng._run_mixed_paged = run_mixed_checked
        eng.step = timed_step


def check_finished(name, reqs, watches):
    unfinished = [r.rid for r in reqs if r.generated != r.output_len]
    check(not unfinished, f"{name}: requests {unfinished} did not finish "
          "their output")
    bad = sorted({rid for w in watches for rid in w.bad_rows})
    check(not bad, f"{name}: non-finite or out-of-vocabulary logits for "
          f"requests {bad}")


def rel_l2(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def memory(dev) -> str:
    stats = dev.memory_stats() or {}
    return " ".join(f"{k} {stats.get(k)}" for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_limit"))


def serve_phase(name, dev, cfg, params, sched, cm, reqs, **kw):
    """Serve ``reqs`` on a fresh engine; print this phase's readings."""
    from repro.launch.serve import build_engine
    eng = build_engine(cfg, sched, cm, params=params, **ENGINE, **kw)
    print(f"{name}: engine ready: {memory(dev)}", flush=True)
    watch = Watch(eng, cfg.vocab_size)
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    check_finished(name, reqs, [watch])
    print(f"{name}: finished {len(done)}/{len(reqs)} requests in "
          f"{eng.iterations} iterations, {wall:.1f} s wall; "
          f"{eng.t_model:.3f} s modeled", flush=True)
    print(f"{name}: compile: {len(watch.compile_s)} shape buckets, their "
          f"first iterations took {sum(watch.compile_s):.1f} s", flush=True)
    if watch.steady_s:
        print(f"{name}: median steady iteration "
              f"{statistics.median(watch.steady_s) * 1e3:.1f} ms wall over "
              f"{len(watch.steady_s)} iterations (host clock, logits on "
              f"the host)", flush=True)
    # drop the pools before the next phase allocates its own
    eng.k_pools = eng.v_pools = eng.k_scales = eng.v_scales = None


def reference_errors(cfg, params, reqs):
    """Relative L2 of each request's first-token logits against a plain
    ``models.prefill`` of its prompt with the same params."""
    import jax
    import jax.numpy as jnp
    from repro.models import prefill
    fn = jax.jit(prefill, static_argnums=(2, 3))
    errs = []
    for r in reqs:
        logits, _ = fn(params, {"tokens": jnp.asarray(r.prompt_tokens[None])},
                       cfg, r.prompt_len)
        errs.append(rel_l2(r._first_row, np.asarray(logits[0], np.float32)))
    return errs


def single_chip(cfg, params, predictor, cm, seed, dev):
    from repro.core import make_scheduler

    def sched():
        return make_scheduler("equinox", predictor=copy.deepcopy(predictor))

    reqs = make_requests(24, seed, prompt=(128, 1536), output=(16, 128))
    serve_phase("bf16 pages", dev, cfg, params, sched(), cm, reqs)
    errs = reference_errors(cfg, params, reqs[:N_REFERENCE])
    for r, e in zip(reqs, errs):
        print(f"bf16 pages: request {r.rid} ({r.prompt_len}-token prompt) "
              f"first-token logits vs models.prefill: rel L2 {e:.2e}",
              flush=True)
    check(max(errs) < REL_L2_LIMIT, f"first-token logits differ from "
          f"models.prefill: rel L2 {max(errs):.2e} >= {REL_L2_LIMIT}")
    print(f"after bf16 pages: {memory(dev)}", flush=True)
    reqs = make_requests(8, seed + 1, prompt=(128, 1536), output=(16, 128))
    serve_phase("int8 pages", dev, cfg, params, sched(), cm, reqs,
                kv_quant=True)
    print(f"after int8 pages: {memory(dev)}", flush=True)


def replicas(n, cfg, params, predictor, cm, seed):
    """``n`` one-chip replicas behind ``Cluster`` against one replica."""
    import jax
    from repro.core import make_scheduler
    from repro.launch.serve import build_engine
    from repro.serving.cluster import Cluster
    devices = jax.devices()
    check(len(devices) >= n, f"--replicas {n} needs {n} devices, JAX "
          f"found {len(devices)}")
    reqs = make_requests(32, seed, prompt=(64, 256), output=(8, 32))

    def engine(device):
        sched = make_scheduler("equinox", predictor=copy.deepcopy(predictor))
        return build_engine(cfg, sched, cm, params=params, device=device,
                            **REPLICA_ENGINE)

    one_reqs = copy.deepcopy(reqs)
    one = engine(devices[0])
    watch = Watch(one, cfg.vocab_size)
    t0 = time.perf_counter()
    one.run(one_reqs)
    check_finished("one replica", one_reqs, [watch])
    print(f"one replica: finished {len(one.finished)}/{len(reqs)} requests, "
          f"{time.perf_counter() - t0:.1f} s wall", flush=True)
    one.k_pools = one.v_pools = None

    reps = [engine(devices[i]) for i in range(n)]
    watches = [Watch(rep, cfg.vocab_size) for rep in reps]
    cl_reqs = copy.deepcopy(reqs)
    t0 = time.perf_counter()
    res = Cluster(reps, policy="least_kv").run(cl_reqs)
    wall = time.perf_counter() - t0
    check_finished(f"{n} replicas", cl_reqs, watches)
    per_replica = res.replica_finished()
    print(f"{n} replicas: finished {sum(per_replica)}/{len(reqs)} requests, "
          f"per replica {per_replica}, {wall:.1f} s wall; "
          f"{max(rep.t_model for rep in reps):.3f} s modeled", flush=True)
    check(all(k > 0 for k in per_replica),
          f"a replica served no request: {per_replica}")
    held = [{d for a in (rep.k_pools, rep.params["embed"]["table"])
             for d in a.devices()} for rep in reps]
    print(f"{n} replicas: devices holding params and pools: "
          f"{[sorted(str(d) for d in h) for h in held]}", flush=True)
    check(all(len(h) == 1 for h in held)
          and len(set().union(*held)) == n,
          f"replicas do not hold their arrays on {n} distinct devices")
    check(reps[0].sched.service is reps[-1].sched.service,
          "replicas do not share fairness state")
    errs = [rel_l2(a._first_row, b._first_row)
            for a, b in zip(cl_reqs, one_reqs)]
    print(f"{n} replicas: first-token logits vs one replica: max rel L2 "
          f"{max(errs):.2e} over {len(errs)} requests", flush=True)
    check(max(errs) < REL_L2_LIMIT, f"replica logits differ from one "
          f"replica: rel L2 {max(errs):.2e} >= {REL_L2_LIMIT}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--replicas", type=int, default=0,
                    help="run only the replica phase on this many chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs import get_config
    from repro.launch.serve import build_predictor, enable_compile_cache
    from repro.models import init_params
    from repro.serving.costmodel import A100_80G, CostModel

    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"jax {jax.__version__} on {len(jax.devices())} x "
          f"{dev.device_kind}; compile cache {cache} ({n_cached} entries "
          f"at start)", flush=True)
    cfg = get_config(CONFIG)
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.key(args.seed), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    cm = CostModel(cfg, A100_80G)
    predictor = build_predictor("mope", cm, args.seed)
    print(f"config {cfg.name}: {n_params} params, {cfg.n_layers} layers, "
          f"d_model {cfg.d_model}; params and predictor ready in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    if args.replicas:
        replicas(args.replicas, cfg, params, predictor, cm, args.seed)
    else:
        single_chip(cfg, params, predictor, cm, args.seed, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
