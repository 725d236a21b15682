"""One cell: set-up, an open loop on the wall clock, drain, metrics, check.

The system under test is the program's own serving path: the launcher's
``build_engine`` on the paged backend, under ``make_scheduler`` with the
launcher's ``build_predictor``.  Each ``ServingEngine.step`` ends with the
step's logits on the host, so the host clock around it is the step's
wall time.

The loop submits each request when it falls due, keeps the engine's
modeled clock at or above the elapsed wall time (so the scheduler never
sees an arrival in its future), steps, and stamps every new output token
with the wall time at the end of the step that produced it.  Latencies
count from the due time.

The harness wraps the engine instance's calls into the batch core and
the fused step to time them on the host (and, when tracing, to mark them
as profiler spans); it changes nothing they do.
"""
from __future__ import annotations

import contextlib
import copy
import gc
import math
import time
from dataclasses import dataclass, field

import numpy as np

from config import family
from stats import percentile, weighted_service

SPANS = ("submit", "admit", "prepare", "plan", "mixed_step", "lifecycle")
SCHED_SPANS = ("admit", "prepare", "plan", "lifecycle")
DRAIN_CAP_S = 90.0          # untimed stepping after the window, at most
SAMPLE_MAX = 16             # requests compared with the reference
SAMPLE_TOKENS = 256         # ... or fewer, once this many served tokens


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def buckets(engine: dict):
    """Every (rows, table rows) shape the fused step can be called with
    under these engine settings: a chunk budget of ``prefill_chunk_tokens``
    shared by the prefilling requests, plus one row per decoding request,
    at most ``max_slots`` requests; both counts padded to powers of two,
    the table with at least one spare row."""
    slots, chunk = engine["max_slots"], engine["prefill_chunk_tokens"]
    max_rows = max(chunk + slots - 1, slots)
    out = []
    r = 1
    while r <= next_pow2(max_rows):
        # each request in the step has at least one row
        tabs = sorted({next_pow2(n + 1) for n in range(1, min(r, slots) + 1)})
        out += [(r, t) for t in tabs]
        r *= 2
    return out


@dataclass
class Iteration:
    t0: float
    t1: float = math.nan
    ctxs: list = field(default_factory=list)     # one per real row
    owners: list = field(default_factory=list)
    n_logits: int = 0
    prefilled: int = 0
    decoded: int = 0
    spans: dict = field(default_factory=dict)    # host seconds per span


@dataclass
class Served:
    specs: list
    reqs: list
    stamps: dict
    tokens: dict
    iters: list
    late: list
    seconds: float
    service: dict
    compiles_in_window: int = 0
    step_traces_in_window: int = 0
    gc_in_window: list = field(default_factory=list)   # (gen, seconds)
    drain_s: float = 0.0
    trace_span: tuple = None      # (start, stop) host seconds of the trace


class Recorder:
    """Times the engine instance's calls; marks them as profiler spans
    while ``tracing``."""

    def __init__(self):
        self.tracing = False
        self.cur: Iteration = None

    def span(self, name):
        if self.tracing:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def wrap(self, obj, attr, name, after=None):
        fn = getattr(obj, attr)

        def timed(*a, **k):
            t = time.perf_counter()
            with self.span(name):
                out = fn(*a, **k)
            if self.cur is not None:
                sp = self.cur.spans
                sp[name] = sp.get(name, 0.0) + time.perf_counter() - t
                if after is not None:
                    after(self.cur, *a)
            return out

        setattr(obj, attr, timed)


def _rows(it: Iteration, plan, decoding):
    """Record the real rows of one fused step."""
    for req, chunk in plan:
        start = req.prefill_done - chunk
        it.ctxs += range(start, start + chunk)
        it.owners += [req.rid] * chunk
        it.n_logits += int(req.prefill_done >= req.prompt_len)
    for r in decoding:
        it.ctxs.append(r._pos)
        it.owners.append(r.rid)
    it.n_logits += len(decoding)


def instrument(eng) -> Recorder:
    rec = Recorder()
    core = eng.core
    rec.wrap(core, "admit", "admit")
    rec.wrap(core, "prepare_iteration", "prepare")
    rec.wrap(core, "plan_prefill", "plan")
    rec.wrap(core, "execute_iteration", "lifecycle")
    rec.wrap(eng, "_run_mixed_paged", "mixed_step", after=_rows)
    return rec


class Session:
    """What a process sets up once per configuration: the program's model
    config, cost model and trained predictor."""

    def __init__(self, c: dict):
        from repro.launch.serve import build_predictor
        from repro.serving.costmodel import A100_80G, CostModel
        self.engine = c["engine"]
        self.cfg = family(c).program_config(c)
        # the scheduler's cost model and the predictor, as the launcher
        # builds them; the predictor trains from a fixed seed, as a
        # deployment would train it once
        self.cm = CostModel(self.cfg, A100_80G)
        self.predictor = build_predictor(self.engine["predictor"], self.cm,
                                         0)
        self.warm: set = set()

    def engine_for(self, params, seed: int, kv_quant: bool = False):
        from repro.core import make_scheduler
        from repro.launch.serve import build_engine
        e = self.engine
        sched = make_scheduler(e["scheduler"],
                               predictor=copy.deepcopy(self.predictor))
        return build_engine(
            self.cfg, sched, self.cm, backend="paged", params=params,
            max_slots=e["max_slots"], max_len=e["max_len"],
            page_size=e["page_size"], kv_budget_tokens=e["kv_budget_tokens"],
            prefill_chunk_tokens=e["prefill_chunk_tokens"],
            slo_budget=e["slo_budget"], kv_quant=kv_quant, seed=seed)

    def warm_up(self, eng) -> int:
        """Run the fused step once at every reachable shape, with padding
        rows only (token 0 at position 0 of the scratch page), so that the
        window compiles nothing.  Returns how many shapes were new here."""
        from repro.serving.engine import _paged_decode_step
        n_new = 0
        width = next_pow2(eng.pool.pages_needed(eng.max_len))
        for rows, tabs in buckets(self.engine):
            key = (rows, tabs, eng.kv_quant)
            if key in self.warm:
                continue
            z = np.zeros(rows, np.int32)
            table = np.full((tabs, width), eng._scratch_page, np.int32)
            rmap = np.full(rows, tabs - 1, np.int32)
            out = _paged_decode_step(
                eng.params, *eng._place((z, z, table, rmap)), eng.k_pools,
                eng.v_pools, eng.k_scales, eng.v_scales, eng.cfg,
                eng.pool.page_size)
            eng.k_pools, eng.v_pools = out[1:3]
            if eng.kv_quant:
                eng.k_scales, eng.v_scales = out[3:]
            np.asarray(out[0][:1])
            self.warm.add(key)
            n_new += 1
        # the predictor's first call traces its ops
        from repro.core import Request
        eng.sched.predictor.predict(Request(
            rid=-1, client="warm", arrival=0.0, prompt_len=64,
            output_len=1, keywords=("chat",)))
        return n_new


def to_requests(specs):
    from repro.core import Request
    return [Request(rid=i, client=s["client"], arrival=s["due"],
                    prompt_len=s["prompt_len"], output_len=s["output_len"],
                    keywords=s["keywords"], prompt_tokens=s["tokens"])
            for i, s in enumerate(specs)]


class CompileCount:
    """Counts executables compiled or loaded from the persistent cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == self.EVENT:
            self.n += 1


class GcPauses:
    """A ``gc.callbacks`` entry: (generation, host seconds) of each of the
    interpreter's garbage collections while it is registered."""

    def __init__(self):
        self.pauses, self._t = [], 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))


def sampleable_tokens(out: Served) -> int:
    """Served tokens of the requests that ``sample`` may pick."""
    return sum(len(out.tokens[r.rid]) for r in out.reqs
               if r.n_preempted == 0)


def serve(eng, specs, seconds: float, compiles: CompileCount,
          trace_dir: str = None, trace_from: float = 0.3,
          drain_cap: float = DRAIN_CAP_S) -> Served:
    """The open loop.  With ``trace_dir`` the profiler records from the
    first iteration boundary after ``trace_from * seconds`` to the first
    one at or after the window's end.

    After the window it steps on, untimed, until every request whose TTFT
    is measured has its first token and ``SAMPLE_TOKENS`` tokens are
    served for the comparison (or nothing is left to serve), for at most
    ``drain_cap`` seconds."""
    import jax
    from repro.serving.engine import _paged_decode_step
    rec = instrument(eng)
    reqs = to_requests(specs)
    by_client = {s["client"]: 0.0 for s in specs if s["fair"]}
    out = Served(specs, reqs, {r.rid: [] for r in reqs},
                 {r.rid: [] for r in reqs}, [], [], seconds, by_client)
    seen = {r.rid: (0, 0) for r in reqs}      # (prefill_done, peak gen)
    active = []
    i, n = 0, len(reqs)
    waiting_ttft = {r.rid for r, s in zip(reqs, specs) if s["ttft"]}
    tracing = closed = False
    c0, s0 = compiles.n, _paged_decode_step._cache_size()
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                if not closed:
                    closed = True
                    gc.callbacks.remove(pauses)
                    out.gc_in_window = pauses.pauses
                    out.compiles_in_window = compiles.n - c0
                    out.step_traces_in_window = (
                        _paged_decode_step._cache_size() - s0)
                if tracing:
                    jax.profiler.stop_trace()
                    rec.tracing = tracing = False
                    out.trace_span = (out.trace_span[0], now)
                # every request is due inside the window: wait for the
                # first token of each whose TTFT is measured, and for the
                # sample's tokens, up to the cap
                sampled = sampleable_tokens(out) >= SAMPLE_TOKENS
                if (not waiting_ttft and sampled
                        or now >= seconds + drain_cap):
                    out.drain_s = now - seconds
                    break
            elif trace_dir and out.trace_span is None \
                    and now >= trace_from * seconds:
                jax.profiler.start_trace(trace_dir)
                rec.tracing = tracing = True
                out.trace_span = (time.perf_counter() - t0, None)
            while i < n and reqs[i].arrival <= now:
                eng.advance_to(now)
                with rec.span("submit"):
                    eng.submit(reqs[i])
                out.late.append(time.perf_counter() - t0 - reqs[i].arrival)
                active.append(reqs[i])
                i += 1
            eng.advance_to(time.perf_counter() - t0)
            if not eng.has_work():
                if i >= n and now >= seconds:
                    out.drain_s = now - seconds
                    break
                nxt = reqs[i].arrival if i < n else seconds
                time.sleep(max(0.0, min(nxt - now, 0.01)))
                continue
            it = Iteration(t0=time.perf_counter() - t0)
            rec.cur = it
            eng.step()
            it.t1 = time.perf_counter() - t0
            rec.cur = None
            out.iters.append(it)
            still = []
            for r in active:
                pd, peak = seen[r.rid]
                dp = max(0, r.prefill_done - pd)
                dg = max(0, r.generated - peak)
                it.prefilled += dp
                it.decoded += dg
                for _ in range(dg):
                    out.stamps[r.rid].append(it.t1)
                    out.tokens[r.rid].append(int(r._next_token))
                if dg:
                    waiting_ttft.discard(r.rid)
                if r.client in by_client and it.t1 <= seconds:
                    by_client[r.client] += weighted_service(dp, dg)
                seen[r.rid] = (r.prefill_done, max(peak, r.generated))
                if r.finish_time is None:
                    still.append(r)
            active = still
    finally:
        if pauses in gc.callbacks:
            gc.callbacks.remove(pauses)
    return out


def end_to_end(out: Served) -> dict:
    """The cell's end-to-end readings (every one this harness can make;
    the caller keeps the cell's own) and the sample sizes behind them."""
    from stats import jain
    w = out.seconds
    done = [it for it in out.iters if it.t1 <= w]
    span = done[-1].t1 if done else math.nan
    tokens = sum(it.prefilled + it.decoded for it in done)
    ttfts = []
    for r, s in zip(out.reqs, out.specs):
        if s["ttft"] and s["due"] < w:
            st = out.stamps[r.rid]
            end = st[0] if st else w + out.drain_s
            ttfts.append(end - s["due"])
    gaps = [b - a for st in out.stamps.values()
            for a, b in zip(st, st[1:]) if b <= w]
    return {
        "ttft_p95_s": percentile(ttfts, 95),
        "tbt_p95_ms": percentile(gaps, 95) * 1e3,
        "tokens_per_s": tokens / span if done else math.nan,
        "service_jain": jain(list(out.service.values())),
        "_n": {"ttft": len(ttfts), "gaps": len(gaps),
               "iterations": len(done), "tokens": tokens,
               "ttft_p50_s": percentile(ttfts, 50),
               "tbt_p50_ms": percentile(gaps, 50) * 1e3,
               "service": dict(out.service)},
    }


def sample(out: Served, seed: int):
    """Requests to compare with the reference: the one with the most
    served tokens, then others drawn from the seed, until SAMPLE_MAX
    requests or SAMPLE_TOKENS served tokens.  Requests that were
    preempted (and so recomputed) are left out."""
    cands = [r for r in out.reqs
             if out.tokens[r.rid] and r.n_preempted == 0]
    if not cands:
        return []
    cands.sort(key=lambda r: -len(out.tokens[r.rid]))
    picked, rest = [cands[0]], cands[1:]
    order = np.random.default_rng([int(seed), 3]).permutation(len(rest))
    for j in order:
        if len(picked) >= SAMPLE_MAX or sum(
                len(out.tokens[r.rid]) for r in picked) >= SAMPLE_TOKENS:
            break
        picked.append(rest[j])
    return picked


def check(c: dict, seed: int, out: Served, picked, control=False) -> dict:
    """The widest gap of a served token below the reference's best logit
    over the sampled requests; with ``control``, also the fp8 control's."""
    from reference import served_gaps
    length = c["engine"]["max_len"]
    worst, worst_ctrl, n = 0.0, 0.0, 0
    for r in picked:
        seq = np.concatenate([r.prompt_tokens,
                              np.asarray(out.tokens[r.rid], np.int32)])
        g, gc_ = served_gaps(c, seed, seq, r.prompt_len, length, control)
        worst = max(worst, float(g.max()))
        n += len(g)
        if control:
            worst_ctrl = max(worst_ctrl, float(gc_.max()))
    res = {"max_gap": worst, "_tokens": n, "_requests": len(picked)}
    if control:
        res["max_gap_fp8"] = worst_ctrl
    return res


def free(eng) -> float:
    """Drop the engine's device state before the reference runs; returns
    the host seconds of the full garbage collection that frees it."""
    eng.k_pools = eng.v_pools = eng.k_scales = eng.v_scales = None
    eng.params = None
    t = time.perf_counter()
    gc.collect()
    return time.perf_counter() - t
