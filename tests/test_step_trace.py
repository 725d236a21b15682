"""Step tracer (DESIGN.md §14): host spans and counters of the serving
step on the wall clock.

- Tracing changes nothing the engine computes: every sampled token and
  every first-token logits row is bit-identical with the tracer on and
  off.
- Off (the default), it builds no profiler annotation, reads no clock
  and keeps no record.
- On, each iteration is one ``engine.step`` span whose children are the
  four ``core.*`` calls and the fused launch's five ``step.*`` parts, in
  order; ``engine.submit`` falls between steps.
- The counters agree with independent counts: the launched rows are
  what reached the jitted step, ``rows_real`` plus the padding is
  ``rows_launched``, ``logits_rows_used`` is the number of logits rows
  the engine read, and ``kv_pages_reserved`` is the pages the core's
  reservations hold, each rounded up to whole pages.
- The flight recorder's ``tw`` stamps are on the tracer's clock: each
  admission falls inside the ``engine.step`` that made it.
"""
import types

import jax
import numpy as np
import pytest

from repro.configs import SMOKE_FACTORIES, get_config
from repro.core import Request, make_scheduler
from repro.models import init_params
from repro.serving import engine as engine_mod
from repro.serving.costmodel import A100_80G, CostModel
from repro.serving import telemetry
from repro.serving.engine import ServingEngine
from repro.serving.telemetry import NO_SPAN, FlightRecorder, StepTracer

CFG = SMOKE_FACTORIES["llama2-7b"]()
# iterations priced at a 7B model's speed, so arrivals meet a busy batch
CM = CostModel(get_config("llama2-7b"), A100_80G)
CORE = ["core.admit", "core.prepare", "core.plan"]
LAUNCH = ["step.pack", "step.dispatch", "step.wait", "step.fetch",
          "step.widen"]
# the two cases exercise the counters that need pressure: a tight KV
# budget with 5x under-predicted outputs preempts; a shared system
# prompt with the prefix cache on hits
CASES = {"preempt": dict(max_len=64, kv_budget_tokens=192),
         "prefix": dict(max_len=96, kv_budget_tokens=2000,
                        prefix_cache=True)}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(7), CFG)


def _reqs(case):
    rng = np.random.default_rng(5)
    out = []
    if case == "preempt":
        for i in range(6):
            o = int(rng.integers(30, 60))
            r = Request(rid=i, client=f"client{i % 2}", arrival=0.05 * i,
                        prompt_len=16, output_len=o, keywords=("chat",))
            r.pred_output_len = max(1.0, o / 5)
            out.append(r)
        return out
    system = rng.integers(0, CFG.vocab_size, 32)
    for i in range(6):
        toks = np.concatenate([system,
                               rng.integers(0, CFG.vocab_size, 8 + 4 * i)])
        out.append(Request(rid=i, client=f"client{i % 2}", arrival=0.05 * i,
                           prompt_len=len(toks),
                           output_len=int(rng.integers(4, 10)),
                           keywords=("chat",),
                           prompt_tokens=np.asarray(toks, np.int32)))
    return out


def _engine(params, case, tracer=None):
    return ServingEngine(CFG, make_scheduler("fcfs"), params=params,
                         cost_model=CM, max_slots=4, backend="paged",
                         page_size=16, chunked=True, prefill_chunk_tokens=16,
                         keep_first_logits=True, tracer=tracer,
                         **CASES[case])


def _serve(eng, case):
    """Run the case; returns every sampled token in order and each
    request's first-token logits row."""
    sampled = []
    sample = eng._sample

    def spy(row):
        sampled.append(sample(row))
        return sampled[-1]

    eng._sample = spy
    done = eng.run(_reqs(case))
    assert len(done) == 6
    return sampled, {r.rid: r._first_row for r in done}


@pytest.mark.parametrize("case", sorted(CASES))
def test_tokens_bit_identical_with_tracer_on_and_off(params, case):
    off = _serve(_engine(params, case), case)
    tracer = StepTracer(on=True)
    eng = _engine(params, case, tracer)
    on = _serve(eng, case)
    assert on[0] == off[0]
    assert on[1].keys() == off[1].keys()
    for rid, row in off[1].items():
        assert np.array_equal(on[1][rid], row)
    records = tracer.drain()
    _check_step_numbers([r["step"] for r in records], eng.iterations)
    assert tracer.drain() == []


def _check_step_numbers(nums, iterations):
    """One record per ``step()`` call, numbered by the engine's iteration
    count at its start: a call that finds nothing to run does not count
    an iteration, so its number repeats."""
    assert nums == sorted(nums)
    assert set(range(iterations)) <= set(nums) <= set(range(iterations + 1))


def test_tracer_off_builds_nothing_and_keeps_nothing(params, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a tracer that is off touched the profiler "
                             "or the clock")

    monkeypatch.setattr(telemetry, "_profiler", refuse)
    monkeypatch.setattr(telemetry, "time",
                        types.SimpleNamespace(perf_counter_ns=refuse))
    eng = _engine(params, "preempt")
    assert not eng.tracer.on
    assert eng.tracer.span("step.pack") is NO_SPAN
    assert eng.tracer.step(0) is NO_SPAN
    _serve(eng, "preempt")
    assert eng.tracer.records == []
    assert eng.tracer._open == {"span_ns": {}, "counts": {}}


class _Profiler:
    """Stands in for ``jax.profiler``: logs each annotation's entry and
    exit in order."""

    def __init__(self):
        self.log = []

    def _ann(self, name, **kw):
        log = self.log

        class Ann:
            def __enter__(self):
                log.append(("enter", name, kw.get("step_num")))

            def __exit__(self, *exc):
                log.append(("exit", name, None))
        return Ann()

    TraceAnnotation = StepTraceAnnotation = _ann


def _tree(log):
    """Nest the entry/exit log: [(name, step_num, children)]."""
    root, stack = [], []
    for kind, name, num in log:
        if kind == "enter":
            node = (name, num, [])
            (stack[-1][2] if stack else root).append(node)
            stack.append(node)
        else:
            assert stack.pop()[0] == name, "spans overlap without nesting"
    assert not stack
    return root


def test_spans_nest_as_the_step_runs(params, monkeypatch):
    prof = _Profiler()
    monkeypatch.setattr(telemetry, "_profiler", lambda: prof)
    tracer = StepTracer(on=True)
    eng = _engine(params, "preempt", tracer)
    _serve(eng, "preempt")
    tree = _tree(prof.log)
    steps = [n for n in tree if n[0] == "engine.step"]
    assert [n[0] for n in tree if n[0] != "engine.step"] == \
        ["engine.submit"] * 6
    _check_step_numbers([n[1] for n in steps], eng.iterations)
    launched = 0
    for _, _, kids in steps:
        names = [k[0] for k in kids]
        assert all(not k[2] for k in kids)       # one level deep
        if names == CORE + LAUNCH + ["core.lifecycle"]:
            launched += 1
        else:                                   # nothing to run yet
            assert names in (CORE[:1], CORE + ["core.lifecycle"])
    assert launched > 0
    # the records hold the same spans, each within its step's time
    records = tracer.drain()
    assert len(records) == len(steps)
    for rec, (_, _, kids) in zip(records, steps):
        sp = rec["span_ns"]
        assert set(sp) - {"engine.submit"} == \
            {"engine.step"} | {k[0] for k in kids}
        inner = sum(v for k, v in sp.items()
                    if k not in ("engine.step", "engine.submit"))
        assert 0 < inner <= sp["engine.step"]
    assert sum(r["span_ns"].get("engine.submit", 0) > 0
               for r in records) > 0
    assert [r["tw"] for r in records] == sorted(r["tw"] for r in records)


@pytest.mark.parametrize("case", sorted(CASES))
def test_counters_agree_with_independent_counts(params, case, monkeypatch):
    launches = []
    step_fn = engine_mod._paged_decode_step

    def spy(params, tokens, ctx, tables, rmap, *rest, **kw):
        launches.append(tokens.shape[0])
        return step_fn(params, tokens, ctx, tables, rmap, *rest, **kw)

    monkeypatch.setattr(engine_mod, "_paged_decode_step", spy)
    tracer = StepTracer(on=True)
    eng = _engine(params, case, tracer)
    # the rows the step planned and the logits rows the engine read
    planned, reads, per_step = [], [0], []
    run_mixed, install, sample = (eng._run_mixed_paged,
                                  eng._install_prefill, eng._sample)

    def count_rows(plan, decoding):
        if plan or decoding:
            planned.append(sum(c for _, c in plan) + len(decoding))
        return run_mixed(plan, decoding)

    def count_first(req, row):
        reads[0] += 1
        return install(req, row)

    def count_decode(row):
        reads[0] += 1
        return sample(row)

    step = eng.step

    def counted_step():
        n_launch, n_plan, reads[0] = len(launches), len(planned), 0
        out = step()
        # pages the reservations hold, each rounded up on its own
        held = sum(-(-n // eng.pool.page_size)
                   for n in eng.core.reserved.values())
        per_step.append((launches[n_launch:], planned[n_plan:], reads[0],
                         held))
        return out

    eng._run_mixed_paged = count_rows
    eng._install_prefill = count_first
    eng._sample = count_decode
    eng.step = counted_step
    eng.run(_reqs(case))
    records = tracer.drain()
    assert len(records) == len(per_step)
    for rec, (launch, real, n_read, held) in zip(records, per_step):
        c = rec["counts"]
        assert c["waiting"] >= 0
        assert 0 <= c["kv_pages_used"] <= eng.pool.n_pages
        assert c["kv_pages_reserved"] == held
        if not launch:
            assert "rows_launched" not in c
            continue
        (rows,) = launch
        (n_real,) = real
        pad = rows - n_real
        assert c["rows_launched"] == rows == c["logits_rows_fetched"]
        assert c["rows_real"] == n_real
        assert c["rows_real"] + pad == c["rows_launched"]
        assert 0 <= pad < max(n_real, 1)         # a power-of-two bucket
        assert c["logits_rows_used"] == n_read
    assert any(r["counts"].get("rows_launched") for r in records)
    assert any(r["counts"]["kv_pages_reserved"] for r in records)
    # each case does what it is named for
    if case == "preempt":
        assert eng.n_preemptions > 0
    else:
        assert eng.core.prefix_cache.stats.hits > 0


def test_recorder_stamps_share_the_tracer_clock(params):
    rec, tracer = FlightRecorder(), StepTracer(on=True)
    eng = ServingEngine(CFG, make_scheduler("fcfs"), params=params,
                        cost_model=CM, max_slots=4, backend="paged",
                        page_size=16, chunked=True, prefill_chunk_tokens=16,
                        observer=rec, tracer=tracer, **CASES["preempt"])
    eng.run(_reqs("preempt"))
    steps = [(r["tw"], r["tw"] + r["span_ns"]["engine.step"] * 1e-9)
             for r in tracer.drain()]
    arrived = {}
    for ev in rec.events:
        if ev["type"] == "arrival":
            arrived[ev["rid"]] = ev["tw"]
        elif ev["type"] == "admit":
            assert arrived[ev["rid"]] <= ev["tw"]
            assert any(a <= ev["tw"] <= b for a, b in steps)
    assert sorted(arrived) == list(range(6))


def _known_plan(eng):
    """One launch by hand, pages of 16: a 20-token prompt chunk at
    positions 16..35, decode rows at positions 50 and 15, and padding to
    32 rows.  Returns (plan, decoding)."""
    rng = np.random.default_rng(3)
    chunk = Request(rid=0, client="a", arrival=0.0, prompt_len=40,
                    output_len=4, keywords=("chat",),
                    prompt_tokens=rng.integers(0, CFG.vocab_size, 40,
                                               dtype=np.int32))
    chunk.prefill_done = 36
    decoding = []
    for rid, pos in ((1, 50), (2, 15)):
        r = Request(rid=rid, client="b", arrival=0.0, prompt_len=pos,
                    output_len=8, keywords=("chat",))
        r._pos, r._next_token = pos, 1
        eng.pool.ensure(rid, pos)
        decoding.append(r)
    return [(chunk, 20)], decoding


@pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
def test_attn_page_counters_match_hand_count(params, on, monkeypatch):
    """``attn_pages_live``: each launched row's ceil(ctx / page) with ctx
    = position + 1 — what the kernel copies; ``attn_pages_table``: rows
    launched x table width — what a full-width walk reads.  A tracer that
    is off counts nothing."""
    tracer = StepTracer(on=on)
    if not on:
        def refuse(**kw):
            raise AssertionError("a tracer that is off was counted into")
        monkeypatch.setattr(tracer, "count", refuse)
    eng = _engine(params, "prefix", tracer)          # max_len 96: width 8
    eng._run_mixed_paged(*_known_plan(eng))
    if not on:
        assert tracer.records == []
        assert tracer._open == {"span_ns": {}, "counts": {}}
        return
    c = tracer._open["counts"]
    chunk = 16 * 2 + 4 * 3          # positions 16..31: 2 pages; 32..35: 3
    decode = 4 + 1                  # positions 50 (51 tokens) and 15 (16)
    padding = (32 - 22) * 1         # token 0 at position 0: one page each
    assert c["rows_real"] == 22 and c["rows_launched"] == 32
    assert c["attn_pages_live"] == chunk + decode + padding
    assert c["attn_pages_table"] == 32 * 8
