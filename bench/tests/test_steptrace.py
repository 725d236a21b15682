"""The step tracer's readers (``bench/steptrace.py``) and the tool that
runs it (``bench/trace_step.py``).

- Gap naming: a gap is named by the innermost program span that covers
  most of it (not by ``engine.step``, which covers every gap of a step),
  and falls back to the harness's spans where no program span is there.
- The per-iteration readings and the queue waits on hand-made records
  and events, and their None where the program has no tracer.
- On a recorded v5e excerpt with the program's spans: the fused step's
  idle gap splits among ``step.*`` and ``core.*`` spans, the ``step.*``
  spans cover the harness's ``mixed_step``, and ``readers.KERNEL_NAMES``
  matches the same kernel ops as before the Pallas calls were named.
- A CPU rehearsal of the tool's two phases on the tiny configuration.
"""
import json
import types

import pytest

import harness
import readers
import steptrace as st
import trace_step
import tracefile
from config import load_json
from conftest import DATA

# ns: a step [0, 1000] with its parts; the harness's mixed_step around
# the launch; an idle gap [600, 900] under step.fetch and step.widen
PROGRAM = [("engine.step", 0, 1000), ("core.admit", 10, 50),
           ("core.prepare", 50, 60), ("core.plan", 60, 100),
           ("step.pack", 100, 300), ("step.dispatch", 300, 320),
           ("step.wait", 320, 600), ("step.fetch", 600, 800),
           ("step.widen", 800, 880), ("core.lifecycle", 900, 980)]
HARNESS = [("mixed_step", 95, 885), ("admit", 10, 50),
           ("submit", 1100, 1200)]


@pytest.mark.parametrize("gap,name", [
    ((600, 900), "step.fetch"),        # fetch 200 ns, widen 80 ns
    ((810, 870), "step.widen"),
    ((880, 900), "engine.step"),       # between the parts: the step
    ((1100, 1150), "submit"),          # no program span: the harness's
    ((1300, 1400), "none"),
])
def test_gaps_are_named_by_the_innermost_program_span(gap, name):
    assert st.name_gap(gap, PROGRAM, HARNESS) == name


def test_gap_split_and_step_cover():
    assert st.gap_split((600, 900), PROGRAM) == pytest.approx(
        {"step.fetch": 200e-9, "step.widen": 80e-9, "engine.step": 20e-9})
    # step.* cover [100, 880] of the mixed_step [95, 885]
    assert st.covered(PROGRAM, HARNESS[0]) == pytest.approx(780 / 790)
    ops = [("op", 0, 600, "op"), ("op", 900, 1000, "op")]
    (name, secs, split), = st.top_gaps(ops, PROGRAM, HARNESS, 0, 1000)
    assert (name, split) == ("step.fetch", st.gap_split((600, 900),
                                                        PROGRAM))
    assert secs == pytest.approx(300e-9)


def _rec(spans, **counts):
    return {"step": 0, "tw": 0.0, "span_ns": spans, "counts": counts}


RECORDS = [
    _rec({"engine.step": 9e6, "core.admit": 1e6, "core.plan": 1e6,
          "step.fetch": 4e6, "step.widen": 2e6},
         rows_real=5, rows_launched=8, logits_rows_used=3,
         logits_rows_fetched=8),
    _rec({"engine.step": 1e6, "core.admit": 2e6}),     # nothing launched
]


def test_per_iteration_readings():
    assert st.sched_span_ms(RECORDS) == pytest.approx(2.0)
    assert st.fetch_ms(RECORDS) == pytest.approx(3.0)
    assert st.logits_used_share(RECORDS) == pytest.approx(37.5)
    assert st.pad_row_share(RECORDS) == pytest.approx(37.5)


@pytest.mark.parametrize("fn", [st.sched_span_ms, st.fetch_ms,
                                st.logits_used_share, st.pad_row_share])
def test_readings_are_none_without_records(fn):
    assert fn([]) is None
    if fn in (st.logits_used_share, st.pad_row_share):
        assert fn(RECORDS[1:]) is None


def test_queue_waits_count_to_the_last_stamp():
    events = [{"type": "arrival", "rid": 1, "tw": 1.0},
              {"type": "arrival", "rid": 2, "tw": 2.0},
              {"type": "admit", "rid": 1, "tw": 4.0},
              {"type": "first_token", "rid": 1, "tw": 5.0},
              {"type": "admit", "rid": 1, "tw": 6.0},   # after preemption
              {"type": "iteration", "tw": 9.0}]
    assert st.queue_waits(events, [1, 2, 3]) == {1: (3.0, 1.0),
                                                 2: (7.0, 0.0)}
    # a recorder without wall stamps reads nothing
    bare = [{k: v for k, v in e.items() if k != "tw"} for e in events]
    assert st.queue_waits(bare, [1]) == {}


def test_light_waits_split_off_the_window_edge():
    # rid 1 due early, served; rid 2 due 2 s before the end, never
    # served (counts to the drain's end); rid 3 not light; rid 4 due
    # after the window
    specs = [{"ttft": True, "due": 1.0}, {"ttft": True, "due": 18.0},
             {"ttft": False, "due": 2.0}, {"ttft": True, "due": 21.0}]
    reqs = [types.SimpleNamespace(rid=i) for i in (1, 2, 3, 4)]
    out = types.SimpleNamespace(
        reqs=reqs, specs=specs, drain_s=30.0,
        stamps={1: [4.0, 4.5], 2: [], 3: [3.0], 4: []})
    events = [{"type": "arrival", "rid": 1, "tw": 101.0},
              {"type": "admit", "rid": 1, "tw": 103.0},
              {"type": "first_token", "rid": 1, "tw": 104.0},
              {"type": "arrival", "rid": 2, "tw": 118.0},
              {"type": "iteration", "tw": 150.0}]
    w = trace_step.light_waits(out, events, seconds=20.0, edge=10.0)
    assert w["by_rid"] == {1: [1.0, 3.0, 2.0, 1.0],
                           2: [18.0, 32.0, 32.0, 0.0]}
    assert w["early"]["n"] == 1 and w["all"]["n"] == 2
    assert w["early"]["wait_share"] == pytest.approx(2.0 / 3.0)
    assert w["all"]["wait_share"] == pytest.approx(34.0 / 35.0)
    assert w["early"]["queue_wait_p95_s"] == pytest.approx(2.0)
    # nothing recorded on the wall clock: no waits, nothing raised
    bare = [{k: v for k, v in e.items() if k != "tw"} for e in events]
    none = trace_step.light_waits(out, bare, seconds=20.0, edge=10.0)
    assert none["all"] == {"n": 0, "ttft_p95_s": None,
                           "queue_wait_p95_s": None, "wait_share": None}


def _recorded(name):
    d = load_json(DATA / name)
    return tracefile.from_json(d), d["window"]


def test_recorded_gap_splits_among_the_program_spans():
    """A granite2b.tenants excerpt from a v5e: the longest idle gap, 73 ms
    after a 256-row step, which the harness's spans can only call
    ``mixed_step``."""
    tr, (t0, t1) = _recorded("recorded_trace_spans.json")
    program, spans = st.split_program(tr.spans)
    ops = tr.ops[0]
    (name, secs, split), = st.top_gaps(ops, program, spans, t0, t1, 1)
    assert tracefile.top_gaps(ops, spans, t0, t1, 1)[0][0] == "mixed_step"
    assert name == "step.widen" and secs == pytest.approx(0.073360249)
    assert split["step.widen"] == pytest.approx(0.048464966)
    assert split["step.fetch"] == pytest.approx(0.011909639)
    assert split["step.wait"] == pytest.approx(0.00241095)
    assert split["core.lifecycle"] == pytest.approx(0.004913979)
    # only the moments between two steps lie outside every program span
    assert 0.99 * secs < sum(split.values()) <= secs
    complete = [s for s in spans if s[0] == "mixed_step" and s[2] < t1]
    assert [round(st.covered(program, s), 4) for s in complete] == [0.9996]


def test_named_kernel_matches_the_same_ops():
    def matched(name):
        tr, _ = _recorded(name)
        return sorted(o[0].split(" = ")[0] for o in tracefile.work_ops(
            tr.ops[0]) if tracefile.matches(o, readers.KERNEL_NAMES))

    before = matched("recorded_trace.json")
    after = matched("recorded_trace_spans.json")
    assert after == [n.replace("%paged_attention.", "%paged_attention_"
                               "splitk.") for n in before]
    assert "%multiply_reduce_fusion.4" in after


SEED = 2**33 + 5
SECONDS = 3.0


@pytest.fixture(scope="module")
def tiny():
    c = load_json(DATA / "tiny.json")
    return c, load_json(DATA / "tiny_mix.json"), harness.Session(c)


def test_tool_cost_phase_on_the_cpu(tiny):
    c, mix, sess = tiny
    lines = list(trace_step.cost(sess, c, mix, [SEED], SECONDS,
                                 harness.CompileCount()))
    assert [ln["tracer"] for ln in lines] == [False, True]
    assert all(ln["tokens_per_s"] > 0 and ln["compiles_in_window"] == 0
               for ln in lines)
    assert lines[1]["records"] > 0
    waits = lines[1]["light_waits"]
    assert waits["all"]["n"] >= waits["early"]["n"]
    assert waits["all"]["n"] == len(waits["by_rid"]) > 0
    assert 0 <= waits["all"]["wait_share"] <= 1.0 + 1e-9
    assert "light_waits" not in lines[0]
    kv = lines[1]["kv_pages"]
    assert 0 < kv["kv_pages_used"] and 0 < kv["kv_pages_reserved"]
    ns = trace_step.host_ns(200)
    assert set(ns) == {"span_off", "span_on", "step_on", "counts_on"}


def test_tool_profile_phase_on_the_cpu(tiny, monkeypatch, tmp_path):
    c, mix, sess = tiny
    monkeypatch.setattr(trace_step, "ROOT", tmp_path)
    line = trace_step.profile("granite2b.tenants", sess, c, mix, SEED,
                              SECONDS, harness.CompileCount(), {})
    out = tmp_path / "bench_out" / "traces" / f"granite2b.tenants.{SEED}"
    steps = json.loads((out / "steps.json").read_text())
    flight = json.loads((out / "flight.json").read_text())
    assert len(steps) == line["records"] > 0
    assert all("tw" in e for e in flight["events"])
    assert line["traced_iterations"] > 0
    for v in line["steptrace"].values():
        assert v["window"] is not None and v["window"] >= 0
    # on the CPU the profile holds no device ops: the one gap is the
    # whole slice, named by a program span
    assert line["program_gaps"][0][0] in st.PROGRAM_SPANS
    assert line["light_waits"]["all"]["queue_wait_p95_s"] >= 0
    assert all(0 < x <= 1.0 + 1e-9 for x in line["mixed_step_cover"])
